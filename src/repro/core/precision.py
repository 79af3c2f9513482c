"""PrecisionPolicy: one object owning every dtype decision of the stack.

The paper's premise is training dense retrievers *under a memory constraint*,
and mixed precision is the memory-scaling axis GradCache (Gao et al., 2021)
and Inf-CL ("Breaking the Memory Barrier", Cheng et al., 2024) treat as table
stakes. Before this module the reproduction's dtypes were scattered implicit
fp32 assumptions (encoder activations, bank buffers, optimizer moments, loss
statistics); now every layer consumes a single ``PrecisionPolicy``:

  * ``param_dtype``   — dtype of the *stored* parameters (the train state).
    All shipped presets keep this fp32: the stored params are the AdamW
    master weights, and the encoders cast them to ``compute_dtype`` at
    application (bf16 "compute copies" are transient, never stored). True
    low-precision param storage is supported by
    ``optim.adamw(keep_master_params=True)``, which then carries the fp32
    masters inside the optimizer state instead.
  * ``compute_dtype`` — encoder activations, representations (including the
    rep-cache store of the ``rep_cache`` backprop strategy) and the q/p/bank
    inputs of both loss backends.
  * ``bank_dtype``    — the FIFO memory-bank ring buffers
    (``core/memory_bank.py``); halves persistent bank HBM again on top of
    bank sharding (bank bytes / (2·D)).
  * ``accum_dtype``   — softmax statistics (logits, lse, per-row losses),
    VJP accumulation inside the fused Pallas kernel, metric reductions and
    gradient accumulation arithmetic. Always fp32 in the shipped presets:
    low-precision *statistics* change the optimization trajectory, while
    low-precision *inputs* only perturb it within rounding tolerance
    (tests/test_precision.py pins both properties).

Presets::

    fp32        params fp32 | compute fp32 | banks fp32 | accum fp32
    bf16        params fp32 | compute bf16 | banks fp32 | accum fp32
    bf16_banks  params fp32 | compute bf16 | banks bf16 | accum fp32

``fp32`` is bit-identical to the historical behavior (every cast is an
identity). Select with ``ContrastiveConfig(precision=...)`` (a preset name or
a ``PrecisionPolicy`` instance), ``--precision`` on both train drivers, or a
shape cell's ``"precision"`` param.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Union

import jax
import jax.numpy as jnp


#: Named contract dtypes. These are the *non-negotiable* fp32 anchors of the
#: stack — unlike the policy fields above they never vary per preset, and
#: call sites reference them by contract name so a reader (and reprolint's
#: RPL001) can tell a deliberate fp32 pin from a forgotten policy bypass.
#:
#: STATS_DTYPE — every statistic that feeds logging or control decisions
#:   (loss, accuracy, bank fill, retrieval recall) is cast here *before* the
#:   reduction; low-precision statistics change the trajectory, not just
#:   perturb it (tests/test_precision.py).
#: SCORE_DTYPE — retrieval similarity scores and top-k merge buffers; a bf16
#:   score merge reorders near-ties across shards and breaks exact/sharded
#:   search equivalence (tests/test_retriever.py).
#: MASTER_DTYPE — AdamW master weights and moments (optim/ keeps its own
#:   literal copy: importing this module from optim/ would cycle through
#:   repro.core.__init__ -> step_program -> optim).
STATS_DTYPE = jnp.float32
SCORE_DTYPE = jnp.float32
MASTER_DTYPE = jnp.float32


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Dtype assignments for one training run (see module docstring)."""

    name: str = "fp32"
    param_dtype: Any = jnp.float32
    compute_dtype: Any = jnp.float32
    bank_dtype: Any = jnp.float32
    accum_dtype: Any = jnp.float32

    def cast_compute(self, x):
        """Cast an array (or None) to the compute dtype; identity under fp32."""
        if x is None:
            return None
        return x.astype(self.compute_dtype)


PRECISION_PRESETS = {
    "fp32": PrecisionPolicy(name="fp32"),
    "bf16": PrecisionPolicy(name="bf16", compute_dtype=jnp.bfloat16),
    "bf16_banks": PrecisionPolicy(
        name="bf16_banks", compute_dtype=jnp.bfloat16, bank_dtype=jnp.bfloat16
    ),
}

_FP32 = PRECISION_PRESETS["fp32"]


def resolve_precision(
    spec: Union[None, str, PrecisionPolicy] = None,
) -> PrecisionPolicy:
    """None -> fp32; a preset name -> the registered policy; an instance ->
    as is. Raises ValueError for unknown names (surfaced at program build)."""
    if spec is None:
        return _FP32
    if isinstance(spec, str):
        if spec not in PRECISION_PRESETS:
            raise ValueError(
                f"unknown precision {spec!r}; one of {sorted(PRECISION_PRESETS)}"
            )
        return PRECISION_PRESETS[spec]
    return spec


def bank_bytes_per_device(
    capacity_q: int,
    capacity_p: int,
    rep_dim: int,
    policy: Union[None, str, PrecisionPolicy] = None,
    *,
    shards: int = 1,
) -> int:
    """Persistent dual-bank buffer bytes per device: the memory axis this
    policy exists to cut. ``shards`` is the DP shard count under
    ``cfg.shard_banks`` (1 = replicated). Counts the representation buffers
    only — the valid/age sidecars are capacity-proportional but d-free."""
    policy = resolve_precision(policy)
    itemsize = jnp.dtype(policy.bank_dtype).itemsize
    return ((capacity_q + capacity_p) * rep_dim * itemsize) // max(shards, 1)
