"""Shared types for the contrastive update builders."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.memory_bank import BankState


class RetrievalBatch(NamedTuple):
    """One global batch of training examples.

    query:        pytree, leaves (B, ...)   — tokenized queries
    passage_pos:  pytree, leaves (B, ...)   — the positive passage per query
    passage_hard: pytree, leaves (B, H, ...) or None — H hard negatives/query
    """

    query: Any
    passage_pos: Any
    passage_hard: Optional[Any] = None


class DualEncoder(NamedTuple):
    """Abstract dual encoder; the encode fns take the full params dict.

    ``params`` is a dict with keys 'query' and 'passage', or, for a shared
    tower (``shared=True``), the key 'shared' alone: one backbone, held once,
    whose gradient is the sum of what the query side and the passage side
    contribute (core/step_program.py). ``encode_counters(params, side,
    batch) -> (reps, counters)``, where given, is the encode call the update
    uses: ``side`` is 'query' or 'passage', and ``counters`` a dict of
    arrays that the update sums over its encode calls and reports in
    ``StepMetrics.tower``."""

    init: Callable[..., Any]                       # (rng, ...) -> params
    encode_query: Callable[[Any, Any], jnp.ndarray]    # (params, batch.query) -> (B, d)
    encode_passage: Callable[[Any, Any], jnp.ndarray]  # (params, passages) -> (B, d)
    rep_dim: int
    shared: bool = False
    encode_counters: Optional[Callable[[Any, str, Any], Any]] = None


@dataclasses.dataclass(frozen=True)
class ContrastiveConfig:
    """Configuration of the contrastive update (paper Secs. 3.1-3.2).

    The update is a composition *negative source x backprop strategy*
    (core/step_program.py). Either name a registered composition with
    ``method=`` (legacy strings: 'dpr', 'grad_accum', 'grad_cache',
    'contaccum'; new: 'contcache', 'prebatch', 'prebatch_cache',
    'dpr_xdev', 'mined'/'mined_accum'/'mined_cache'), or set the axes
    explicitly:

    negatives: 'in_batch' | 'mined' | 'gathered' | 'dual_bank' |
        'passage_bank' (None -> resolved from ``method``). 'mined' marks the
        asynchronously-mined hard negatives of repro/mining: the miner's
        table is joined into every batch as extra passage_hard columns at
        assembly time (data/loader.py), so inside the program the source
        behaves exactly like 'in_batch'.
    backprop: 'direct' | 'scan' | 'rep_cache'
        (None -> resolved from ``method``). An explicitly set axis overrides
        the corresponding half of ``method``.
    accumulation_steps: K. Global batch B must be divisible by K.
    bank_size: N_memory (equal for both banks unless overridden — the paper's
        dual-bank symmetry; ``bank_size_q``/``bank_size_p`` override for the
        pre-batch-negatives ablation by *disabling* one bank. Unequal
        non-zero capacities are rejected for dual-bank sources: the rings
        stop being slot-aligned as soon as either wraps).
    reset_banks_each_update: 'w/o past encoder' ablation (Table 2).
    use_query_bank: False reproduces pre-batch negatives (w/o M_q, Table 2).
    loss_impl: 'dense' | 'fused' — how the loss's softmax statistics are
        computed (core/loss.py LossBackend). 'dense' (default) materializes
        the (M, N) logits block; 'fused' streams it through the blocked
        online-softmax Pallas kernel (gradient-exact, never materialized).
        Composes with every negatives/backprop setting.
    precision: a PrecisionPolicy or preset name ('fp32' | 'bf16' |
        'bf16_banks', core/precision.py) governing every dtype of the update:
        encoder compute copies, representations (incl. the rep_cache store),
        bank buffers and the loss-backend inputs. Softmax statistics, metric
        reductions and gradient accumulation stay in ``accum_dtype`` (fp32 in
        every preset) regardless. 'fp32' (default) is bit-identical to the
        historical all-fp32 behavior; orthogonal to negatives/backprop,
        loss_impl and shard_banks.
    bank_dtype: explicit memory-bank buffer dtype override; None (default)
        defers to ``precision`` (the normal path — set the policy, not this).
    shard_banks: shard the memory banks across the DP mesh instead of
        replicating them. Each device owns a ``bank_size / D`` contiguous
        block of ring slots (memory_bank.shard_push); the loss gathers the
        passage-bank columns over ``dp_axis`` and evaluates only the local
        query-bank rows. Identical math to replicated banks (trajectory
        parity in tests/test_distributed.py) at 1/D the per-device bank HBM.
        Requires ``dp_axis``; only meaningful under shard_map with the bank
        leaves sharded by ``memory_bank.bank_spec`` /
        ``distribution.sharding.contrastive_state_spec``.
    loss_comm: 'all_gather' | 'ring' — how sharded bank columns reach the
        loss (core/loss.py). 'all_gather' (default) gathers the full
        (N_mem, d) passage-column block before every loss eval: O(N_mem*d)
        transient memory per device, flat in D. 'ring' streams the D shards
        around the DP ring with ppermute, merging each N_mem/D chunk into the
        carried online-softmax state: exactly the same loss/gradients (fp
        summation-order tolerance) at O(N_mem*d/D) transient memory. Requires
        ``shard_banks`` (and hence ``dp_axis``) plus a bank-consuming
        negatives source; validated at program build.
    """

    method: str = "contaccum"
    negatives: Optional[str] = None
    backprop: Optional[str] = None
    temperature: float = 1.0
    accumulation_steps: int = 1
    bank_size: int = 0
    bank_size_q: Optional[int] = None
    bank_size_p: Optional[int] = None
    use_query_bank: bool = True
    reset_banks_each_update: bool = False
    grad_clip_norm: float = 2.0
    bank_dtype: Any = None
    loss_impl: str = "dense"
    # PrecisionPolicy preset name or instance (core/precision.py); 'fp32'
    # reproduces the historical all-fp32 behavior bit-for-bit.
    precision: Any = "fp32"
    # Cross-device negatives: name(s) of mesh axes to all-gather representations
    # over; None means single-device semantics.
    dp_axis: Optional[Any] = None
    # Shard the memory banks over dp_axis (capacity/D rows per device)
    # instead of replicating them; see the class docstring.
    shard_banks: bool = False
    # How sharded bank columns reach the loss: 'all_gather' materializes the
    # global block, 'ring' streams shards around the DP ring (1/D transient
    # memory); see the class docstring.
    loss_comm: str = "all_gather"

    def resolved_precision(self):
        """The PrecisionPolicy this config runs under (presets resolved)."""
        from repro.core.precision import resolve_precision

        return resolve_precision(self.precision)

    def resolved_bank_dtype(self):
        """Bank buffer dtype: explicit ``bank_dtype`` override, else the
        precision policy's ``bank_dtype``."""
        if self.bank_dtype is not None:
            return self.bank_dtype
        return self.resolved_precision().bank_dtype

    def resolved_bank_sizes(self):
        nq = self.bank_size if self.bank_size_q is None else self.bank_size_q
        np_ = self.bank_size if self.bank_size_p is None else self.bank_size_p
        if not self.use_query_bank:
            nq = 0
        return nq, np_

    def resolved_composition_names(self):
        """(negatives, backprop) names after legacy-``method`` resolution."""
        from repro.core.step_program import method_composition

        neg, bp = self.negatives, self.backprop
        if neg is None or bp is None:
            legacy = method_composition(self.method)
            neg = neg or legacy[0]
            bp = bp or legacy[1]
        return neg, bp


class ContrastiveState(NamedTuple):
    step: jnp.ndarray
    params: Any
    opt_state: Any
    bank_q: BankState
    bank_p: BankState


class StepMetrics(NamedTuple):
    """One update's metrics. For a shared tower, ``grad_norm_query`` and
    ``grad_norm_passage`` are the norms of each side's contribution to the
    one gradient (through the query encodings, through the passage
    encodings), and ``grad_norm`` the norm of their sum. ``tower`` holds the
    encoder's counters summed over the update (an array counter as its
    ``_max`` and ``_mean``); empty for encoders without counters."""

    loss: jnp.ndarray
    accuracy: jnp.ndarray
    grad_norm: jnp.ndarray
    grad_norm_query: jnp.ndarray
    grad_norm_passage: jnp.ndarray
    grad_norm_ratio: jnp.ndarray  # ||grad_passage|| / ||grad_query|| (paper Fig. 5)
    n_negatives: jnp.ndarray      # negatives per query row actually used
    bank_fill_q: jnp.ndarray
    bank_fill_p: jnp.ndarray
    tower: Any = {}


def subtree_norm(grads: Any, key: str) -> jnp.ndarray:
    from repro.common.treemath import tree_global_norm

    if isinstance(grads, dict) and key in grads:
        return tree_global_norm(grads[key])
    return jnp.zeros(())


def chunk_tree(tree: Any, k: int) -> Any:
    """Reshape every leaf (B, ...) -> (K, B//K, ...)."""

    def _r(x):
        b = x.shape[0]
        assert b % k == 0, f"global batch {b} not divisible by K={k}"
        return x.reshape((k, b // k) + x.shape[1:])

    return jax.tree_util.tree_map(_r, tree)


def flatten_hard(hard: Any) -> Any:
    """(B, H, ...) -> (B*H, ...) for encoding."""

    def _f(x):
        return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])

    return jax.tree_util.tree_map(_f, hard)
