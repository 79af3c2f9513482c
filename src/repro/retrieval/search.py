"""Pluggable search backends: how one device scores queries against its
index rows (the serving mirror of core/loss.py's LossBackend).

A ``SearchBackend`` computes exact top-k over one index block:

  * ``dense`` (default) — blocked matmul + running ``lax.top_k`` merge
    (``jax.lax.scan`` over column blocks of ``block`` rows): never
    materializes the (Q, N) score matrix, peak transient is the (Q, block)
    tile plus the (Q, k) running best.
  * ``fused`` — the blocked Pallas kernel (kernels/fused_topk): QK^T tiles
    stream through VMEM with an in-kernel running top-k, reusing the
    fused-infonce streaming machinery. Runs under ``interpret=True`` off-TPU
    so the whole serving matrix is CPU-testable.

Shared contract (pinned by tests/test_retrieval.py):

  * scores come back fp32 whatever dtype queries/index arrive in (bf16
    compute/index under the bf16 policies) — the serving counterpart of the
    LossBackend fp32-stats contract;
  * ids are *local* column indices, int32, ties broken toward the lowest id
    (``lax.top_k`` over the full row); the Retriever adds the shard's global
    row offset;
  * ``col_valid`` masks columns exactly (corpus padding, unfilled shard
    slots); slots with no valid candidate (k > n_valid) return score
    ``NEG_INF`` and id ``-1``.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Protocol, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.precision import SCORE_DTYPE
from repro.kernels.fused_infonce.fused_infonce import NEG_INF


class SearchBackend(Protocol):
    """Exact top-k of one query block against one index block."""

    name: str

    def topk(
        self,
        q_reps: jnp.ndarray,     # (Q, d) query representations
        index: jnp.ndarray,      # (N, d) index rows (this device's block)
        k: int,
        *,
        col_valid: Optional[jnp.ndarray] = None,  # (N,) bool
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (scores (Q, k) fp32, ids (Q, k) int32, -1 = empty)."""
        ...


@dataclasses.dataclass(frozen=True)
class DenseSearchBackend:
    """Blocked-scan exact top-k: one (Q, block) score tile at a time."""

    block: int = 65536

    name = "dense"

    def topk(self, q_reps, index, k, *, col_valid=None):
        n = index.shape[0]
        block = max(min(self.block, n), 1)
        n_blocks = (n + block - 1) // block
        pad = n_blocks * block - n
        valid = (
            jnp.ones((n,), bool) if col_valid is None else col_valid
        )
        if pad:
            index = jnp.pad(index, ((0, pad), (0, 0)))
            valid = jnp.pad(valid, (0, pad))
        blocks = index.reshape(n_blocks, block, -1)
        vblocks = valid.reshape(n_blocks, block)
        q = q_reps.shape[0]

        def body(carry, inp):
            best_s, best_i = carry
            blk, vld, b0 = inp
            s = jax.lax.dot_general(
                q_reps, blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ids = b0 + jnp.arange(block, dtype=jnp.int32)
            s = jnp.where(vld[None, :], s, NEG_INF)
            ids = jnp.where(vld, ids, -1)
            # running best first: ties break toward earlier column blocks,
            # matching lax.top_k over the full row
            with jax.named_scope("block_topk"):
                cat_s = jnp.concatenate([best_s, s], axis=1)
                cat_i = jnp.concatenate(
                    [best_i, jnp.broadcast_to(ids[None, :], s.shape)], axis=1
                )
                top_s, pos = jax.lax.top_k(cat_s, k)
                return (top_s, jnp.take_along_axis(cat_i, pos, axis=1)), None

        init = (
            jnp.full((q, k), NEG_INF, SCORE_DTYPE),
            jnp.full((q, k), -1, jnp.int32),
        )
        offsets = jnp.arange(n_blocks, dtype=jnp.int32) * block
        (scores, ids), _ = jax.lax.scan(body, init, (blocks, vblocks, offsets))
        return scores, ids


@dataclasses.dataclass(frozen=True)
class FusedSearchBackend:
    """Blocked Pallas QK^T + in-kernel running top-k (kernels/fused_topk).
    ``interpret=None`` auto-selects: compiled on TPU, interpreter elsewhere."""

    block_q: int = 128
    block_n: int = 128
    interpret: Optional[bool] = None

    name = "fused"

    def topk(self, q_reps, index, k, *, col_valid=None):
        from repro.kernels.fused_topk.ops import fused_topk_scores

        return fused_topk_scores(
            q_reps, index, k, col_valid=col_valid,
            block_q=self.block_q, block_n=self.block_n,
            interpret=self.interpret,
        )


SEARCH_BACKENDS = {"dense": DenseSearchBackend, "fused": FusedSearchBackend}


def resolve_search_backend(
    spec: Union[None, str, SearchBackend] = None, **kwargs
) -> SearchBackend:
    """None -> dense; a registered name -> fresh instance (kwargs forwarded);
    an instance -> as is. Raises ValueError for unknown names."""
    if spec is None:
        return DenseSearchBackend(**kwargs)
    if isinstance(spec, str):
        if spec not in SEARCH_BACKENDS:
            raise ValueError(
                f"unknown search_impl {spec!r}; one of {sorted(SEARCH_BACKENDS)}"
            )
        return SEARCH_BACKENDS[spec](**kwargs)
    return spec
