"""Pluggable search backends: how one device scores queries against its
index rows (the serving mirror of core/loss.py's LossBackend).

A ``SearchBackend`` computes exact top-k over one index block:

  * ``dense`` (default) — blocked matmul + running ``lax.top_k`` merge
    (``jax.lax.scan`` over column blocks of ``block`` rows): never
    materializes the (Q, N) score matrix. Where k groups' candidates fill
    at most three quarters of a block (``merge_group``: groups of 32
    columns, 4·k·32 <= 3·block) the merge is exact and two-stage: the
    block's k best column groups by their maxima, then ``lax.top_k`` over
    the running best and those groups' k·32 columns, re-sorted into id
    order so that ties still break toward the lowest id; peak transient is
    the (Q, block) tile plus the (Q, k·32) candidates. Other shapes keep
    one ``lax.top_k`` over the running best and the whole tile.
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


# Columns per group of the two-stage block merge: on a TPU v5e at the
# serving cells' shapes (Q 32, block 65,536, k 100) a search's block took
# 0.39 ms with 32, 0.47 with 64, 0.51 with 16, 0.78 with 128 and 0.95 with
# one top_k (the 2,048 group maxima and the 3,200 candidates balance).
GROUP = 32


def merge_group(block: int, k: int) -> Optional[int]:
    """Columns per group of the two-stage block merge, or None where one
    ``top_k`` over the whole block is kept: ``GROUP``, when it divides
    ``block`` and the k groups' candidates are at most 3/4 of the block.
    On a TPU v5e (block 65,536; Q 1, 32 and 256; k 32 to 2,048) the
    two-stage merge took 0.24-0.99 of one ``top_k``'s time up to
    k·32 = 3/4 block, and 1.01-1.23 of it at k·32 = block."""
    return GROUP if block % GROUP == 0 and 4 * k * GROUP <= 3 * block else None


def _topk_merge(best_s, best_i, cand_s, cand_i, k):
    """Top k of the running best followed by the candidates, with their
    ids. The running best goes first: ties break toward earlier column
    blocks, matching ``lax.top_k`` over the full row."""
    cat_s = jnp.concatenate([best_s, cand_s], axis=1)
    cat_i = jnp.concatenate([best_i, cand_i], axis=1)
    top_s, pos = jax.lax.top_k(cat_s, k)
    return top_s, jnp.take_along_axis(cat_i, pos, axis=1)


def _merge(best_s, best_i, s, ids, k, g):
    """Running (Q, k) best merged with one block's masked (Q, block) scores
    and its (block,) ids, over groups of ``g`` columns where g is set."""
    q, block = s.shape
    if g is None:
        return _topk_merge(best_s, best_i, s, jnp.broadcast_to(ids, s.shape), k)
    with jax.named_scope("group_max"):
        grouped = s.reshape(q, block // g, g)
        group_max = grouped.max(axis=2)
    with jax.named_scope("group_select"):
        # groups back in id order: the final top_k breaks ties by position
        sel = jnp.sort(jax.lax.top_k(group_max, k)[1], axis=1)
    with jax.named_scope("candidate_topk"):
        cand_s = jnp.take_along_axis(grouped, sel[:, :, None], axis=1)
        cand_i = ids.reshape(block // g, g)[sel]
        return _topk_merge(best_s, best_i, cand_s.reshape(q, k * g),
                           cand_i.reshape(q, k * g), k)


@dataclasses.dataclass(frozen=True)
class DenseSearchBackend:
    """Blocked-scan exact top-k: one (Q, block) score tile at a time.

    Each block is merged into the running (Q, k) best in two exact stages
    where ``merge_group(block, k)`` gives a group size G (``GROUP``, 32,
    when it divides the block and 4·k·G <= 3·block): the maximum of each
    contiguous group of G columns, ``top_k`` of those maxima to pick k
    groups, then ``top_k`` over the running best and those k·G candidates.
    Every element at or above a row's k-th value lies in a group whose
    maximum is too, and at most k groups rank above it (lower-indexed
    groups win ties, and hold lower ids), so the selection is that of one
    ``top_k`` over the block. The picked groups are sorted back into id
    order before the final ``top_k``, which breaks ties by position. Peak
    transient: the (Q, block) tile plus the (Q, k·G) candidates. Other
    shapes keep one ``top_k`` over the running best and the whole block.
    On a TPU v5e the merge's sorts are stable; one ``top_k`` over a whole
    block at k 512 is not, and there returned equal scores higher id
    first."""

    block: int = 65536

    name = "dense"

    def merge_width(self, k: int) -> int:
        """Columns each full block's merge sorts: the group maxima and the
        running best with the candidates, or the running best and the
        block."""
        g = merge_group(self.block, k)
        return self.block + k if g is None else self.block // g + k + k * g

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
        g = merge_group(block, k)

        def body(carry, inp):
            blk, vld, b0 = inp
            s = jax.lax.dot_general(
                q_reps, blk, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            ids = b0 + jnp.arange(block, dtype=jnp.int32)
            s = jnp.where(vld[None, :], s, NEG_INF)
            ids = jnp.where(vld, ids, -1)
            with jax.named_scope("block_topk"):
                return _merge(*carry, s, ids, k, g), None

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
