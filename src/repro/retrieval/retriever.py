"""Retriever: one sharded, precision-aware inference surface.

The serving mirror of the StepProgram design (core/step_program.py): a
``Retriever`` composes three pluggable layers —

  * an ``IndexStore`` (index.py) — the encoded corpus in the policy's index
    dtype, replicated or sharded row-blocks over the DP mesh;
  * a ``SearchBackend`` (search.py) — dense blocked-scan vs the fused Pallas
    QK^T + running-top-k kernel;
  * the query tower of the training ``DualEncoder`` — the *same* params,
    precision policy and (under shard_map) mesh machinery as training, which
    is what ANCE-style periodic re-encode/search requires.

Replicated layout: one jitted ``encode -> topk`` program. Sharded layout:
the same program under shard_map — each device scores its local ``rows/D``
index block (gather-free: the index never moves), candidates merge with one
psum (each shard deposits its (Q, k) block into its slice of a zeros
(Q, D, k) buffer; the psum assembles all slices, a final ``top_k`` over the
D*k candidates reduces them). Slices are shard-major, so ties still break
toward the lowest global id — sharded ids match replicated bit-for-bit
(tests/test_retrieval.py).

Select everything from ``RetrieverConfig``: top-k, search backend, index
layout, precision. ``launch/serve.py`` exposes the same axes as CLI flags.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dist import DistCtx
from repro.core.precision import PrecisionPolicy, resolve_precision
from repro.core.types import DualEncoder
from repro.kernels.fused_infonce.fused_infonce import NEG_INF
from repro.retrieval.index import IndexStore, build_index_store
from repro.retrieval.search import SearchBackend, resolve_search_backend


@dataclasses.dataclass(frozen=True)
class RetrieverConfig:
    """Configuration of the inference surface (mirrors ContrastiveConfig).

    top_k: results per query.
    search_impl: 'dense' | 'fused' — how one device scores its index block
        (search.py SearchBackend; same switch shape as cfg.loss_impl).
    index_layout: 'replicated' | 'sharded' — whether every device holds all
        N index rows or a contiguous rows/D block over the DP mesh
        (requires a mesh; same lever as cfg.shard_banks).
    precision: PrecisionPolicy or preset name (core/precision.py). Queries
        are scored in ``compute_dtype``, the index is stored in
        ``bank_dtype`` (persistent HBM, like the bank rings), scores are
        always fp32 (the backend contract).
    index_dtype: explicit index-buffer dtype override; None defers to the
        policy (set the policy, not this — mirrors cfg.bank_dtype).
    score_block: dense backend column-block size (peak transient is
        Q x score_block).
    block_q/block_n: fused backend VMEM tile sizes.
    encode_batch: offline corpus-encode batch (one compiled shape).
    dp_axis: mesh axis name the sharded layout shards over.
    """

    top_k: int = 20
    search_impl: str = "dense"
    index_layout: str = "replicated"
    precision: Any = "fp32"
    index_dtype: Any = None
    score_block: int = 65536
    block_q: int = 128
    block_n: int = 128
    encode_batch: int = 256
    dp_axis: str = "data"

    def resolved_precision(self) -> PrecisionPolicy:
        return resolve_precision(self.precision)

    def resolved_index_dtype(self):
        if self.index_dtype is not None:
            return self.index_dtype
        return self.resolved_precision().bank_dtype

    def resolve_backend(self) -> SearchBackend:
        if self.search_impl == "dense":
            return resolve_search_backend("dense", block=self.score_block)
        if self.search_impl == "fused":
            return resolve_search_backend(
                "fused", block_q=self.block_q, block_n=self.block_n
            )
        return resolve_search_backend(self.search_impl)


def make_dp_mesh(dp: int, axis: str = "data"):
    """A 1-D DP mesh over the first ``dp`` local devices (the serving
    counterpart of launch/train.py's --dp mesh)."""
    from jax.sharding import Mesh

    if jax.device_count() < dp:
        raise ValueError(
            f"sharded index needs >= {dp} devices (have {jax.device_count()}; "
            f"on CPU set XLA_FLAGS=--xla_force_host_platform_device_count={dp})"
        )
    return Mesh(np.array(jax.devices()[:dp]), (axis,))


class Retriever:
    """Built from the training stack's pieces: a DualEncoder (+ its params,
    typically restored from a trainer checkpoint — serving.load_trained_params),
    a RetrieverConfig, and (for the sharded layout) the DP mesh."""

    def __init__(
        self,
        encoder: DualEncoder,
        params: Any,
        cfg: RetrieverConfig = RetrieverConfig(),
        *,
        mesh=None,
        index: Optional[IndexStore] = None,
    ):
        if cfg.index_layout not in ("replicated", "sharded"):
            raise ValueError(
                f"unknown index_layout {cfg.index_layout!r}; "
                "one of ['replicated', 'sharded']"
            )
        if cfg.index_layout == "sharded" and mesh is None:
            raise ValueError(
                "index_layout='sharded' needs a mesh (make_dp_mesh(D)); "
                "the index rows shard over its DP axis"
            )
        self.encoder = encoder
        self.params = params
        self.cfg = cfg
        self.mesh = mesh
        self.backend = cfg.resolve_backend()
        self.policy = cfg.resolved_precision()
        self.shards = (
            int(mesh.shape[cfg.dp_axis]) if cfg.index_layout == "sharded" else 1
        )
        self.index = index
        self._encode_p = jax.jit(encoder.encode_passage)
        self._search_tokens = None   # jit cache, built on first search
        self._search_reps = None

    # ---------------------------------------------------------- index build
    def build_index(self, passages: np.ndarray) -> IndexStore:
        """Offline corpus build with the passage tower (fixed-batch encode,
        index rows stored in the policy's index dtype). Under the sharded
        layout the store is *placed* sharded — each device holds only its
        rows/D block persistently (the 1/D HBM claim), and search consumes
        it without resharding. Rebuilding with the current ``self.params``
        is the ANCE periodic re-encode; the jitted search programs persist
        across rebuilds (they retrace only if the index shape changes)."""
        store = build_index_store(
            lambda toks: self._encode_p(self.params, jnp.asarray(toks)),
            passages,
            batch=self.cfg.encode_batch,
            dtype=self.cfg.resolved_index_dtype(),
            shards=self.shards,
        )
        if self.cfg.index_layout == "sharded":
            # one device_put straight from the host store into the sharded
            # layout: each device pulls only its rows/D block — the full
            # matrix never lands on any single device
            from jax.sharding import NamedSharding, PartitionSpec as P

            ax = self.cfg.dp_axis
            store = store._replace(
                reps=jax.device_put(
                    store.reps, NamedSharding(self.mesh, P(ax, None))
                ),
                row_valid=jax.device_put(
                    store.row_valid, NamedSharding(self.mesh, P(ax))
                ),
            )
        else:
            store = store._replace(
                reps=jnp.asarray(store.reps),
                row_valid=jnp.asarray(store.row_valid),
            )
        self.index = store
        return self.index

    # -------------------------------------------------------------- search
    def _local_topk(self, q_reps, reps, row_valid, shard_index):
        """One device's exact top-k over its index rows, ids globalized."""
        q_reps = self.policy.cast_compute(q_reps)
        scores, ids = self.backend.topk(
            q_reps, reps, self.cfg.top_k, col_valid=row_valid
        )
        offset = jnp.asarray(shard_index, jnp.int32) * reps.shape[0]
        return scores, jnp.where(ids >= 0, ids + offset, -1)

    def _merge_shards(self, scores, ids, shard_index, ctx: DistCtx):
        """psum top-k merge: deposit this shard's (Q, k) candidates into its
        slice of a zeros (Q, D, k) buffer; the psum assembles every slice
        exactly once, a final top_k reduces D*k -> k. Slices are shard-major
        so ties break toward the lowest global id, matching replicated."""
        q, k = scores.shape
        d = self.shards
        with jax.named_scope("shard_merge"):
            buf_s = jnp.zeros((q, d, k), scores.dtype)
            buf_i = jnp.zeros((q, d, k), ids.dtype)
            start = (0, shard_index, 0)
            buf_s = jax.lax.dynamic_update_slice(buf_s, scores[:, None, :], start)
            buf_i = jax.lax.dynamic_update_slice(buf_i, ids[:, None, :], start)
            cat_s = ctx.psum(buf_s).reshape(q, d * k)
            cat_i = ctx.psum(buf_i).reshape(q, d * k)
            top_s, pos = jax.lax.top_k(cat_s, k)
            top_i = jnp.take_along_axis(cat_i, pos, axis=1)
            return top_s, jnp.where(top_s > NEG_INF / 2, top_i, -1)

    def _build_search(self, encode: bool):
        cfg = self.cfg

        def local(params, reps, row_valid, queries, shard_index, ctx):
            q_reps = (
                self.encoder.encode_query(params, queries) if encode else queries
            )
            scores, ids = self._local_topk(q_reps, reps, row_valid, shard_index)
            if cfg.index_layout == "sharded":
                scores, ids = self._merge_shards(scores, ids, shard_index, ctx)
            return ids, scores

        if cfg.index_layout == "replicated":
            return jax.jit(
                lambda params, reps, row_valid, queries: local(
                    params, reps, row_valid, queries, 0, DistCtx()
                )
            )

        from jax.sharding import PartitionSpec as P

        ax = cfg.dp_axis
        ctx = DistCtx(ax)

        def sharded(params, reps, row_valid, queries):
            # queries replicated: every device encodes the (small) serving
            # batch; the index (the big operand) never moves
            return local(params, reps, row_valid, queries, ctx.shard_index(), ctx)

        return jax.jit(
            jax.shard_map(
                sharded,
                mesh=self.mesh,
                in_specs=(P(), P(ax, None), P(ax), P()),
                out_specs=(P(), P()),
                check_vma=False,
            )
        )

    def _require_index(self) -> IndexStore:
        if self.index is None:
            raise ValueError("no index built yet: call build_index(passages)")
        return self.index

    def search(self, query_tokens) -> Tuple[np.ndarray, np.ndarray]:
        """Encode query tokens with the query tower and return
        (ids (Q, k) int32, scores (Q, k) fp32); ids -1 = empty slot."""
        store = self._require_index()
        if self._search_tokens is None:
            self._search_tokens = self._build_search(encode=True)
        ids, scores = self._search_tokens(
            self.params, store.reps, store.row_valid, jnp.asarray(query_tokens)
        )
        return np.asarray(ids), np.asarray(scores)

    def search_reps(self, q_reps) -> Tuple[np.ndarray, np.ndarray]:
        """Search pre-encoded query representations (Q, d)."""
        store = self._require_index()
        if self._search_reps is None:
            self._search_reps = self._build_search(encode=False)
        ids, scores = self._search_reps(
            self.params, store.reps, store.row_valid, jnp.asarray(q_reps)
        )
        return np.asarray(ids), np.asarray(scores)
