"""Recsys ranking models: DLRM (dot interaction), DCN-v2 (cross network),
DeepFM (factorization machine branch).

The embedding lookup is the hot path. JAX has no native EmbeddingBag, so we
build it: all per-field tables are stacked into ONE row-sharded table with
per-field row offsets ("table stacking" — the standard TPU DLRM layout), and
lookup is `jnp.take` + optional `segment_sum` for multi-hot bags. Under GSPMD
the row-sharded gather lowers to local-gather + mask + all-reduce over the
"model" axis; the §Perf hillclimb iterates on this collective.

A factorized two-tower scoring path (`score_candidates`) serves the
``retrieval_cand`` shape: the user side is computed once and 1M candidate
items are scored with a batched interaction + top-MLP, not 1M full forwards.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


@dataclasses.dataclass(frozen=True)
class RecsysConfig:
    name: str
    n_dense: int
    vocab_sizes: Tuple[int, ...]          # rows per sparse field
    embed_dim: int
    interaction: str                      # "dot" | "cross" | "fm"
    bot_mlp: Tuple[int, ...] = ()         # dense-feature tower (DLRM)
    top_mlp: Tuple[int, ...] = ()         # final tower (ends in 1 logit)
    n_cross_layers: int = 0               # DCN-v2
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    # Optional NamedSharding for the (B, F, D) lookup output. Forcing the
    # batch sharding here lets GSPMD lower the row-sharded-table gather to
    # reduce-scatter (+local slice) instead of full-width all-reduce
    # (§Perf iteration A2 — measured: GSPMD ignores it; superseded by A3).
    lookup_sharding: Any = None
    # Optional explicit-collective lookup (table, flat_idx) -> (B, F, D),
    # built by make_psum_scatter_lookup (§Perf iteration A3).
    lookup_fn: Any = None

    # stacked-table rows are padded so the row dim divides the 256-way
    # ("model","data") sharding on both production meshes; padding rows are
    # never indexed (offsets keep per-field ranges disjoint).
    row_pad_multiple: int = 512

    @property
    def n_sparse(self) -> int:
        return len(self.vocab_sizes)

    @property
    def total_rows(self) -> int:
        raw = int(sum(self.vocab_sizes))
        m = self.row_pad_multiple
        return ((raw + m - 1) // m) * m if m else raw

    def field_offsets(self) -> jnp.ndarray:
        import numpy as np

        return jnp.asarray(
            np.concatenate([[0], np.cumsum(self.vocab_sizes)[:-1]]), jnp.int32
        )

    def param_count(self) -> int:
        n = self.total_rows * self.embed_dim
        dims_in = self._concat_dim()
        for mlp, d0 in ((self.bot_mlp, self.n_dense), (self.top_mlp, dims_in)):
            prev = d0
            for d in mlp:
                n += prev * d + d
                prev = d
        if self.interaction == "cross":
            x0 = self.n_dense + self.n_sparse * self.embed_dim
            n += self.n_cross_layers * (x0 * x0 + x0)
        return n

    def _concat_dim(self) -> int:
        """Input width of the top MLP."""
        f, d = self.n_sparse, self.embed_dim
        if self.interaction == "dot":
            n_items = f + 1  # embeddings + bottom-MLP output
            return (n_items * (n_items - 1)) // 2 + (self.bot_mlp[-1] if self.bot_mlp else 0)
        if self.interaction == "cross":
            x0 = self.n_dense + f * d
            return x0 + (self.top_mlp[-1] if self.top_mlp else 0)  # cross ++ deep
        if self.interaction == "fm":
            return f * d
        raise ValueError(self.interaction)


def _mlp_init(rng, dims: Sequence[int], d_in: int, pd):
    ks = jax.random.split(rng, max(len(dims), 1))
    layers = []
    prev = d_in
    for k, d in zip(ks, dims):
        layers.append({"w": dense_init(k, prev, d, dtype=pd), "b": jnp.zeros((d,), pd)})
        prev = d
    return layers


def _mlp_apply(layers, x, *, final_relu: bool = False):
    for i, lp in enumerate(layers):
        x = x @ lp["w"] + lp["b"]
        if i < len(layers) - 1 or final_relu:
            x = jax.nn.relu(x)
    return x


def init_recsys(rng, cfg: RecsysConfig):
    ks = jax.random.split(rng, 8)
    pd = cfg.param_dtype
    params = {
        # ONE stacked table; sharding rules split it by rows over "model"
        "table": (
            jax.random.uniform(
                ks[0], (cfg.total_rows, cfg.embed_dim), minval=-0.05, maxval=0.05
            )
        ).astype(pd),
    }
    if cfg.bot_mlp:
        params["bot"] = _mlp_init(ks[1], cfg.bot_mlp, cfg.n_dense, pd)
    if cfg.interaction == "cross":
        x0 = cfg.n_dense + cfg.n_sparse * cfg.embed_dim
        kk = jax.random.split(ks[2], cfg.n_cross_layers)
        params["cross"] = [
            {"w": dense_init(k, x0, x0, scale=0.1 / x0 ** 0.5, dtype=pd), "b": jnp.zeros((x0,), pd)}
            for k in kk
        ]
        params["deep"] = _mlp_init(ks[3], cfg.top_mlp, x0, pd)
        params["final"] = {
            "w": dense_init(ks[4], x0 + cfg.top_mlp[-1], 1, dtype=pd),
            "b": jnp.zeros((1,), pd),
        }
    elif cfg.interaction == "fm":
        params["w_first"] = (jax.random.normal(ks[2], (cfg.total_rows,)) * 0.01).astype(pd)
        params["deep"] = _mlp_init(
            ks[3], tuple(cfg.top_mlp) + (1,), cfg.n_sparse * cfg.embed_dim, pd
        )
    else:  # dot
        params["top"] = _mlp_init(ks[3], cfg.top_mlp, cfg._concat_dim(), pd)
    return params


def embedding_lookup(params, cfg: RecsysConfig, sparse_idx: jnp.ndarray) -> jnp.ndarray:
    """(B, n_sparse) per-field indices -> (B, n_sparse, embed_dim).

    Indices are per-field local; the stacked-table offset is added here.
    """
    flat = sparse_idx + cfg.field_offsets()[None, :]
    if cfg.lookup_fn is not None:
        return cfg.lookup_fn(params["table"], flat).astype(cfg.dtype)
    out = jnp.take(params["table"], flat, axis=0).astype(cfg.dtype)
    if cfg.lookup_sharding is not None:
        out = jax.lax.with_sharding_constraint(out, cfg.lookup_sharding)
    return out


def make_psum_scatter_lookup(mesh, table_axes=("model", "data"),
                             batch_axes=None):
    """Explicit-collective embedding lookup (§Perf iteration A3).

    GSPMD lowers ``jnp.take`` from a row-sharded table to a FULL-WIDTH
    partial + all-reduce + slice (measured on dlrm-mlperf; the constraint
    trick of A2 did not change it). This shard_map formulation does the
    communication-optimal thing by hand:

        all-gather the local indices over the table axes   (KBs)
        masked gather from the local row shard             (local)
        psum_scatter back to the batch sharding            (1/2 the
                                                            all-reduce wire,
                                                            no follow-up
                                                            all-gather)

    Batch must be sharded over ``batch_axes`` (default: pod? + table_axes
    reversed to ("data","model") order) with any "pod" axis outermost; the
    table is replicated across pods, so each pod resolves its own batch
    share independently. Fully differentiable (gather/scatter transposes).

    Returns ``lookup(table, flat_idx) -> (b_local..., F, D)-global-view``.
    """
    from jax.sharding import PartitionSpec as P

    in_pod = tuple(a for a in mesh.axis_names if a in table_axes)
    # batch dim0 ordering: mesh axis order ("pod","data","model")
    if batch_axes is None:
        batch_axes = tuple(mesh.axis_names)
    n_shards = 1
    for a in table_axes:
        n_shards *= mesh.shape[a]
    # gather/scatter axis tuple in the BATCH's dim-0 shard order (mesh order)
    gs_axes = tuple(a for a in batch_axes if a in table_axes)

    def kernel(table_shard, idx_local):
        # table_shard: (rows/n_shards, D); idx_local: (b/dev, F) global row ids
        rows_local = table_shard.shape[0]
        # table row-block index in table_axes major-to-minor order
        shard_id = 0
        for a in table_axes:
            shard_id = shard_id * mesh.shape[a] + jax.lax.axis_index(a)
        row_lo = shard_id * rows_local

        idx_pod = jax.lax.all_gather(idx_local, gs_axes, axis=0, tiled=True)
        rel = idx_pod - row_lo
        ok = (rel >= 0) & (rel < rows_local)
        part = jnp.where(
            ok[..., None],
            jnp.take(table_shard, jnp.clip(rel, 0, rows_local - 1), axis=0),
            0.0,
        )                                              # (B_pod, F, D) partial
        return jax.lax.psum_scatter(part, gs_axes, scatter_dimension=0,
                                    tiled=True)        # (b/dev, F, D)

    return jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(P(table_axes, None), P(batch_axes, None)),
        out_specs=P(batch_axes, None, None),
    )


def embedding_bag(params, cfg: RecsysConfig, multi_hot: jnp.ndarray, lengths: jnp.ndarray):
    """Multi-hot bags: (B, F, L) indices + (B, F) valid lengths -> mean-pooled
    (B, F, D). JAX's EmbeddingBag equivalent: gather + masked mean."""
    b, f, l = multi_hot.shape
    flat = multi_hot + cfg.field_offsets()[None, :, None]
    vecs = jnp.take(params["table"], flat, axis=0).astype(cfg.dtype)  # (B,F,L,D)
    mask = (jnp.arange(l)[None, None, :] < lengths[..., None]).astype(cfg.dtype)
    s = (vecs * mask[..., None]).sum(2)
    return s / jnp.maximum(mask.sum(2, keepdims=True)[..., 0][..., None], 1.0)


def _dot_interaction(emb: jnp.ndarray, bot: Optional[jnp.ndarray]) -> jnp.ndarray:
    """DLRM pairwise dots: emb (B, F, D) [+ bot (B, D)] -> (B, n_pairs [+D])."""
    items = emb if bot is None else jnp.concatenate([bot[:, None, :], emb], axis=1)
    b, f, d = items.shape
    sims = jnp.einsum("bfd,bgd->bfg", items, items)
    iu, ju = jnp.triu_indices(f, k=1)
    pairs = sims[:, iu, ju]
    return pairs if bot is None else jnp.concatenate([bot, pairs], axis=-1)


def forward(params, cfg: RecsysConfig, dense: jnp.ndarray, sparse_idx: jnp.ndarray):
    """Returns per-example logits (B,)."""
    emb = embedding_lookup(params, cfg, sparse_idx)        # (B, F, D)
    dense = dense.astype(cfg.dtype)
    if cfg.interaction == "dot":
        bot = _mlp_apply(params["bot"], dense, final_relu=True)
        z = _dot_interaction(emb, bot)
        return _mlp_apply(params["top"], z)[:, 0]
    if cfg.interaction == "cross":
        x0 = jnp.concatenate([dense, emb.reshape(emb.shape[0], -1)], axis=-1)
        x = x0
        for lp in params["cross"]:
            x = x0 * (x @ lp["w"] + lp["b"]) + x
        deep = _mlp_apply(params["deep"], x0, final_relu=True)
        z = jnp.concatenate([x, deep], axis=-1)
        return _mlp_apply([params["final"]], z)[:, 0]
    if cfg.interaction == "fm":
        flat_idx = sparse_idx + cfg.field_offsets()[None, :]
        first = jnp.take(params["w_first"], flat_idx, axis=0).sum(-1)
        s = emb.sum(1)
        fm2 = 0.5 * (s * s - (emb * emb).sum(1)).sum(-1)
        deep = _mlp_apply(params["deep"], emb.reshape(emb.shape[0], -1))[:, 0]
        return first + fm2 + deep
    raise ValueError(cfg.interaction)


def bce_loss(params, cfg: RecsysConfig, dense, sparse_idx, labels):
    logits = forward(params, cfg, dense, sparse_idx).astype(jnp.float32)
    labels = labels.astype(jnp.float32)
    loss = jnp.mean(
        jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    )
    acc = jnp.mean(((logits > 0) == (labels > 0.5)).astype(jnp.float32))
    return loss, {"bce": loss, "accuracy": acc}


def score_candidates(
    params,
    cfg: RecsysConfig,
    dense: jnp.ndarray,        # (1, n_dense) one user/query
    sparse_idx: jnp.ndarray,   # (1, n_sparse) user-side fields
    cand_ids: jnp.ndarray,     # (C,) candidate ids for field 0
):
    """retrieval_cand shape: score 1 query against C candidates by swapping
    field 0's embedding. User-side embeddings/bottom tower computed once."""
    emb_user = embedding_lookup(params, cfg, sparse_idx)   # (1, F, D)
    cand = jnp.take(
        params["table"], cand_ids + cfg.field_offsets()[0], axis=0
    ).astype(cfg.dtype)                                     # (C, D)
    c = cand.shape[0]
    emb = jnp.broadcast_to(emb_user, (c,) + emb_user.shape[1:])
    emb = emb.at[:, 0, :].set(cand)
    dense_b = jnp.broadcast_to(dense.astype(cfg.dtype), (c, dense.shape[1]))
    if cfg.interaction == "dot":
        bot = _mlp_apply(params["bot"], dense_b, final_relu=True)
        z = _dot_interaction(emb, bot)
        return _mlp_apply(params["top"], z)[:, 0]
    if cfg.interaction == "cross":
        x0 = jnp.concatenate([dense_b, emb.reshape(c, -1)], axis=-1)
        x = x0
        for lp in params["cross"]:
            x = x0 * (x @ lp["w"] + lp["b"]) + x
        deep = _mlp_apply(params["deep"], x0, final_relu=True)
        z = jnp.concatenate([x, deep], axis=-1)
        return _mlp_apply([params["final"]], z)[:, 0]
    # fm
    flat0 = cand_ids + cfg.field_offsets()[0]
    first_user = jnp.take(
        params["w_first"], sparse_idx[0, 1:] + cfg.field_offsets()[1:], axis=0
    ).sum()
    first = first_user + jnp.take(params["w_first"], flat0, axis=0)
    s = emb.sum(1)
    fm2 = 0.5 * (s * s - (emb * emb).sum(1)).sum(-1)
    deep = _mlp_apply(params["deep"], emb.reshape(c, -1))[:, 0]
    return first + fm2 + deep
