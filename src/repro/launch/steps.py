"""Cell programs: (architecture x shape cell x mesh) -> jit-able step function
plus ShapeDtypeStruct inputs carrying NamedShardings (the shannon/kernels
dry-run pattern: weak-type-correct, shardable, zero device allocation).

Every assigned shape cell lowers one of:
  train          LM causal-LM training step (microbatched grad accumulation)
  prefill        LM KV-cache build + last-position logits
  decode         LM one-token serve step against a seq_len KV cache
  gnn_full/...   SchNet training step (full graph / sampled block / molecules)
  recsys_train   DLRM/DCN/DeepFM BCE training step
  recsys_serve   forward scoring
  recsys_retrieval  1 query x 1M candidates factorized scoring
  contrastive    the paper's ContAccum update at pod scale (dual banks,
                 cross-device in-batch negatives via GSPMD)

Irregular sizes (edge counts, candidate counts) are padded up to the device
count with explicit validity masks — static shapes everywhere, masked
elements contribute zero (recorded in ``static_info['padded']``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.treemath import tree_add, tree_scale, tree_zeros_like
from repro.configs import get_arch, list_archs
from repro.configs.base import ArchSpec, ShapeCell
from repro.core.methods import build_step_program, init_state
from repro.core.precision import bank_bytes_per_device, resolve_precision
from repro.core.types import ContrastiveConfig, RetrievalBatch
from repro.distribution.sharding import (
    BERT_RULES,
    GNN_RULES,
    LM_RULES,
    RECSYS_RULES,
    bank_rules,
    contrastive_state_spec,
    dp_axes,
    make_param_shardings,
)
from repro.models.bert import BertConfig
from repro.models.gnn import GraphBatch, SchNetConfig, init_schnet, schnet_loss
from repro.models.lm import (
    KVCache,
    LMConfig,
    decode_step,
    init_lm,
    lm_loss,
    prefill,
)
from repro.models.recsys import (
    RecsysConfig,
    bce_loss,
    forward as recsys_forward,
    init_recsys,
    score_candidates,
)
from repro.models.towers import make_bert_dual_encoder
from repro.optim.adamw import adamw, apply_updates, chain, clip_by_global_norm
from repro.optim.schedules import linear_warmup_linear_decay


class TrainState(NamedTuple):
    step: jnp.ndarray
    params: Any
    opt: Any


@dataclasses.dataclass
class CellProgram:
    arch_id: str
    shape_name: str
    kind: str
    fn: Callable
    args: Tuple[Any, ...]               # ShapeDtypeStructs with shardings
    donate_argnums: Tuple[int, ...]
    static_info: dict


# bf16 Adam moments for the >=100B configs (HBM budget; see configs notes)
MOMENT_DTYPE = {
    "qwen1.5-110b": jnp.bfloat16,
    "qwen3-moe-235b-a22b": jnp.bfloat16,
}


# --------------------------------------------------------------------- utils
def _axes_size(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _all_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def _sds(mesh: Mesh, shape, dtype, spec: P):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))


def _shard_like(mesh: Mesh, tree, rules, *, dense_ffn: bool = False):
    """eval_shape tree -> same tree of SDS with rule-derived shardings."""
    sh = make_param_shardings(mesh, tree, rules, dense_ffn=dense_ffn)
    return jax.tree_util.tree_map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h), tree, sh
    )


def _pad_to(n: int, multiple: int) -> int:
    return ((n + multiple - 1) // multiple) * multiple


def _constrain(mesh: Mesh, x, spec: P):
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _make_tx(arch_id: str, *, lr: float = 3e-4, clip: float = 1.0):
    sched = linear_warmup_linear_decay(lr, 2000, 200_000)
    return chain(
        clip_by_global_norm(clip),
        adamw(sched, moment_dtype=MOMENT_DTYPE.get(arch_id, jnp.float32)),
    )


# ---------------------------------------------------------------- LM: train
def _lm_flops(cfg: LMConfig, tokens: int, *, train: bool) -> float:
    n = cfg.active_param_count()
    mult = 6.0 if train else 2.0
    # attention score/value flops (not in 6ND): 2 * 2 * S * tokens * H * dh,
    # halved for causal masking
    attn = 2.0 * tokens * cfg.n_heads * cfg.dh * cfg.n_layers
    return mult * n * tokens + (3.0 if train else 1.0) * attn


def _lm_train_program(arch: ArchSpec, cell: ShapeCell, mesh: Mesh) -> CellProgram:
    cfg: LMConfig = arch.model_cfg
    B, S = cell.params["global_batch"], cell.params["seq_len"]
    dp = dp_axes(mesh)
    dps = _axes_size(mesh, dp)
    # microbatch count: honor the config but keep every microbatch shardable
    m = max(1, min(arch.micro_batch(cell.name), B // dps))
    while B % m or (B // m) % dps:
        m -= 1

    tx = _make_tx(arch.arch_id)

    def loss_fn(params, tokens, targets):
        return lm_loss(params, cfg, tokens, targets)

    def train_step(state: TrainState, tokens, targets):
        # tokens/targets: (m, B//m, S), microbatch-major
        def micro(g_acc, inp):
            tk, tg = inp
            (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, tk, tg
            )
            return tree_add(g_acc, g), loss

        grads, losses = jax.lax.scan(
            micro, tree_zeros_like(state.params), (tokens, targets)
        )
        grads = tree_scale(grads, 1.0 / m)
        updates, opt = tx.update(grads, state.opt, state.params)
        params = apply_updates(state.params, updates)
        return TrainState(state.step + 1, params, opt), {"loss": losses.mean()}

    dense_ffn = cfg.moe is None
    params_s = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    opt_s = jax.eval_shape(_make_tx(arch.arch_id).init, params_s)
    state = TrainState(
        step=_sds(mesh, (), jnp.int32, P()),
        params=_shard_like(mesh, params_s, LM_RULES, dense_ffn=dense_ffn),
        opt=_shard_like(mesh, opt_s, LM_RULES, dense_ffn=dense_ffn),
    )
    tokens = _sds(mesh, (m, B // m, S), jnp.int32, P(None, dp, None))
    targets = _sds(mesh, (m, B // m, S), jnp.int32, P(None, dp, None))
    return CellProgram(
        arch_id=arch.arch_id,
        shape_name=cell.name,
        kind="train",
        fn=train_step,
        args=(state, tokens, targets),
        donate_argnums=(0,),
        static_info={
            "model_flops": _lm_flops(cfg, B * S, train=True),
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count(),
            "microbatches": m,
            "tokens_per_step": B * S,
        },
    )


# -------------------------------------------------------------- LM: prefill
def _lm_prefill_program(arch: ArchSpec, cell: ShapeCell, mesh: Mesh) -> CellProgram:
    cfg: LMConfig = arch.model_cfg
    B, S = cell.params["global_batch"], cell.params["seq_len"]
    dp = dp_axes(mesh)
    cache_spec = P(None, dp, "model", None, None)

    def prefill_step(params, tokens):
        cache, logits = prefill(params, cfg, tokens)
        cache = KVCache(
            k=_constrain(mesh, cache.k, cache_spec),
            v=_constrain(mesh, cache.v, cache_spec),
            length=cache.length,
        )
        return cache, logits

    params_s = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    params = _shard_like(mesh, params_s, LM_RULES, dense_ffn=cfg.moe is None)
    tokens = _sds(mesh, (B, S), jnp.int32, P(dp, None))
    return CellProgram(
        arch_id=arch.arch_id,
        shape_name=cell.name,
        kind="prefill",
        fn=prefill_step,
        args=(params, tokens),
        donate_argnums=(),
        static_info={
            "model_flops": _lm_flops(cfg, B * S, train=False),
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count(),
            "tokens_per_step": B * S,
        },
    )


# --------------------------------------------------------------- LM: decode
def _lm_decode_program(arch: ArchSpec, cell: ShapeCell, mesh: Mesh) -> CellProgram:
    cfg: LMConfig = arch.model_cfg
    B, S = cell.params["global_batch"], cell.params["seq_len"]
    dp = dp_axes(mesh)
    if B == 1:
        # long-context: nothing to shard on batch, context-parallel over
        # every axis (sequence-sharded KV cache -> distributed flash-decode)
        batch_spec = P(None)
        seq_axes: Tuple[str, ...] = _all_axes(mesh)
    else:
        batch_spec = P(dp)
        seq_axes = ("model",)
    cache_spec = P(None, None if B == 1 else dp, seq_axes, None, None)

    def serve_step(params, cache: KVCache, token):
        new_cache, logits = decode_step(params, cfg, cache, token)
        new_cache = KVCache(
            k=_constrain(mesh, new_cache.k, cache_spec),
            v=_constrain(mesh, new_cache.v, cache_spec),
            length=new_cache.length,
        )
        return new_cache, logits

    params_s = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    params = _shard_like(mesh, params_s, LM_RULES, dense_ffn=cfg.moe is None)
    kv_shape = (cfg.n_layers, B, S, cfg.n_kv_heads, cfg.dh)
    cache = KVCache(
        k=_sds(mesh, kv_shape, cfg.dtype, cache_spec),
        v=_sds(mesh, kv_shape, cfg.dtype, cache_spec),
        length=_sds(mesh, (B,), jnp.int32, P()),
    )
    token = _sds(mesh, (B,), jnp.int32, batch_spec)
    kv_bytes = 2 * np.prod(kv_shape) * jnp.dtype(cfg.dtype).itemsize
    return CellProgram(
        arch_id=arch.arch_id,
        shape_name=cell.name,
        kind="decode",
        fn=serve_step,
        args=(params, cache, token),
        donate_argnums=(1,),
        static_info={
            # decode is memory-bound: one full pass over active params + the
            # KV cache per generated token
            "model_flops": 2.0 * cfg.active_param_count() * B
            + 4.0 * B * S * cfg.n_kv_heads * cfg.dh * cfg.n_layers,
            "params": cfg.param_count(),
            "kv_cache_bytes": float(kv_bytes),
            "tokens_per_step": B,
        },
    )


# --------------------------------------------------------------------- GNN
def _gnn_program(arch: ArchSpec, cell: ShapeCell, mesh: Mesh) -> CellProgram:
    base: SchNetConfig = arch.model_cfg
    p = cell.params
    all_ax = _all_axes(mesh)
    n_dev = _axes_size(mesh, all_ax)
    kind = cell.kind
    dp = dp_axes(mesh)

    if kind == "gnn_mol":
        cfg = base  # atomic-number embedding, energy regression
        n_graphs = p["batch"]
        n_nodes = p["batch"] * p["n_nodes"]
        n_edges_raw = p["batch"] * p["n_edges"]
        nodes_sds = _sds(mesh, (n_nodes,), jnp.int32, P())
        targets = _sds(mesh, (n_graphs,), jnp.float32, P())
        graph_id = _sds(mesh, (n_nodes,), jnp.int32, P())
        target_mask = None
    else:
        if kind == "gnn_minibatch":
            from repro.data.graph import block_sizes

            n_nodes, n_edges_raw = block_sizes(p["batch_nodes"], p["fanouts"])
        else:
            n_nodes, n_edges_raw = p["n_nodes"], p["n_edges"]
        cfg = dataclasses.replace(
            base, d_feat=p["d_feat"], n_classes=p["n_classes"]
        )
        n_graphs = 1
        nodes_sds = _sds(mesh, (n_nodes, p["d_feat"]), jnp.float32, P())
        targets = _sds(mesh, (n_nodes,), jnp.int32, P())
        graph_id = None
        target_mask = _sds(mesh, (n_nodes,), bool, P())

    n_edges = _pad_to(n_edges_raw, n_dev)
    edge_spec = P(all_ax)
    tx = _make_tx(arch.arch_id, lr=1e-3)

    def train_step(state, nodes, src, dst, edge_dist, node_mask, edge_mask,
                   targets_, target_mask_, graph_id_):
        g = GraphBatch(
            nodes=nodes, src=src, dst=dst, edge_dist=edge_dist,
            node_mask=node_mask, edge_mask=edge_mask, graph_id=graph_id_,
            n_graphs=n_graphs, targets=targets_, target_mask=target_mask_,
        )

        def loss_fn(params):
            return schnet_loss(params, cfg, g)

        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(state.params)
        updates, opt = tx.update(grads, state.opt, state.params)
        params = apply_updates(state.params, updates)
        return TrainState(state.step + 1, params, opt), {"loss": loss}

    params_s = jax.eval_shape(lambda: init_schnet(jax.random.PRNGKey(0), cfg))
    opt_s = jax.eval_shape(_make_tx(arch.arch_id, lr=1e-3).init, params_s)
    state = TrainState(
        step=_sds(mesh, (), jnp.int32, P()),
        params=_shard_like(mesh, params_s, GNN_RULES),
        opt=_shard_like(mesh, opt_s, GNN_RULES),
    )
    args = (
        state,
        nodes_sds,
        _sds(mesh, (n_edges,), jnp.int32, edge_spec),
        _sds(mesh, (n_edges,), jnp.int32, edge_spec),
        _sds(mesh, (n_edges,), jnp.float32, edge_spec),
        _sds(mesh, (n_nodes,), bool, P()),
        _sds(mesh, (n_edges,), bool, edge_spec),
        targets,
        target_mask,
        graph_id,
    )
    h = cfg.d_hidden
    # fwd: edge gather/filter (E*(rbf*h + 2h^2)) + node MLPs (N*4h^2), x3 bwd
    model_flops = 3.0 * 2.0 * cfg.n_interactions * (
        n_edges_raw * (cfg.n_rbf * h + 2 * h * h) + n_nodes * 2 * h * h
    )
    return CellProgram(
        arch_id=arch.arch_id,
        shape_name=cell.name,
        kind=kind,
        fn=train_step,
        args=args,
        donate_argnums=(0,),
        static_info={
            "model_flops": model_flops,
            "n_nodes": n_nodes,
            "n_edges": n_edges,
            "padded": {"n_edges": [n_edges_raw, n_edges]},
        },
    )


# ------------------------------------------------------------------- recsys
def _recsys_mlp_flops(cfg: RecsysConfig) -> float:
    total = 0.0
    prev = cfg.n_dense
    for d in cfg.bot_mlp:
        total += 2 * prev * d
        prev = d
    prev = cfg._concat_dim()
    for d in cfg.top_mlp:
        total += 2 * prev * d
        prev = d
    if cfg.interaction == "cross":
        x0 = cfg.n_dense + cfg.n_sparse * cfg.embed_dim
        total += cfg.n_cross_layers * 2 * x0 * x0
    if cfg.interaction == "dot":
        f = cfg.n_sparse + 1
        total += 2 * f * f * cfg.embed_dim
    return total


def _recsys_program(arch: ArchSpec, cell: ShapeCell, mesh: Mesh) -> CellProgram:
    cfg: RecsysConfig = arch.model_cfg
    p = cell.params
    # §Perf iteration A1 (EXPERIMENTS.md): recsys MLPs are replicated over
    # "model", so a ("pod","data")-only batch made every model-rank duplicate
    # the same MLP compute AND all-reduced the full lookup tensor across the
    # whole mesh. Sharding the batch over ALL axes removes the duplication
    # (measured: 16.3x less compute, 9.6x less wire on dlrm-mlperf).
    dp = _all_axes(mesh)
    kind = cell.kind
    # §Perf iteration A3: explicit-collective lookup (all-gather indices ->
    # local-shard masked gather -> psum_scatter). Replaces GSPMD's full-width
    # partial + all-reduce + slice lowering of jnp.take (A2's sharding
    # constraint was ignored — see EXPERIMENTS.md §Perf A). Applied when the
    # batch divides the mesh (retrieval_cand's B=1 user-side lookup stays on
    # the plain path; its cost is negligible next to candidate scoring).
    from repro.models.recsys import make_psum_scatter_lookup

    if kind != "recsys_retrieval" and p["batch"] % _axes_size(mesh, dp) == 0:
        cfg = dataclasses.replace(
            cfg,
            lookup_fn=make_psum_scatter_lookup(
                mesh, table_axes=("model", "data"), batch_axes=dp
            ),
        )
    params_s = jax.eval_shape(lambda: init_recsys(jax.random.PRNGKey(0), cfg))
    params = _shard_like(mesh, params_s, RECSYS_RULES)

    if kind == "recsys_retrieval":
        all_ax = _all_axes(mesh)
        n_dev = _axes_size(mesh, all_ax)
        c = _pad_to(p["n_candidates"], n_dev)

        def retrieval_step(params_, dense, sparse, cand_ids):
            return score_candidates(params_, cfg, dense, sparse, cand_ids)

        args = (
            params,
            _sds(mesh, (1, cfg.n_dense), jnp.float32, P()),
            _sds(mesh, (1, cfg.n_sparse), jnp.int32, P()),
            _sds(mesh, (c,), jnp.int32, P(all_ax)),
        )
        flops = (_recsys_mlp_flops(cfg) + 2 * cfg.n_sparse * cfg.embed_dim) * c
        return CellProgram(
            arch_id=arch.arch_id, shape_name=cell.name, kind=kind,
            fn=retrieval_step, args=args, donate_argnums=(),
            static_info={
                "model_flops": flops,
                "params": cfg.param_count(),
                "padded": {"n_candidates": [p["n_candidates"], c]},
            },
        )

    b = p["batch"]
    dense = _sds(mesh, (b, cfg.n_dense), jnp.float32, P(dp, None))
    sparse = _sds(mesh, (b, cfg.n_sparse), jnp.int32, P(dp, None))

    if kind == "recsys_serve":
        def serve_step(params_, dense_, sparse_):
            return recsys_forward(params_, cfg, dense_, sparse_)

        return CellProgram(
            arch_id=arch.arch_id, shape_name=cell.name, kind=kind,
            fn=serve_step, args=(params, dense, sparse), donate_argnums=(),
            static_info={
                "model_flops": _recsys_mlp_flops(cfg) * b,
                "params": cfg.param_count(),
            },
        )

    # recsys_train
    tx = _make_tx(arch.arch_id, lr=1e-3)
    labels = _sds(mesh, (b,), jnp.float32, P(dp))
    opt_s = jax.eval_shape(tx.init, params_s)
    state = TrainState(
        step=_sds(mesh, (), jnp.int32, P()),
        params=params,
        opt=_shard_like(mesh, opt_s, RECSYS_RULES),
    )

    def train_step(state_, dense_, sparse_, labels_):
        def loss_fn(params_):
            return bce_loss(params_, cfg, dense_, sparse_, labels_)

        (loss, m), grads = jax.value_and_grad(loss_fn, has_aux=True)(state_.params)
        updates, opt = tx.update(grads, state_.opt, state_.params)
        new_params = apply_updates(state_.params, updates)
        return TrainState(state_.step + 1, new_params, opt), {
            "loss": loss, "accuracy": m["accuracy"],
        }

    return CellProgram(
        arch_id=arch.arch_id, shape_name=cell.name, kind=kind,
        fn=train_step, args=(state, dense, sparse, labels), donate_argnums=(0,),
        static_info={
            "model_flops": 3.0 * _recsys_mlp_flops(cfg) * b,
            "params": cfg.param_count(),
        },
    )


# ------------------------------------------------- contrastive (the paper)
def _contrastive_program(arch: ArchSpec, cell: ShapeCell, mesh: Mesh) -> CellProgram:
    bcfg: BertConfig = arch.model_cfg
    p = cell.params
    # §Perf iteration B1 (EXPERIMENTS.md): both towers fit per chip with
    # optimizer state (~3.5 GB fp32), so pure DP — replicated weights, batch
    # over every mesh axis — removes the weight-contraction activation
    # all-reduces that dominated the baseline (12 x 67.5 GiB wire/step).
    # Sharding rules stay selectable: "tp_fsdp" reproduces the baseline.
    # xdev: explicit shard_map over the DP axes instead of single-program
    # GSPMD — required for cfg.shard_banks (each device owns bank_size/D
    # ring slots; batch sharded, weights replicated, collectives by name)
    xdev = p.get("xdev", False)
    shard_banks = bool(p.get("shard_banks", False))
    # loss_comm='ring' streams the bank shards around the DP ring at loss
    # time (O(bank*d/D) transient) instead of all-gathering them; cells opt
    # in via "loss_comm" and step_program validates it needs shard_banks
    loss_comm = p.get("loss_comm", "all_gather")
    if shard_banks and not xdev:
        raise ValueError(
            "cell sets shard_banks without xdev: sharded banks need the "
            "explicit shard_map path (bank leaves sharded by bank_spec); "
            "the single-program GSPMD path would silently replicate them"
        )
    mode = p.get("sharding", "pure_dp")
    if xdev:
        dp = dp_axes(mesh)
        if p["global_batch"] % _axes_size(mesh, dp) or (
            shard_banks and p["bank_size"] % _axes_size(mesh, dp)
        ):
            raise ValueError(
                f"xdev cell needs global_batch ({p['global_batch']}) and a "
                f"sharded bank_size ({p['bank_size']}) divisible by the DP "
                f"axes {dp} (= {_axes_size(mesh, dp)} shards)"
            )
        rules = bank_rules(dp, shard_banks) + [(r".*", P())]
    elif mode == "pure_dp":
        # largest axis prefix that divides the global batch (paper_batch's
        # B=128 < 256 chips: the paper's own geometry deliberately under-
        # fills a pod — remaining ranks replicate)
        dp = _all_axes(mesh)
        while dp and p["global_batch"] % _axes_size(mesh, dp):
            dp = dp[:-1]
        dp = dp or dp_axes(mesh)
        rules = [(r".*", P())]
    else:
        dp = dp_axes(mesh)
        rules = BERT_RULES
    # §Perf iteration B2, generalized into a PrecisionPolicy
    # (core/precision.py): cells select a preset via "precision"; the legacy
    # "bf16_compute" flag (default True) maps to the 'bf16' preset — bf16
    # activations with fp32 master weights, banks and softmax statistics.
    # 'bf16_banks' additionally stores the bank rings in bf16.
    policy = resolve_precision(
        p.get("precision", "bf16" if p.get("bf16_compute", True) else "fp32")
    )
    bcfg = bcfg.with_precision(policy)
    ccfg = ContrastiveConfig(
        # any registered source x strategy composition; cells default to the
        # paper's contaccum but can select e.g. contcache / prebatch_cache
        method=p.get("method", "contaccum"),
        negatives=p.get("negatives"),
        backprop=p.get("backprop"),
        accumulation_steps=p["accum_steps"],
        bank_size=p["bank_size"],
        # 'fused' streams the extended logits block through the Pallas
        # online-softmax kernel (compiled on TPU, interpreter elsewhere)
        loss_impl=p.get("loss_impl", "dense"),
        precision=policy,
        temperature=1.0,
        # xdev: explicit collectives over the named DP axes (shard_map).
        # Otherwise dp_axis=None: single-program semantics; GSPMD derives
        # the cross-device negative all-gathers from the batch sharding.
        dp_axis=dp if xdev else None,
        shard_banks=shard_banks,
        loss_comm=loss_comm,
    )
    enc = make_bert_dual_encoder(bcfg)
    tx = chain(
        clip_by_global_norm(2.0),
        adamw(linear_warmup_linear_decay(2e-5, 1237, 50_000)),
    )
    program = build_step_program(enc, tx, ccfg)
    update = program.update
    if xdev:
        state_spec = contrastive_state_spec(dp, shard_banks)
        batch_spec = RetrievalBatch(
            query=P(dp, None),
            passage_pos=P(dp, None),
            passage_hard=P(dp, None, None),
        )
        update = jax.shard_map(
            program.update,
            mesh=mesh,
            in_specs=(state_spec, batch_spec),
            out_specs=(state_spec, P()),
            check_vma=False,
        )

    state_s = jax.eval_shape(
        lambda: init_state(jax.random.PRNGKey(0), enc, tx, ccfg)
    )
    state = _shard_like(mesh, state_s, rules)

    b, ql, pl = p["global_batch"], p["q_len"], p["p_len"]
    # mined hard negatives (repro/mining) arrive as extra passage_hard
    # columns injected at batch assembly — to the compiled program they are
    # indistinguishable from corpus-supplied hard negatives, so the cell
    # just widens the column axis
    mined = p.get("mined_negatives", 0)
    h = p["n_hard"] + mined
    batch = RetrievalBatch(
        query=_sds(mesh, (b, ql), jnp.int32, P(dp, None)),
        passage_pos=_sds(mesh, (b, pl), jnp.int32, P(dp, None)),
        passage_hard=_sds(mesh, (b, h, pl), jnp.int32, P(dp, None, None)),
    )

    tokens = b * (ql + pl * (1 + h))
    nq, np_ = program.source.bank_sizes(ccfg)
    bank_shards = _axes_size(mesh, dp) if shard_banks else 1
    bank_bytes_dev = bank_bytes_per_device(
        nq, np_, bcfg.d_model, policy, shards=bank_shards
    )
    if program.strategy.name == "rep_cache":
        # one full-batch similarity matrix regardless of K
        rows, cols, n_mats = b + nq, b * (1 + h) + np_, 1
    else:
        k_eff = 1 if program.strategy.name == "direct" else p["accum_steps"]
        rows, cols, n_mats = b // k_eff + nq, (b // k_eff) * (1 + h) + np_, k_eff
    sim_flops = 2.0 * rows * cols * bcfg.d_model * 3 * n_mats
    return CellProgram(
        arch_id=arch.arch_id, shape_name=cell.name, kind="contrastive",
        fn=update, args=(state, batch), donate_argnums=(0,),
        static_info={
            "model_flops": 6.0 * bcfg.param_count() * tokens + sim_flops,
            "params": 2 * bcfg.param_count(),
            "bank_size": p["bank_size"],
            "accum_steps": p["accum_steps"],
            "method": program.name,
            "negatives": program.source.name,
            "backprop": program.strategy.name,
            "loss_impl": ccfg.loss_impl,
            "precision": policy.name,
            "xdev": xdev,
            "shard_banks": shard_banks,
            "loss_comm": loss_comm,
            "bank_shards": bank_shards,
            "bank_bytes_per_device": float(bank_bytes_dev),
            "mined_negatives": mined,
        },
    )


# --------------------------------------- retrieval serving / eval (the paper)
def _retrieval_program(arch: ArchSpec, cell: ShapeCell, mesh: Mesh) -> CellProgram:
    """Inference cells on the Retriever surface (repro/retrieval): query-tower
    encode + exact top-k against a corpus index sharded in contiguous row
    blocks over the DP axes (P(dp) rows — the bank_rules layout applied to
    the serving-side persistent state). Queries stay replicated: the big
    operand (the index) never moves; GSPMD derives the candidate merge.

    ``retrieval_serve`` is the online shape (small coalesced batch),
    ``retrieval_eval`` the offline one (the periodic ANCE-style eval sweep:
    thousands of queries against the full index). Both honor the cell's
    "precision" (index rows in the policy's bank dtype, query reps in
    compute dtype, scores fp32) and "search_impl" (dense blocked-scan vs
    the fused Pallas QK^T + running-top-k kernel)."""
    from repro.retrieval.retriever import RetrieverConfig

    bcfg: BertConfig = arch.model_cfg
    p = cell.params
    dp = dp_axes(mesh)
    policy = resolve_precision(p.get("precision", "bf16_banks"))
    bcfg = bcfg.with_precision(policy)
    rcfg = RetrieverConfig(
        top_k=p["top_k"],
        search_impl=p.get("search_impl", "dense"),
        precision=policy,
    )
    backend = rcfg.resolve_backend()
    enc = make_bert_dual_encoder(bcfg)
    k = p["top_k"]

    def search_step(params, index, row_valid, tokens):
        q = enc.encode_query(params, tokens).astype(policy.compute_dtype)
        scores, ids = backend.topk(q, index, k, col_valid=row_valid)
        return ids, scores

    n_dev = _axes_size(mesh, dp)
    n = _pad_to(p["n_passages"], n_dev)
    q_n, ql, d = p["n_queries"], p["q_len"], bcfg.d_model
    params_s = jax.eval_shape(lambda: enc.init(jax.random.PRNGKey(0)))
    args = (
        _shard_like(mesh, params_s, [(r".*", P())]),
        _sds(mesh, (n, d), policy.bank_dtype, P(dp, None)),
        _sds(mesh, (n,), bool, P(dp)),
        _sds(mesh, (q_n, ql), jnp.int32, P()),
    )
    index_bytes_dev = (n * d * jnp.dtype(policy.bank_dtype).itemsize) // n_dev
    return CellProgram(
        arch_id=arch.arch_id, shape_name=cell.name, kind=cell.kind,
        fn=search_step, args=args, donate_argnums=(),
        static_info={
            # encode is inference (2ND); scoring is one Q x N x d matmul
            "model_flops": 2.0 * bcfg.param_count() * q_n * ql
            + 2.0 * q_n * n * d,
            "params": bcfg.param_count(),
            "top_k": k,
            "search_impl": rcfg.search_impl,
            "precision": policy.name,
            "index_rows": n,
            "index_shards": n_dev,
            "index_bytes_per_device": float(index_bytes_dev),
            "padded": {"n_passages": [p["n_passages"], n]},
        },
    )


# --------------------------------------------------------------- dispatcher
_BUILDERS = {
    "train": _lm_train_program,
    "prefill": _lm_prefill_program,
    "decode": _lm_decode_program,
    "gnn_full": _gnn_program,
    "gnn_minibatch": _gnn_program,
    "gnn_mol": _gnn_program,
    "recsys_train": _recsys_program,
    "recsys_serve": _recsys_program,
    "recsys_retrieval": _recsys_program,
    "contrastive": _contrastive_program,
    "retrieval_serve": _retrieval_program,
    "retrieval_eval": _retrieval_program,
}


def build_cell(arch_id: str, shape_name: str, mesh: Mesh) -> CellProgram:
    arch = get_arch(arch_id)
    if shape_name not in arch.shapes:
        raise KeyError(
            f"{arch_id} has no shape {shape_name!r}; known: {sorted(arch.shapes)}"
        )
    cell = arch.shapes[shape_name]
    return _BUILDERS[cell.kind](arch, cell, mesh)


def list_cells(include_contrastive: bool = True):
    """All (arch, shape) pairs: the assigned 40 plus the paper's own cells."""
    out = []
    for arch_id in list_archs():
        arch = get_arch(arch_id)
        if arch.family == "bert" and not include_contrastive:
            continue
        for shape_name in arch.shapes:
            out.append((arch_id, shape_name))
    return out


def input_specs(arch_id: str, shape_name: str, mesh: Mesh):
    """ShapeDtypeStruct stand-ins for every input of the cell's step fn."""
    return build_cell(arch_id, shape_name, mesh).args
