"""Mixture-of-Experts FFN: token-choice top-k routing, two layers.

``moe_dropless`` is the retriever towers' layer (DeepSeek-V3, arXiv:
2412.19437 section 2.1.2): sigmoid scores, a selection-only bias,
normalised and scaled top-k gates, shared experts, and only the
experts this device holds (``MoEConfig.held``) computed, as grouped matrix
products over contiguous expert groups (``jax.lax.ragged_dot``). It drops
no token, whatever the skew: a representation never depends on what else
is in the batch.

``moe_ffn`` is the capacity-factor layer of the dry-run LM presets, which
drops the tokens past an expert's capacity:

TPU-native formulation (GShard/Switch style, as used by MaxText's "dropping"
strategy): tokens are processed in groups; within a group a k-hot dispatch
tensor (group, experts, capacity) routes tokens into expert buffers via a
single einsum, the experts run as one batched matmul over the expert dim
(sharded over the "model" mesh axis -> expert parallelism; XLA inserts the
all-to-alls), and a combine einsum returns weighted expert outputs.

The group scan bounds the dispatch tensor's memory to
group_size * n_experts * capacity while keeping the expert GEMMs large.
Dispatch-einsum FLOPs scale with group_size (smaller groups = less overhead),
which is one of the §Perf hillclimb levers.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.layers import swiglu


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_expert: int                  # expert hidden (a.k.a. moe_intermediate)
    capacity_factor: float = 1.25
    group_size: int = 1024         # tokens per dispatch group
    router_aux_weight: float = 0.01
    normalize_top_k: bool = True   # qwen3/mixtral-style renormalization
    # §Perf iteration C1: process all groups as one batched einsum (group dim
    # inherits the token/batch sharding -> groups run data-parallel) instead
    # of a sequential lax.scan over GLOBAL groups, which made every device
    # execute every group on its 1/dp token slice with a partial-sum
    # all-reduce per iteration (measured 1.3 TiB wire/step on qwen3-moe).
    # scan mode remains for memory-constrained single-host debugging.
    vectorize_groups: bool = True
    # the dropless layer (moe_dropless) and what it reads
    dropless: bool = False
    routed_scale: float = 1.0            # routed_scaling_factor
    d_shared: int = 0                    # width of the shared experts, fused
    held: Optional[Tuple[int, int]] = None  # (first, count) held here; None: all

    @property
    def held_range(self) -> Tuple[int, int]:
        return self.held or (0, self.n_experts)

    def param_count(self, d_model: int) -> int:
        """Parameters of one layer as held here (router, bias, held and
        shared experts)."""
        n_held = self.held_range[1]
        bias = self.n_experts if self.dropless else 0
        return (d_model * self.n_experts + bias + 3 * d_model * self.d_expert * n_held
                + 3 * d_model * self.d_shared)


def init_moe(rng, d_model: int, cfg: MoEConfig, n_layers: int, param_dtype=jnp.float32):
    """Stacked (n_layers, ...) weights of the held experts (all of them
    unless ``cfg.held``), the router over every expert, the shared experts
    where the config has them, and the dropless layer's selection bias."""
    ks = list(jax.random.split(rng, 4)) + list(jax.random.split(jax.random.fold_in(rng, 1), 4))
    e, f = cfg.n_experts, cfg.d_expert
    n_held = cfg.held_range[1]

    def stack(key, shape, scale_dim):
        return (
            jax.random.normal(key, (n_layers,) + shape) * (scale_dim ** -0.5)
        ).astype(param_dtype)

    p = {
        "router": stack(ks[0], (d_model, e), d_model),
        "w_gate": stack(ks[1], (n_held, d_model, f), d_model),
        "w_up": stack(ks[2], (n_held, d_model, f), d_model),
        "w_down": stack(ks[3], (n_held, f, d_model), f),
    }
    if cfg.dropless:
        # the load-balancing bias; held at its seeded value (no gradient)
        p["select_bias"] = stack(ks[4], (e,), 1.0) * 0.1
    if cfg.d_shared:
        p["shared"] = {
            "w_gate": stack(ks[5], (d_model, cfg.d_shared), d_model),
            "w_up": stack(ks[6], (d_model, cfg.d_shared), d_model),
            "w_down": stack(ks[7], (cfg.d_shared, d_model), cfg.d_shared),
        }
    return p


def route(router, bias, x, cfg: MoEConfig):
    """(T, d) -> (gates (T, k) float32, experts (T, k) int32): the top-k of
    the sigmoid scores plus the selection bias (``noaux_tc``), gated by the
    unbiased scores, normalised over the k where the config says so, then
    scaled by ``routed_scale``."""
    scores = jax.nn.sigmoid(x.astype(jnp.float32) @ router.astype(jnp.float32))
    choose = scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, experts = jax.lax.top_k(choose, cfg.top_k)
    gates = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.normalize_top_k:
        gates = gates / (gates.sum(-1, keepdims=True) + 1e-20)
    return gates * cfg.routed_scale, experts


def _grouped(lhs, rhs, group_sizes, valid):
    """``ragged_dot`` over the buffer's routed rows. The chip's kernel
    writes no row past the last group, so those rows, and their rows of
    the products' cotangents, are masked on the way in and out."""
    lhs = jnp.where(valid, lhs, 0)
    return jnp.where(valid, jax.lax.ragged_dot(lhs, rhs, group_sizes), 0)


def moe_dropless(params, x: jnp.ndarray, cfg: MoEConfig):
    """x (T, d) -> (y (T, d), counters). Every token is routed over all
    ``cfg.n_experts``; the slots that go to the held experts are sorted by
    expert and computed as grouped products (three forward, each with its
    two backward products), weighted by their gates and summed per token.
    Slots to absent experts add nothing here: other devices hold them. The
    shared experts are added once.

    Counters (int32 sums): ``expert_slots`` (n_held,), the slots
    routed to each held expert; ``absent_slots``, those routed elsewhere;
    ``rows_routed``, the rows the grouped products had to compute, and
    ``rows_buffer``, the rows of the static buffer that the gather, the
    masks, the SwiGLU and the scatter span whatever the routing."""
    t, d = x.shape
    first, n_held = cfg.held_range
    k = cfg.top_k
    dt = x.dtype
    with jax.named_scope("moe_router"):
        gates, experts = route(params["router"], params["select_bias"], x, cfg)
    with jax.named_scope("moe_dispatch"):
        local = experts.reshape(-1) - first                      # (T*k,)
        held = (local >= 0) & (local < n_held)
        group = jnp.where(held, local, n_held)                   # absent last
        group_sizes = jnp.zeros((n_held + 1,), jnp.int32).at[group].add(1)[:n_held]
        # at most min(k, n_held) of a token's slots are held here: a buffer
        # of that many rows a token holds every routing, however skewed
        rows = t * min(k, n_held)
        order = jnp.argsort(group, stable=True)[:rows]
        token = order // k
        xs = jnp.take(x, token, axis=0)
        routed = group_sizes.sum()
        # a row past the held slots computes nothing and gets no weight
        valid = (jnp.arange(rows) < routed)[:, None]
        w = jnp.where(valid[:, 0], gates.reshape(-1)[order], 0.0)
    with jax.named_scope("moe_experts"):
        h = swiglu(
            _grouped(xs, params["w_gate"].astype(dt), group_sizes, valid),
            _grouped(xs, params["w_up"].astype(dt), group_sizes, valid),
        )
        # the gate weighs the slot before the (linear) down projection
        ys = _grouped(h * w[:, None].astype(dt), params["w_down"].astype(dt), group_sizes,
                      valid)
    with jax.named_scope("moe_combine"):
        y = jnp.zeros((t, d), jnp.float32).at[token].add(ys)
    if cfg.d_shared:
        with jax.named_scope("moe_shared"):
            sp = params["shared"]
            y = y + (swiglu(x @ sp["w_gate"].astype(dt), x @ sp["w_up"].astype(dt))
                     @ sp["w_down"].astype(dt)).astype(jnp.float32)
    counters = {
        "expert_slots": group_sizes,
        "absent_slots": t * k - routed,
        "rows_routed": routed,
        "rows_buffer": jnp.int32(rows),
    }
    return y.astype(dt), counters


def _capacity(group_size: int, cfg: MoEConfig) -> int:
    c = int(group_size * cfg.top_k * cfg.capacity_factor / cfg.n_experts)
    return max(c, cfg.top_k)


def moe_ffn(params, x: jnp.ndarray, cfg: MoEConfig) -> Tuple[jnp.ndarray, dict]:
    """x: (T, d) flattened tokens -> (T, d), plus aux metrics/losses.

    params leaves are per-layer (no leading L dim) — the layer scan slices.
    """
    t, d = x.shape
    g = min(cfg.group_size, t)
    assert t % g == 0, f"token count {t} not divisible by group size {g}"
    n_groups = t // g
    cap = _capacity(g, cfg)
    e = cfg.n_experts

    router = params["router"].astype(jnp.float32)

    def group_step(carry, xg):
        # xg: (g, d)
        logits = xg.astype(jnp.float32) @ router                    # (g, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_i = jax.lax.top_k(probs, cfg.top_k)              # (g, k)
        if cfg.normalize_top_k:
            top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

        # k-hot expert mask with gate values at chosen entries
        khot = jax.nn.one_hot(top_i, e, dtype=jnp.float32)          # (g, k, E)
        gates = (khot * top_p[..., None]).sum(1)                    # (g, E)
        mask = khot.sum(1)                                          # (g, E) 0/1

        # position of each token within its expert's capacity buffer
        pos = jnp.cumsum(mask, axis=0) - 1.0                        # (g, E)
        keep = mask * (pos < cap)
        disp = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=x.dtype)
        disp = disp * keep[..., None].astype(x.dtype)               # (g, E, C)
        combine = disp * gates[..., None].astype(x.dtype)           # (g, E, C)

        # dispatch -> expert GEMMs -> combine
        xe = jnp.einsum("gec,gd->ecd", disp, xg)
        h = swiglu(
            jnp.einsum("ecd,edf->ecf", xe, params["w_gate"].astype(xg.dtype)),
            jnp.einsum("ecd,edf->ecf", xe, params["w_up"].astype(xg.dtype)),
        )
        ye = jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(xg.dtype))
        yg = jnp.einsum("gec,ecd->gd", combine, ye)                 # (g, d)

        # Switch load-balance loss terms: fraction routed vs mean router prob
        f_e = mask.mean(0)          # (E,) fraction of tokens to each expert
        p_e = probs.mean(0)
        aux = e * jnp.sum(f_e * p_e)
        dropped = 1.0 - keep.sum() / jnp.maximum(mask.sum(), 1.0)
        return carry, (yg, aux, dropped)

    if n_groups == 1:
        _, (y, aux, dropped) = group_step(None, x)
        out = y
        aux_mean = aux
        drop_mean = dropped
    elif cfg.vectorize_groups:
        out, aux_mean, drop_mean = _moe_groups_batched(params, x, cfg, n_groups, g, cap)
    else:
        xs = x.reshape(n_groups, g, d)
        _, (ys, auxs, drops) = jax.lax.scan(group_step, None, xs)
        out = ys.reshape(t, d)
        aux_mean = auxs.mean()
        drop_mean = drops.mean()

    metrics = {
        "moe_aux_loss": cfg.router_aux_weight * aux_mean,
        "moe_dropped_frac": drop_mean,
    }
    return out, metrics


def _moe_groups_batched(params, x: jnp.ndarray, cfg: MoEConfig, n_groups: int,
                        g: int, cap: int):
    """All dispatch groups as one batched einsum chain (leading G dim).

    Under GSPMD the G dim inherits the token sharding, so groups execute
    data-parallel; the expert dim stays sharded over "model" (EP). Dispatch
    memory is bounded per device by (G/dp) * g * E * C — the same bound the
    scan enforced globally, now enforced by the sharding.
    """
    t, d = x.shape
    e = cfg.n_experts
    router = params["router"].astype(jnp.float32)
    xs = x.reshape(n_groups, g, d)

    logits = jnp.einsum("Ggd,de->Gge", xs.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.top_k)                # (G, g, k)
    if cfg.normalize_top_k:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    khot = jax.nn.one_hot(top_i, e, dtype=jnp.float32)            # (G, g, k, E)
    gates = (khot * top_p[..., None]).sum(2)                      # (G, g, E)
    mask = khot.sum(2)                                            # (G, g, E)

    pos = jnp.cumsum(mask, axis=1) - 1.0                          # (G, g, E)
    keep = mask * (pos < cap)
    disp = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=x.dtype)
    disp = disp * keep[..., None].astype(x.dtype)                 # (G, g, E, C)
    combine = disp * gates[..., None].astype(x.dtype)

    xe = jnp.einsum("Ggec,Ggd->Gecd", disp, xs)
    h = swiglu(
        jnp.einsum("Gecd,edf->Gecf", xe, params["w_gate"].astype(x.dtype)),
        jnp.einsum("Gecd,edf->Gecf", xe, params["w_up"].astype(x.dtype)),
    )
    ye = jnp.einsum("Gecf,efd->Gecd", h, params["w_down"].astype(x.dtype))
    y = jnp.einsum("Ggec,Gecd->Ggd", combine, ye)                 # (G, g, d)

    f_e = mask.mean(1)                                            # (G, E)
    p_e = probs.mean(1)
    aux = (e * jnp.sum(f_e * p_e, axis=-1)).mean()
    dropped = 1.0 - keep.sum() / jnp.maximum(mask.sum(), 1.0)
    return y.reshape(t, d), aux, dropped
