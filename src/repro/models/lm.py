"""Decoder-only causal LM family (covers all five assigned LM architectures).

Modern pre-norm transformer: RMSNorm, RoPE, GQA attention, SwiGLU FFN
(or MoE FFN), optional QKV bias (qwen1.5), untied LM head. The
DeepSeek-V3 block (Moonlight-16B-A3B) is the same skeleton with latent
attention (``mla``, models/mla.py), ``first_dense`` leading dense layers
before the scanned expert stack, and the dropless expert layer
(``MoEConfig.dropless``); as a retriever tower it builds no LM head.

Layers are scanned with stacked parameters so an 80-layer 110B-parameter
model lowers to a compact HLO; remat policy is configurable. The LM loss is
computed in sequence chunks so (B, S, 150k-vocab) logits never materialize.

Three entry points per the assigned shape cells:
  ``train_step_loss``  — causal-LM loss (train_4k)
  ``prefill``          — build KV cache + last-position logits (prefill_32k)
  ``decode_step``      — one new token against a seq_len cache (decode_32k,
                         long_500k; cache seq dim may be sequence-sharded)
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.attention import attention, decode_attention
from repro.models.mla import MLAConfig, init_mla, mla
from repro.models.moe import MoEConfig, init_moe, moe_dropless, moe_ffn


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None        # defaults to d_model // n_heads
    rope_theta: float = 10000.0
    qkv_bias: bool = False
    tie_embeddings: bool = False
    norm_eps: float = 1e-6
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None       # latent attention in place of GQA
    first_dense: int = 0                  # leading dense layers (FFN d_ff)
    head: bool = True                     # build the LM head (towers: no)
    # execution
    # per-arch declaration: LM towers default to bf16 compute (the presets in
    # configs/ override per size); resolve_precision turns this into a policy
    dtype: Any = jnp.bfloat16  # reprolint: disable=RPL001
    param_dtype: Any = jnp.float32
    attention_impl: str = "chunked"
    q_chunk: int = 512
    kv_chunk: int = 1024
    loss_chunk: int = 512                 # sequence chunk for the xent loss
    remat: str = "full"                   # none | full | dots
    scan_layers: bool = True

    @property
    def dh(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    def with_precision(self, policy) -> "LMConfig":
        """Bind a PrecisionPolicy (core/precision.py; instance or preset
        name): params stored in ``param_dtype``, cast to ``compute_dtype``
        where each is used; the router and the norms keep float32."""
        from repro.core.precision import resolve_precision

        policy = resolve_precision(policy)
        return dataclasses.replace(
            self, dtype=policy.compute_dtype, param_dtype=policy.param_dtype
        )

    def param_count(self) -> int:
        d, dh = self.d_model, self.dh
        if self.mla is not None:
            return self._mla_param_count()
        attn = d * (self.n_heads * dh) * 2 + d * (self.n_kv_heads * dh) * 2
        if self.moe:
            ffn = d * self.moe.n_experts * self.moe.d_expert * 3 + d * self.moe.n_experts
        else:
            ffn = d * self.d_ff * 3
        per_layer = attn + ffn + 2 * d
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb + d

    def _mla_param_count(self) -> int:
        """Parameters held here: embedding (and head, if built), the dense
        leading layers, the expert stack with its held experts, norms."""
        d = self.d_model
        attn = self.mla.param_count(d, self.n_heads) + 2 * d
        dense = attn + 3 * d * self.d_ff
        stack = attn + (self.moe.param_count(d) if self.moe else 3 * d * self.d_ff)
        emb = self.vocab_size * d * (2 if self.head and not self.tie_embeddings else 1)
        return self.first_dense * dense + (self.n_layers - self.first_dense) * stack + emb + d

    def active_param_count(self) -> int:
        """Activated parameters per token (MoE: top_k of n_experts)."""
        if not self.moe:
            return self.param_count()
        d, dh = self.d_model, self.dh
        attn = d * (self.n_heads * dh) * 2 + d * (self.n_kv_heads * dh) * 2
        ffn = d * self.moe.top_k * self.moe.d_expert * 3 + d * self.moe.n_experts
        per_layer = attn + ffn + 2 * d
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return self.n_layers * per_layer + emb + d


class KVCache(NamedTuple):
    k: jnp.ndarray        # (L, B, S_max, Hk, Dh)
    v: jnp.ndarray        # (L, B, S_max, Hk, Dh)
    length: jnp.ndarray   # (B,) int32 valid prefix


def init_lm(rng, cfg: LMConfig):
    if cfg.mla is not None:
        return _init_mla_lm(rng, cfg)
    d, dh, h, hk = cfg.d_model, cfg.dh, cfg.n_heads, cfg.n_kv_heads
    nl = cfg.n_layers
    ks = jax.random.split(rng, 12)
    pd = cfg.param_dtype

    def stack(key, shape, fan_in):
        return (jax.random.normal(key, (nl,) + shape) * (fan_in ** -0.5)).astype(pd)

    attn = {
        "wq": stack(ks[0], (d, h * dh), d),
        "wk": stack(ks[1], (d, hk * dh), d),
        "wv": stack(ks[2], (d, hk * dh), d),
        "wo": stack(ks[3], (h * dh, d), h * dh),
    }
    if cfg.qkv_bias:
        attn["bq"] = jnp.zeros((nl, h * dh), pd)
        attn["bk"] = jnp.zeros((nl, hk * dh), pd)
        attn["bv"] = jnp.zeros((nl, hk * dh), pd)

    if cfg.moe is not None:
        ffn = init_moe(ks[4], d, cfg.moe, nl, pd)
    else:
        ffn = {
            "w_gate": stack(ks[5], (d, cfg.d_ff), d),
            "w_up": stack(ks[6], (d, cfg.d_ff), d),
            "w_down": stack(ks[7], (cfg.d_ff, d), cfg.d_ff),
        }

    params = {
        "embed": (jax.random.normal(ks[8], (cfg.vocab_size, d)) * 0.02).astype(pd),
        "layers": {
            "ln1": jnp.ones((nl, d), pd),
            "ln2": jnp.ones((nl, d), pd),
            "attn": attn,
            "ffn": ffn,
        },
        "final_norm": jnp.ones((d,), pd),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(ks[9], (d, cfg.vocab_size)) * (d ** -0.5)
        ).astype(pd)
    return params


def _init_mla_lm(rng, cfg: LMConfig):
    """{"embed", "dense_layers", "layers", "final_norm"[, "lm_head"]}: the
    ``first_dense`` leading layers and the expert stack, each stacked for
    its own scan."""
    d, pd = cfg.d_model, cfg.param_dtype
    nd, nm = cfg.first_dense, cfg.n_layers - cfg.first_dense
    ks = jax.random.split(rng, 8)

    def dense_ffn(key, n):
        kg, ku, kd = jax.random.split(key, 3)
        f = cfg.d_ff
        return {
            "w_gate": (jax.random.normal(kg, (n, d, f)) * d ** -0.5).astype(pd),
            "w_up": (jax.random.normal(ku, (n, d, f)) * d ** -0.5).astype(pd),
            "w_down": (jax.random.normal(kd, (n, f, d)) * f ** -0.5).astype(pd),
        }

    def layers(key_attn, n, ffn):
        return {
            "ln1": jnp.ones((n, d), pd),
            "ln2": jnp.ones((n, d), pd),
            "attn": init_mla(key_attn, d, cfg.n_heads, cfg.mla, n, pd),
            "ffn": ffn,
        }

    moe_ffn_p = (init_moe(ks[4], d, cfg.moe, nm, pd) if cfg.moe is not None
                 else dense_ffn(ks[4], nm))
    params = {
        "embed": (jax.random.normal(ks[0], (cfg.vocab_size, d)) * 0.02).astype(pd),
        "dense_layers": layers(ks[1], nd, dense_ffn(ks[2], nd)),
        "layers": layers(ks[3], nm, moe_ffn_p),
        "final_norm": jnp.ones((d,), pd),
    }
    if cfg.head and not cfg.tie_embeddings:
        params["lm_head"] = (jax.random.normal(ks[5], (d, cfg.vocab_size)) * d ** -0.5).astype(pd)
    return params


def _ffn(cfg: LMConfig, fp, y, dense: bool):
    """The block's feed-forward on normed input y (B, S, d): a dense SwiGLU
    or the expert layer. Returns (out, aux, counters)."""
    b, s, d = y.shape
    dt = cfg.dtype
    if dense or cfg.moe is None:
        out = L.swiglu(y @ fp["w_gate"].astype(dt), y @ fp["w_up"].astype(dt)) @ fp[
            "w_down"
        ].astype(dt)
        return out, {}, {}
    if cfg.moe.dropless:
        ff, counters = moe_dropless(fp, y.reshape(b * s, d), cfg.moe)
        return ff.reshape(b, s, d), {}, counters
    ff, aux = moe_ffn(fp, y.reshape(b * s, d), cfg.moe)
    return ff.reshape(b, s, d), aux, {}


def _mla_block(cfg: LMConfig, lp, x, cos, sin, *, dense: bool):
    """One DeepSeek-V3 block: x + MLA(norm x), then + FFN(norm x).
    Returns (x', counters)."""
    y = L.rms_norm(lp["ln1"], x, eps=cfg.norm_eps)
    with jax.named_scope("mla"):
        x = x + mla(lp["attn"], y, cfg.mla, cfg.n_heads, cos, sin,
                    dtype=cfg.dtype, eps=cfg.norm_eps)
    y = L.rms_norm(lp["ln2"], x, eps=cfg.norm_eps)
    ff, _, counters = _ffn(cfg, lp["ffn"], y, dense)
    return x + ff, counters


def _block(cfg: LMConfig, lp, x, cos, sin, *, kv_mask=None, causal=True):
    """One transformer block. lp: per-layer params (no leading L dim).
    x: (B, S, d). Returns (x', aux_metrics, (k, v))."""
    b, s, d = x.shape
    h, hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    dt = cfg.dtype

    y = L.rms_norm(lp["ln1"], x, eps=cfg.norm_eps)
    ap = lp["attn"]
    q = y @ ap["wq"].astype(dt)
    k = y @ ap["wk"].astype(dt)
    v = y @ ap["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + ap["bq"].astype(dt)
        k = k + ap["bk"].astype(dt)
        v = v + ap["bv"].astype(dt)
    q = q.reshape(b, s, h, dh)
    k = k.reshape(b, s, hk, dh)
    v = v.reshape(b, s, hk, dh)
    q = L.apply_rotary(q, cos, sin)
    k = L.apply_rotary(k, cos, sin)

    o = attention(
        q, k, v,
        impl=cfg.attention_impl,
        causal=causal,
        kv_mask=kv_mask,
        q_chunk=cfg.q_chunk,
        kv_chunk=cfg.kv_chunk,
    )
    x = x + (o.reshape(b, s, h * dh) @ ap["wo"].astype(dt))

    y = L.rms_norm(lp["ln2"], x, eps=cfg.norm_eps)
    ff, aux, _ = _ffn(cfg, lp["ffn"], y, dense=False)
    x = x + ff
    return x, aux, (k, v)


def _remat_wrap(cfg: LMConfig, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn)
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    raise ValueError(f"unknown remat policy {cfg.remat!r}")


def _mla_backbone(params, cfg: LMConfig, tokens: jnp.ndarray):
    """tokens (B, S) -> (final hidden states (B, S, d), counters): the
    dense leading layers, then the expert stack, each one scan. Counters are
    the expert layers', summed over layers except ``expert_slots``, which
    stays per layer (n_layers - first_dense, n_held)."""
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    rope = cfg.mla.qk_rope_head_dim
    cos, sin = L.rotary_embedding(jnp.arange(s), rope, cfg.rope_theta, cfg.dtype)
    cos = jnp.broadcast_to(cos, (b, s, rope // 2))
    sin = jnp.broadcast_to(sin, (b, s, rope // 2))

    def stack(x, layers, dense):
        def layer_fn(x, lp):
            return _mla_block(cfg, lp, x, cos, sin, dense=dense)

        fn = _remat_wrap(cfg, layer_fn)
        if cfg.scan_layers:
            return jax.lax.scan(fn, x, layers)
        n = jax.tree_util.tree_leaves(layers)[0].shape[0]
        outs = []
        for i in range(n):
            x, c = fn(x, jax.tree_util.tree_map(lambda a: a[i], layers))
            outs.append(c)
        return x, jax.tree_util.tree_map(lambda *cs: jnp.stack(cs), *outs) if outs else {}

    if cfg.first_dense:
        x, _ = stack(x, params["dense_layers"], True)
    x, per_layer = stack(x, params["layers"], False)
    counters = {k: (v if k == "expert_slots" else v.sum(0)) for k, v in per_layer.items()}
    return L.rms_norm(params["final_norm"], x, eps=cfg.norm_eps), counters


def backbone(params, cfg: LMConfig, tokens: jnp.ndarray, *, collect_cache: bool = False):
    """tokens (B, S) -> hidden states (B, S, d) [+ stacked (k, v) per layer]."""
    if cfg.mla is not None:
        if collect_cache:
            raise NotImplementedError("latent attention keeps no KV cache here")
        x, _ = _mla_backbone(params, cfg, tokens)
        return x, jnp.zeros((), jnp.float32), None
    b, s = tokens.shape
    x = jnp.take(params["embed"], tokens, axis=0).astype(cfg.dtype)
    pos = jnp.arange(s)
    cos, sin = L.rotary_embedding(pos, cfg.dh, cfg.rope_theta, cfg.dtype)
    cos = jnp.broadcast_to(cos, (b, s, cfg.dh // 2))
    sin = jnp.broadcast_to(sin, (b, s, cfg.dh // 2))

    moe_aux_acc = jnp.zeros((), jnp.float32)

    def layer_fn(carry, lp):
        x, aux_acc = carry
        x, aux, kv = _block(cfg, lp, x, cos, sin, causal=True)
        aux_acc = aux_acc + aux.get("moe_aux_loss", jnp.zeros((), jnp.float32))
        return (x, aux_acc), (kv if collect_cache else None)

    layer_fn = _remat_wrap(cfg, layer_fn)

    if cfg.scan_layers:
        (x, moe_aux_acc), kvs = jax.lax.scan(layer_fn, (x, moe_aux_acc), params["layers"])
    else:
        kv_list = []
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            (x, moe_aux_acc), kv = layer_fn((x, moe_aux_acc), lp)
            kv_list.append(kv)
        kvs = (
            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *kv_list)
            if collect_cache
            else None
        )

    x = L.rms_norm(params["final_norm"], x, eps=cfg.norm_eps)
    return x, moe_aux_acc / cfg.n_layers, kvs


def _head(params, cfg: LMConfig, x):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return x @ w.astype(cfg.dtype)


def lm_loss(params, cfg: LMConfig, tokens: jnp.ndarray, targets: jnp.ndarray):
    """Chunked next-token cross entropy. tokens/targets: (B, S); targets may
    use -1 for padding (masked out). Logits are built loss_chunk columns of
    sequence at a time, so (B, S, V) never materializes."""
    x, moe_aux, _ = backbone(params, cfg, tokens)
    b, s, d = x.shape
    c = min(cfg.loss_chunk, s)
    assert s % c == 0
    n = s // c

    xs = x.reshape(b, n, c, d).transpose(1, 0, 2, 3)
    ts = targets.reshape(b, n, c).transpose(1, 0, 2)

    # checkpoint: without it the scan saves each chunk's (B, c, V) logits for
    # the backward pass — the very tensor the chunking exists to avoid.
    @jax.checkpoint
    def chunk_loss(carry, inp):
        xc, tc = inp
        logits = _head(params, cfg, xc).astype(jnp.float32)   # (B, c, V)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tc_safe = jnp.maximum(tc, 0)
        pos = jnp.take_along_axis(logits, tc_safe[..., None], axis=-1)[..., 0]
        mask = (tc >= 0).astype(jnp.float32)
        loss_sum, count = carry
        return (loss_sum + ((lse - pos) * mask).sum(), count + mask.sum()), None

    (loss_sum, count), _ = jax.lax.scan(
        chunk_loss, (jnp.zeros(()), jnp.zeros(())), (xs, ts)
    )
    loss = loss_sum / jnp.maximum(count, 1.0)
    return loss + moe_aux, {"lm_loss": loss, "moe_aux": moe_aux, "tokens": count}


def prefill(params, cfg: LMConfig, tokens: jnp.ndarray, *, max_seq: Optional[int] = None):
    """Build the KV cache for a prompt; returns (cache, last-position logits)."""
    b, s = tokens.shape
    max_seq = max_seq or s
    x, _, kvs = backbone(params, cfg, tokens, collect_cache=True)
    k, v = kvs  # (L, B, S, Hk, Dh)
    if max_seq > s:
        pad = [(0, 0), (0, 0), (0, max_seq - s), (0, 0), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    cache = KVCache(
        k=k, v=v, length=jnp.full((b,), s, jnp.int32)
    )
    logits = _head(params, cfg, x[:, -1:, :])[:, 0]
    return cache, logits


def decode_step(params, cfg: LMConfig, cache: KVCache, token: jnp.ndarray):
    """One decode step. token: (B,) int32. Returns (new_cache, logits (B, V)).

    The per-layer attention is a softmax over the cache's sequence dim; when
    that dim is sharded ("model"/"data" axes for the long-context shapes) XLA
    emits partial-softmax + all-reduce (distributed flash-decode).
    """
    b = token.shape[0]
    h, hk, dh, d = cfg.n_heads, cfg.n_kv_heads, cfg.dh, cfg.d_model
    dt = cfg.dtype
    x = jnp.take(params["embed"], token[:, None], axis=0).astype(dt)  # (B, 1, d)
    pos = cache.length  # (B,)
    cos, sin = L.rotary_embedding(pos[:, None], dh, cfg.rope_theta, dt)  # (B, 1, dh/2)

    def layer_fn(carry, inp):
        x, = carry
        lp, kc, vc = inp  # kc/vc: (B, S_max, Hk, Dh)
        y = L.rms_norm(lp["ln1"], x, eps=cfg.norm_eps)
        ap = lp["attn"]
        q = y @ ap["wq"].astype(dt)
        k = y @ ap["wk"].astype(dt)
        v = y @ ap["wv"].astype(dt)
        if cfg.qkv_bias:
            q = q + ap["bq"].astype(dt)
            k = k + ap["bk"].astype(dt)
            v = v + ap["bv"].astype(dt)
        q = L.apply_rotary(q.reshape(b, 1, h, dh), cos, sin)
        k = L.apply_rotary(k.reshape(b, 1, hk, dh), cos, sin)
        v = v.reshape(b, 1, hk, dh)

        # write new kv at position `length` (same for all batch rows here)
        idx = pos[0]
        kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype), (0, idx, 0, 0))
        vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype), (0, idx, 0, 0))

        o = decode_attention(q, kc.astype(dt), vc.astype(dt), cache_len=pos + 1)
        x = x + (o.reshape(b, 1, h * dh) @ ap["wo"].astype(dt))

        y = L.rms_norm(lp["ln2"], x, eps=cfg.norm_eps)
        if cfg.moe is not None:
            ff, _ = moe_ffn(lp["ffn"], y.reshape(b, d), cfg.moe)
            ff = ff.reshape(b, 1, d)
        else:
            fp = lp["ffn"]
            ff = L.swiglu(y @ fp["w_gate"].astype(dt), y @ fp["w_up"].astype(dt)) @ fp[
                "w_down"
            ].astype(dt)
        x = x + ff
        return (x,), (kc, vc)

    if cfg.scan_layers:
        (x,), (k_new, v_new) = jax.lax.scan(
            layer_fn, (x,), (params["layers"], cache.k, cache.v)
        )
    else:
        ks, vs = [], []
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            (x,), (kc, vc) = layer_fn((x,), (lp, cache.k[i], cache.v[i]))
            ks.append(kc)
            vs.append(vc)
        k_new, v_new = jnp.stack(ks), jnp.stack(vs)

    x = L.rms_norm(params["final_norm"], x, eps=cfg.norm_eps)
    logits = _head(params, cfg, x)[:, 0]
    return KVCache(k=k_new, v=v_new, length=cache.length + 1), logits


def encode_pooled(params, cfg: LMConfig, tokens: jnp.ndarray, mask: Optional[jnp.ndarray] = None):
    """LM-as-retriever embedding (GTR/E5 style; E5-Mistral, RepLLaMA):
    mean-pool final hidden states over valid positions, summed in float32,
    returned in the compute dtype. Used when the paper's contrastive
    objective rides on a causal-LM backbone. Returns (pooled, the expert
    layers' counters: empty for other towers)."""
    if cfg.mla is not None:
        x, counters = _mla_backbone(params, cfg, tokens)
    else:
        (x, _, _), counters = backbone(params, cfg, tokens), {}
    xf = x.astype(jnp.float32)
    if mask is None:
        pooled = xf.mean(axis=1)
    else:
        m = mask.astype(jnp.float32)[..., None]
        pooled = (xf * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
    pooled = pooled.astype(x.dtype)
    return pooled, counters
