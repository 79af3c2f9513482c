"""BERT-base encoder — the paper's backbone (bert-base-uncased) for the DPR
dual encoder. Post-LN transformer with learned positional embeddings, GELU
FFN, biases throughout, [CLS] representation (DPR uses the raw final-layer
[CLS], no pooler head)."""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.attention import attention


@dataclasses.dataclass(frozen=True)
class BertConfig:
    name: str = "bert-base-uncased"
    n_layers: int = 12
    d_model: int = 768
    n_heads: int = 12
    d_ff: int = 3072
    vocab_size: int = 30522
    max_position: int = 512
    type_vocab: int = 2
    norm_eps: float = 1e-12
    dtype: Any = jnp.float32
    param_dtype: Any = jnp.float32
    attention_impl: str = "plain"
    remat: str = "none"
    scan_layers: bool = True

    @property
    def dh(self) -> int:
        return self.d_model // self.n_heads

    def with_precision(self, policy) -> "BertConfig":
        """Bind a PrecisionPolicy (core/precision.py; instance or preset
        name): params are *stored* in ``param_dtype`` (fp32 masters in every
        preset) and cast to ``compute_dtype`` at application — ``dtype``
        drives every activation matmul below, and layer_norm keeps its fp32
        internals (models/layers.py), matching the policy's fp32
        ``accum_dtype`` for normalization statistics."""
        import dataclasses as _dc

        from repro.core.precision import resolve_precision

        policy = resolve_precision(policy)
        return _dc.replace(
            self, dtype=policy.compute_dtype, param_dtype=policy.param_dtype
        )

    def param_count(self) -> int:
        d = self.d_model
        per_layer = 4 * d * d + 4 * d + 2 * d * self.d_ff + self.d_ff + d + 4 * d
        emb = (self.vocab_size + self.max_position + self.type_vocab) * d + 2 * d
        return self.n_layers * per_layer + emb


def tiny_bert(vocab: int = 1000) -> BertConfig:
    """Two-layer, d=64 tower: the drivers' default, sized for CPU tests."""
    return BertConfig(
        name="bert-tiny",
        n_layers=2,
        d_model=64,
        n_heads=4,
        d_ff=128,
        vocab_size=vocab,
        max_position=64,
    )


def init_bert(rng, cfg: BertConfig):
    d, nl = cfg.d_model, cfg.n_layers
    ks = jax.random.split(rng, 10)
    pd = cfg.param_dtype

    def stack(key, shape, fan_in):
        return (jax.random.normal(key, (nl,) + shape) * (fan_in ** -0.5)).astype(pd)

    return {
        "embed": {
            "word": (jax.random.normal(ks[0], (cfg.vocab_size, d)) * 0.02).astype(pd),
            "pos": (jax.random.normal(ks[1], (cfg.max_position, d)) * 0.02).astype(pd),
            "type": (jax.random.normal(ks[2], (cfg.type_vocab, d)) * 0.02).astype(pd),
            "ln_s": jnp.ones((d,), pd),
            "ln_b": jnp.zeros((d,), pd),
        },
        "layers": {
            "wqkv": stack(ks[3], (d, 3 * d), d),
            "bqkv": jnp.zeros((nl, 3 * d), pd),
            "wo": stack(ks[4], (d, d), d),
            "bo": jnp.zeros((nl, d), pd),
            "ln1_s": jnp.ones((nl, d), pd),
            "ln1_b": jnp.zeros((nl, d), pd),
            "w1": stack(ks[5], (d, cfg.d_ff), d),
            "b1": jnp.zeros((nl, cfg.d_ff), pd),
            "w2": stack(ks[6], (cfg.d_ff, d), cfg.d_ff),
            "b2": jnp.zeros((nl, d), pd),
            "ln2_s": jnp.ones((nl, d), pd),
            "ln2_b": jnp.zeros((nl, d), pd),
        },
    }


def bert_hidden(params, cfg: BertConfig, tokens: jnp.ndarray, mask: Optional[jnp.ndarray] = None):
    """tokens (B, S) -> final hidden states (B, S, d)."""
    b, s = tokens.shape
    dt = cfg.dtype
    if mask is None:
        mask = jnp.ones((b, s), bool)
    emb = params["embed"]
    x = (
        jnp.take(emb["word"], tokens, axis=0)
        + emb["pos"][None, :s]
        + emb["type"][0][None, None]
    ).astype(dt)
    x = L.layer_norm(emb["ln_s"], emb["ln_b"], x, eps=cfg.norm_eps)

    h, dh, d = cfg.n_heads, cfg.dh, cfg.d_model

    def layer_fn(x, lp):
        qkv = x @ lp["wqkv"].astype(dt) + lp["bqkv"].astype(dt)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, s, h, dh)
        k = k.reshape(b, s, h, dh)
        v = v.reshape(b, s, h, dh)
        o = attention(q, k, v, impl=cfg.attention_impl, causal=False, kv_mask=mask)
        att = o.reshape(b, s, d) @ lp["wo"].astype(dt) + lp["bo"].astype(dt)
        x = L.layer_norm(lp["ln1_s"], lp["ln1_b"], x + att, eps=cfg.norm_eps)
        ff = L.gelu(x @ lp["w1"].astype(dt) + lp["b1"].astype(dt))
        ff = ff @ lp["w2"].astype(dt) + lp["b2"].astype(dt)
        x = L.layer_norm(lp["ln2_s"], lp["ln2_b"], x + ff, eps=cfg.norm_eps)
        return x, None

    if cfg.remat != "none":
        layer_fn = jax.checkpoint(layer_fn)

    if cfg.scan_layers:
        x, _ = jax.lax.scan(layer_fn, x, params["layers"])
    else:
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            x, _ = layer_fn(x, lp)
    return x


def bert_encode(params, cfg: BertConfig, tokens: jnp.ndarray, mask: Optional[jnp.ndarray] = None):
    """[CLS] representation, (B, d) — DPR's sentence embedding."""
    return bert_hidden(params, cfg, tokens, mask)[:, 0]
