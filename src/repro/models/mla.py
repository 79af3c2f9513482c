"""Multi-head latent attention (MLA), DeepSeek-V2/V3's attention
(arXiv:2412.19437, section 2.1), in the form without a query LoRA that
DeepSeek-V2-Lite and Moonlight-16B-A3B use.

Per token x (width d), for H heads:

  q          = x W_q                        (H, nope + rope)
  [c, k_pe]  = x W_kv_a                     (kv_lora + rope): the latent and
                                             one RoPE key shared by all heads
  c          = RMSNorm(c)
  [k_no, v]  = c W_kv_b                     (H, nope + v_dim)
  q_pe, k_pe = RoPE(q_pe), RoPE(k_pe)
  attention of [q_no, q_pe] against [k_no, k_pe] over v, scale
  (nope + rope) ** -0.5, then the output projection W_o (H * v_dim, d).

RoPE is applied in the half-split layout (``layers.apply_rotary``), where
the published code interleaves pairs; the two differ by a fixed permutation
of the rope columns of W_q and W_kv_a.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.attention import plain_attention


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def param_count(self, d_model: int, n_heads: int) -> int:
        h, r = n_heads, self.kv_lora_rank
        return (d_model * h * self.qk_head_dim
                + d_model * (r + self.qk_rope_head_dim) + r
                + r * h * (self.qk_nope_head_dim + self.v_head_dim)
                + h * self.v_head_dim * d_model)


def init_mla(rng, d_model: int, n_heads: int, cfg: MLAConfig, n_layers: int,
             param_dtype=jnp.float32) -> dict:
    """Stacked (n_layers, ...) MLA weights, N(0, 1/fan_in)."""
    h, r = n_heads, cfg.kv_lora_rank
    shapes = {
        "wq": (d_model, h * cfg.qk_head_dim),
        "wkv_a": (d_model, r + cfg.qk_rope_head_dim),
        "wkv_b": (r, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": (h * cfg.v_head_dim, d_model),
    }
    keys = jax.random.split(rng, len(shapes))
    p = {
        name: (jax.random.normal(k, (n_layers,) + s) * s[0] ** -0.5).astype(param_dtype)
        for k, (name, s) in zip(keys, shapes.items())
    }
    p["kv_norm"] = jnp.ones((n_layers, r), param_dtype)
    return p


def mla(p, x, cfg: MLAConfig, n_heads: int, cos, sin, *, dtype, eps: float):
    """Causal latent attention, x (B, S, d) -> (B, S, d). ``p``: one
    layer's weights; cos/sin: the rope tables (B, S, rope/2)."""
    b, s, _ = x.shape
    h, nope, rope = n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = (x @ p["wq"].astype(dtype)).reshape(b, s, h, nope + rope)
    kv_a = x @ p["wkv_a"].astype(dtype)
    c = L.rms_norm(p["kv_norm"], kv_a[..., : cfg.kv_lora_rank], eps=eps)
    k_pe = kv_a[..., cfg.kv_lora_rank:].reshape(b, s, 1, rope)
    kv = (c @ p["wkv_b"].astype(dtype)).reshape(b, s, h, nope + cfg.v_head_dim)
    q_pe = L.apply_rotary(q[..., nope:], cos, sin)
    k_pe = L.apply_rotary(k_pe, cos, sin)
    q = jnp.concatenate([q[..., :nope], q_pe], -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (b, s, h, rope))], -1)
    o = plain_attention(q, k, kv[..., nope:], causal=True, scale=cfg.qk_head_dim ** -0.5)
    return o.reshape(b, s, h * cfg.v_head_dim) @ p["wo"].astype(dtype)
