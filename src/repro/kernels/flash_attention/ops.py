"""Public flash-attention API with custom VJP.

Forward: the Pallas kernel. Backward: recompute through the pure-JAX chunked
online-softmax implementation (models/attention.py) — same blocked memory
profile, one implementation to maintain for training. (A fully-Pallas dq/dk/dv
backward is a further §Perf lever; the recompute path is the shipping
default, as in several production JAX attention stacks.)
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
from repro.models.attention import chunked_attention


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, kv_mask, causal, scale, block_q, block_k, interpret):
    return flash_attention_fwd(
        q, k, v, causal=causal, kv_mask=kv_mask, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )


def _fwd(q, k, v, kv_mask, causal, scale, block_q, block_k, interpret):
    out = _flash(q, k, v, kv_mask, causal, scale, block_q, block_k, interpret)
    return out, (q, k, v, kv_mask)


def _bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, kv_mask = res

    def f(q_, k_, v_):
        return chunked_attention(
            q_, k_, v_, causal=causal, kv_mask=kv_mask, scale=scale,
            q_chunk=block_q, kv_chunk=block_k,
        )

    _, vjp = jax.vjp(f, q, k, v)
    dq, dk, dv = vjp(g)
    return dq, dk, dv, None


_flash.defvjp(_fwd, _bwd)


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    kv_mask: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    if kv_mask is None:
        kv_mask = jnp.ones((q.shape[0], k.shape[1]), dtype=bool)
    return _flash(q, k, v, kv_mask, causal, scale, block_q, block_k,
                  resolve_interpret(interpret))
