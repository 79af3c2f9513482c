"""Jitted public API for the fused InfoNCE kernel with a custom VJP.

``fused_infonce_stats(q, p, labels, col_valid)`` returns per-row
``(lse, pos, amax)`` — everything the loss backend in core/loss.py needs:
``loss = mean(lse - pos)`` (or any per-row weighting, the VJP takes arbitrary
row cotangents) and ``pos >= amax`` recovers argmax-accuracy. None of it
materializes the (M, N) similarity matrix in either direction.

``amax`` is a metrics-only output: its cotangent is discarded by the VJP, so
callers must wrap any use of it in ``jax.lax.stop_gradient``.

The per-row ``(lse, pos, amax)`` triple is also the kernel's *carried
online-softmax state*: ``lse`` is the sufficient statistic of the running
(max, sum-exp) pair the kernel maintains across column tiles, so stats
computed over disjoint column chunks (e.g. one memory-bank shard at a time
as it streams around a device ring) compose into the stats of the full
column set with ``merge_row_stats`` — exactly, not approximately. The
gradients compose too: differentiating through the merge scales each chunk's
``g_lse`` cotangent by ``exp(lse_chunk - lse_global)``, which turns every
chunk-local softmax coefficient ``exp(s - lse_chunk)`` into the *global*
coefficient ``exp(s - lse_global)`` inside the chunk's custom VJP — so dQ
accumulates across chunk calls and each chunk's dP stays exact without the
(M, N_total) matrix ever existing on one device.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.fused_infonce.fused_infonce import (
    fused_infonce_bwd,
    fused_infonce_fwd,
)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def fused_infonce_stats(q, p, labels, col_valid, inv_tau=1.0, block_m=128,
                        block_n=128, interpret=None):
    """(lse, pos, amax) per row. Differentiable w.r.t. q and p; ``col_valid``
    ((N,) bool or None) masks columns out of the softmax and the gradients.
    ``interpret=None``: compiled on TPU, interpreter elsewhere."""
    return fused_infonce_fwd(
        q, p, labels, col_valid=col_valid, inv_tau=inv_tau,
        block_m=block_m, block_n=block_n,
        interpret=resolve_interpret(interpret),
    )


def _stats_fwd(q, p, labels, col_valid, inv_tau, block_m, block_n, interpret):
    lse, pos, amax = fused_infonce_stats(
        q, p, labels, col_valid, inv_tau, block_m, block_n, interpret
    )
    return (lse, pos, amax), (q, p, labels, col_valid, lse)


def _stats_bwd(inv_tau, block_m, block_n, interpret, res, cotangents):
    q, p, labels, col_valid, lse = res
    g_lse, g_pos, _ = cotangents  # amax is metrics-only: cotangent discarded
    dq, dp = fused_infonce_bwd(
        q, p, labels, lse, g_lse, g_pos, col_valid=col_valid,
        inv_tau=inv_tau, block_m=block_m, block_n=block_n,
        interpret=resolve_interpret(interpret),
    )
    return dq, dp, None, None


fused_infonce_stats.defvjp(_stats_fwd, _stats_bwd)


def merge_row_stats(lse_chunks, pos_chunks, owns_chunks, amax_chunks):
    """Compose per-chunk row statistics over a *partition* of the column set
    into the statistics of the full set.

    Args (all stacked along a leading chunk axis, shapes (C, M)):
      lse_chunks:  per-chunk ``logsumexp`` rows — the carried softmax state.
      pos_chunks:  per-chunk positive logits; only the owning chunk's value
                   is read (non-owners may carry anything).
      owns_chunks: bool — True where the row's positive column lies inside
                   that chunk. Each row must be owned by exactly one chunk.
      amax_chunks: per-chunk running row maxima (metrics-only, like ``amax``).

    Returns (lse, pos, amax) over the union of the chunks' columns. The merge
    is the online-softmax combine in lse form:
    ``lse = log sum_k exp(lse_k)`` — exact because ``exp(lse_k)`` is chunk
    k's sum of ``exp(s)``. Differentiable in ``lse_chunks``/``pos_chunks``
    (the chain rule routes ``exp(lse_k - lse)`` back to chunk k, and the pos
    cotangent to the owning chunk only); ``amax`` stays metrics-only.

    Chunks with zero valid columns are safe: their logits are masked to the
    finite ``NEG_INF`` (-1e30) sentinel, so their ``exp(lse_k - lse)`` weight
    underflows to exactly 0 rather than producing NaNs.
    """
    lse = jax.nn.logsumexp(lse_chunks, axis=0)
    pos = jnp.sum(jnp.where(owns_chunks, pos_chunks, 0.0), axis=0)
    amax = jnp.max(amax_chunks, axis=0)
    return lse, pos, amax


def fused_infonce_rows(q, p, labels, inv_tau=1.0, block_m=128, block_n=128,
                       interpret=None):
    """(lse, pos) per row, all columns valid. Differentiable w.r.t. q and p."""
    lse, pos, _ = fused_infonce_stats(
        q, p, labels, None, inv_tau, block_m, block_n, interpret
    )
    return lse, pos


def fused_infonce_loss(
    q: jnp.ndarray,
    p: jnp.ndarray,
    labels: Optional[jnp.ndarray] = None,
    *,
    col_valid: Optional[jnp.ndarray] = None,
    temperature: float = 1.0,
    block_m: int = 128,
    block_n: int = 128,
    interpret: Optional[bool] = None,
):
    """Mean InfoNCE over rows (``interpret`` as in ``fused_infonce_stats``)."""
    if labels is None:
        labels = jnp.arange(q.shape[0], dtype=jnp.int32)
    lse, pos, _ = fused_infonce_stats(
        q, p, labels, col_valid, 1.0 / temperature, block_m, block_n, interpret
    )
    return jnp.mean(lse - pos)
