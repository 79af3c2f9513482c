"""Jitted public API for the fused top-k scoring kernel.

``fused_topk_scores(q, index, k)`` is the serving analogue of
``fused_infonce_stats``: the (Q, N) score matrix streams tile-by-tile
through VMEM with a per-row running top-k, never materializing in HBM.
Inference-only (no VJP). ``interpret=None`` auto-selects: compiled on TPU,
interpreter elsewhere (CPU-testable) — ``repro.kernels.resolve_interpret``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.fused_topk.fused_topk import fused_topk


def fused_topk_scores(
    q: jnp.ndarray,
    index: jnp.ndarray,
    k: int,
    *,
    col_valid: Optional[jnp.ndarray] = None,
    inv_tau: float = 1.0,
    block_q: int = 128,
    block_n: int = 128,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(scores (Q, k) fp32, ids (Q, k) int32; -1 ids mark empty slots)."""
    return fused_topk(
        q, index, k, col_valid=col_valid, inv_tau=inv_tau,
        block_q=block_q, block_n=block_n,
        interpret=resolve_interpret(interpret),
    )
