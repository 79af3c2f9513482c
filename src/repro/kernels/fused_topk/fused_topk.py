"""Blocked QK^T + running top-k Pallas kernel (the serving-side hot loop).

The inference half of the paper's retriever scores every query against the
whole corpus index — a (Q, N) similarity matrix with N in the millions at
production scale. Like the fused InfoNCE kernel this matrix never touches
HBM: the kernel streams (block_q x block_n) tiles through VMEM and folds
each tile into a per-row running top-k scratch (scores + global column ids),
the search-side analogue of fused_infonce's online-softmax accumulator.

Merge semantics per tile: k rounds of selection over the (bq, k) running
best and the (bq, bn) fresh tile — take the row max, then the lowest
candidate id holding it, emit that (score, id) and mask it out. Every id in
the running block comes from an earlier column block, so ties break toward
the lowest column id — exactly ``lax.top_k`` over the full row (ref.py).
The selection is plain max/min/where vector work, which Mosaic lowers
(``lax.top_k`` has no Pallas TPU lowering). Invalid columns (corpus padding,
masked shards) are NEG_INF with id -1, so k > n_valid rows come back with
-1-id tail slots instead of garbage. The running block is kept lane-dense:
k is padded up to a multiple of 128 internally and sliced on return.

Grid layout mirrors fused_infonce_fwd: (Q/bq, N/bn), N innermost so the
top-k scratch carries across column blocks; outputs are written on the last
column step. The contraction dim d is loaded whole per tile (rep_dim <= 8192
fits VMEM). Mixed precision: q/p block loads may be bf16 (the policy's
compute/bank dtypes — a bf16 index halves the tile bytes); every tile matmul
accumulates in fp32 (``preferred_element_type``) and the running scores are
fp32 throughout, so a low-precision index perturbs scores only at input
rounding, never at accumulation.

Inference-only: no VJP — serving never differentiates through search.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels.fused_infonce.fused_infonce import (
    NEG_INF,
    _blocking,
    _pad_axis0,
)


_TAKEN = -3e38  # below NEG_INF: a candidate already emitted this round
_NO_ID = 2 ** 31 - 1


def _topk_kernel(valid_ref, q_ref, p_ref, s_out, i_out, s_scr, i_scr,
                 *, inv_tau, k, bn, n_blocks):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        s_scr[...] = jnp.full_like(s_scr, NEG_INF)
        i_scr[...] = jnp.full_like(i_scr, -1)

    s = jax.lax.dot_general(
        q_ref[...], p_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * inv_tau                                              # (bq, bn)
    vld = valid_ref[...] != 0                                # (1, bn)
    s = jnp.where(vld, s, NEG_INF)
    ids = j * bn + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ids = jnp.where(vld, ids, -1)

    run_s = s_scr[...]                                       # (bq, kp)
    run_i = i_scr[...]
    slot = jax.lax.broadcasted_iota(jnp.int32, run_s.shape, 1)

    def select(r, carry):
        run_s, s, out_s, out_i = carry
        best = jnp.maximum(
            run_s.max(axis=1, keepdims=True), s.max(axis=1, keepdims=True)
        )                                                    # (bq, 1)
        pick = jnp.minimum(
            jnp.where(run_s == best, run_i, _NO_ID).min(axis=1, keepdims=True),
            jnp.where(s == best, ids, _NO_ID).min(axis=1, keepdims=True),
        )
        # an exhausted row (only NEG_INF / taken left) yields an empty slot;
        # empty slots and invalid columns share id -1, so masking by id
        # retires every one of them at once
        live = best > NEG_INF / 2
        out_s = jnp.where(slot == r, jnp.where(live, best, NEG_INF), out_s)
        out_i = jnp.where(slot == r, jnp.where(live, pick, -1), out_i)
        run_s = jnp.where(run_i == pick, _TAKEN, run_s)
        s = jnp.where(ids == pick, _TAKEN, s)
        return run_s, s, out_s, out_i

    _, _, top_s, top_i = jax.lax.fori_loop(
        0, k, select,
        (run_s, s, jnp.full_like(run_s, NEG_INF), jnp.full_like(run_i, -1)),
    )
    s_scr[...] = top_s
    i_scr[...] = top_i

    @pl.when(j == n_blocks - 1)
    def _final():
        s_out[...] = s_scr[...]
        i_out[...] = i_scr[...]


def fused_topk(
    q: jnp.ndarray,                       # (Q, d)
    p: jnp.ndarray,                       # (N, d) corpus index block
    k: int,
    *,
    col_valid: Optional[jnp.ndarray] = None,   # (N,) bool
    inv_tau: float = 1.0,
    block_q: int = 128,
    block_n: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(scores (Q, k) fp32, ids (Q, k) int32); ids are -1 for empty slots.

    Arbitrary Q/N are handled by internal padding (padded rows are sliced
    off, padded columns are marked invalid), matching fused_infonce.
    """
    m, d = q.shape
    n, _ = p.shape
    bq, bn, m_pad, n_pad = _blocking(m, n, block_q, block_n)
    ct = jnp.result_type(q.dtype, p.dtype)
    valid = (
        jnp.ones((n,), jnp.int32)
        if col_valid is None
        else col_valid.astype(jnp.int32)
    )
    q = _pad_axis0(q.astype(ct), m_pad)
    p = _pad_axis0(p.astype(ct), n_pad)
    valid = _pad_axis0(valid, n_pad)[None, :]
    grid = (m_pad // bq, n_pad // bn)
    kp = -(-k // 128) * 128  # lane-dense running block

    kernel = functools.partial(
        _topk_kernel, inv_tau=inv_tau, k=k, bn=bn, n_blocks=grid[1]
    )
    scores, ids = pl.pallas_call(
        kernel,
        grid_spec=pl.GridSpec(
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bn), lambda i, j: (0, j)),
                pl.BlockSpec((bq, d), lambda i, j: (i, 0)),
                pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((bq, kp), lambda i, j: (i, 0)),
                pl.BlockSpec((bq, kp), lambda i, j: (i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, kp), jnp.float32),
                pltpu.VMEM((bq, kp), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((m_pad, kp), jnp.float32),
            jax.ShapeDtypeStruct((m_pad, kp), jnp.int32),
        ],
        interpret=interpret,
    )(valid, q, p)
    return scores[:m, :k], ids[:m, :k]
