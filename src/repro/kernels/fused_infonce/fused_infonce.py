"""Blocked InfoNCE Pallas TPU kernels (the paper's softmax-cost hot spot).

The (M, N) similarity matrix of ContAccum's extended batch
(M, N ~ N_local + N_memory, up to 128k columns at pod scale) never touches
HBM: the forward kernel streams (block_m x block_n) tiles through VMEM with
an online-softmax accumulator (running max / sum-exp scratch), extracting the
positive logit when the row's label falls inside the current column block.
The backward kernels recompute tiles and emit dQ / dP with the same blocking.

Bank-layout support (what core/loss.py's extended matrix needs):
  * ``col_valid`` — per-column validity; invalid columns (bank warm-up slots,
    padding) are masked to NEG_INF inside every tile, so they contribute
    neither to the softmax nor to the gradients (the backward coefficient is
    zeroed for masked columns, matching the dense ``jnp.where`` whose
    gradient w.r.t. a masked logit is exactly zero).
  * ragged M/N — inputs are padded internally to the block grid (padded rows
    are dropped from the outputs, padded columns are masked invalid), so
    batch/bank sizes need not be multiples of the 128-lane MXU tile.
  * ``amax`` output — the per-row running maximum, so callers can derive
    argmax-accuracy (``pos >= amax``) without a second pass.

Grid layout (fwd, dq): (M/bm, N/bn), N innermost so per-row scratch carries
across column blocks; output rows are revisited — final values written on the
last column step. dp uses the transposed grid (N/bn, M/bm).

MXU alignment: block_m/block_n default 128 (fp32 lane width 8x128; the matmul
tiles are 128x128). d (the contraction dim) is loaded whole per tile —
rep_dim <= 8192 fits VMEM comfortably (128 x 8192 x 4B = 4 MiB per operand).

Mixed precision (core/precision.py): q/p block loads may be bf16 (the
policy's compute dtype — halves the VMEM per operand tile and feeds the MXU
its native input width). Mismatched q/p dtypes are reconciled to a common
compute dtype at the entry points below; every tile matmul accumulates in
fp32 (``preferred_element_type``), the online-softmax scratch, lse/pos/amax
outputs and backward coefficients are fp32 throughout (the policy's
``accum_dtype``), and dQ/dP are accumulated in fp32 before a final cast back
to the input dtype — low-precision inputs never degrade the statistics or
the VJP accumulation.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _fwd_kernel(labels_ref, valid_ref, q_ref, p_ref, lse_ref, pos_ref, amax_ref,
                m_scr, l_scr, *, inv_tau, bn, n_blocks):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        pos_ref[...] = jnp.zeros_like(pos_ref)

    s = jax.lax.dot_general(
        q_ref[...],
        p_ref[...],
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * inv_tau  # (bm, bn)
    # invalid columns never enter the softmax (bank warm-up slots, padding)
    vld = valid_ref[...] != 0                                   # (1, bn)
    s = jnp.where(vld, s, NEG_INF)

    # per-row statistics are (bm, 1) columns throughout: 2-D keeps them in
    # the layout Mosaic and XLA agree on for any row count
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.exp(s - m_new).sum(
        axis=-1, keepdims=True
    )
    m_scr[...] = m_new

    # positive logit: label inside this column block?
    local = labels_ref[...] - j * bn                            # (bm, 1)
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) == local
    ).astype(jnp.float32)
    pos_j = (s * onehot).sum(axis=-1, keepdims=True)
    in_blk = onehot.sum(axis=-1, keepdims=True) > 0
    pos_ref[...] = jnp.where(in_blk, pos_j, pos_ref[...])

    @pl.when(j == n_blocks - 1)
    def _final():
        lse_ref[...] = m_scr[...] + jnp.log(l_scr[...])
        amax_ref[...] = m_scr[...]


def _pad_axis0(x: jnp.ndarray, to: int, fill=0):
    n = x.shape[0]
    if n == to:
        return x
    pad = [(0, to - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad, constant_values=fill)


def _blocking(m: int, n: int, block_m: int, block_n: int):
    """Effective block sizes + padded sizes: blocks are clipped to the array,
    then the array is padded up to a whole number of blocks (ragged shapes)."""
    bm = min(block_m, m)
    bn = min(block_n, n)
    m_pad = -(-m // bm) * bm
    n_pad = -(-n // bn) * bn
    return bm, bn, m_pad, n_pad


def _prep_operands(q, p, labels, col_valid, m_pad, n_pad):
    """Pad to the block grid: padded rows are zeros (outputs sliced off),
    padded columns are marked invalid (masked to NEG_INF in-kernel). q/p are
    reconciled to a common compute dtype (dtype-aware block loads: bf16
    stays bf16, mixed bf16/fp32 inputs promote to fp32) — the in-kernel
    matmuls accumulate in fp32 regardless.

    ``labels`` and the validity mask are ordinary VMEM-blocked operands, laid
    out 2-D so each grid step loads only its own (bm, 1) label column and
    (1, bn) validity row: a bank-scale mask never has to fit scalar memory,
    and the kernel body reads vectors, never scalars."""
    n = p.shape[0]
    ct = jnp.result_type(q.dtype, p.dtype)
    valid = (
        jnp.ones((n,), jnp.int32)
        if col_valid is None
        else col_valid.astype(jnp.int32)
    )
    return (
        _pad_axis0(q.astype(ct), m_pad),
        _pad_axis0(p.astype(ct), n_pad),
        _pad_axis0(labels.astype(jnp.int32), m_pad)[:, None],
        _pad_axis0(valid, n_pad)[None, :],
    )


def fused_infonce_fwd(
    q: jnp.ndarray,
    p: jnp.ndarray,
    labels: jnp.ndarray,
    *,
    col_valid: Optional[jnp.ndarray] = None,
    inv_tau: float = 1.0,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool = False,
):
    """Returns (lse, pos, amax) per row; loss = mean(lse - pos).

    ``col_valid`` (N,) masks columns exactly (None = all valid); arbitrary
    M/N are handled by internal padding.
    """
    m, d = q.shape
    n, _ = p.shape
    bm, bn, m_pad, n_pad = _blocking(m, n, block_m, block_n)
    q, p, labels, valid = _prep_operands(q, p, labels, col_valid, m_pad, n_pad)
    grid = (m_pad // bm, n_pad // bn)

    kernel = functools.partial(
        _fwd_kernel, inv_tau=inv_tau, bn=bn, n_blocks=grid[1]
    )
    lse, pos, amax = pl.pallas_call(
        kernel,
        grid_spec=pl.GridSpec(
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((1, bn), lambda i, j: (0, j)),
                pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
                pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            ],
            out_specs=[pl.BlockSpec((bm, 1), lambda i, j: (i, 0))] * 3,
            scratch_shapes=[
                pltpu.VMEM((bm, 1), jnp.float32),
                pltpu.VMEM((bm, 1), jnp.float32),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((m_pad, 1), jnp.float32)] * 3,
        interpret=interpret,
    )(labels, valid, q, p)
    return lse[:m, 0], pos[:m, 0], amax[:m, 0]


def _coeff(s, vld, lse_rows, labels, col0, g_lse, g_pos):
    """Per-tile cotangent of the logits: prob * g_lse + onehot * g_pos.
    Zero for invalid columns — the dense path's ``where`` mask has exactly
    zero gradient w.r.t. a masked logit. ``labels`` is the (bm, 1) label
    column, ``vld`` the (1, bn) validity row; row statistics are (bm, 1)."""
    prob = jnp.exp(s - lse_rows)
    onehot = (
        jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) == labels - col0
    ).astype(jnp.float32)
    coeff = prob * g_lse + onehot * g_pos
    return jnp.where(vld, coeff, 0.0)


def _dq_kernel(labels_ref, valid_ref, q_ref, p_ref, lse_ref, glse_ref, gpos_ref,
               dq_ref, *, inv_tau, bn):
    """dQ = sum over column blocks of coeff @ P * inv_tau."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    s = jax.lax.dot_general(
        q_ref[...], p_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * inv_tau
    vld = valid_ref[...] != 0
    s = jnp.where(vld, s, NEG_INF)
    coeff = _coeff(s, vld, lse_ref[...], labels_ref[...], j * bn,
                   glse_ref[...], gpos_ref[...]) * inv_tau
    dq_ref[...] += jax.lax.dot_general(
        coeff.astype(p_ref.dtype), p_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dq_ref.dtype)


def _dp_kernel(labels_ref, valid_ref, q_ref, p_ref, lse_ref, glse_ref, gpos_ref,
               dp_ref, *, inv_tau, bn):
    """dP = sum over row blocks of coeff^T @ Q * inv_tau.
    Grid: (N/bn, M/bm) — column blocks outer, row blocks inner (accumulated)."""
    i = pl.program_id(1)
    j = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dp_ref[...] = jnp.zeros_like(dp_ref)

    s = jax.lax.dot_general(
        q_ref[...], p_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * inv_tau  # (bm, bn)
    vld = valid_ref[...] != 0
    s = jnp.where(vld, s, NEG_INF)
    coeff = _coeff(s, vld, lse_ref[...], labels_ref[...], j * bn,
                   glse_ref[...], gpos_ref[...]) * inv_tau
    dp_ref[...] += jax.lax.dot_general(
        coeff.astype(q_ref.dtype), q_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dp_ref.dtype)


def fused_infonce_bwd(
    q, p, labels, lse, g_lse, g_pos,
    *,
    col_valid: Optional[jnp.ndarray] = None,
    inv_tau: float = 1.0,
    block_m: int = 128,
    block_n: int = 128,
    interpret: bool = False,
):
    """Exact VJP given the per-row cotangents of (lse, pos)."""
    m, d = q.shape
    n, _ = p.shape
    bm, bn, m_pad, n_pad = _blocking(m, n, block_m, block_n)
    q, p, labels, valid = _prep_operands(q, p, labels, col_valid, m_pad, n_pad)
    # padded rows carry zero cotangents and lse=0, so their uniform
    # exp(0 - 0) probabilities never reach dQ/dP. Statistics and cotangents
    # are fp32 in-kernel whatever dtype q/p arrive in (accum_dtype contract).
    lse, g_lse, g_pos = (
        _pad_axis0(x.astype(jnp.float32), m_pad)[:, None]
        for x in (lse, g_lse, g_pos)
    )
    grid_q = (m_pad // bm, n_pad // bn)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, inv_tau=inv_tau, bn=bn),
        grid_spec=pl.GridSpec(
            grid=grid_q,
            in_specs=[
                pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((1, bn), lambda i, j: (0, j)),
                pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
                pl.BlockSpec((bn, d), lambda i, j: (j, 0)),
            ] + [pl.BlockSpec((bm, 1), lambda i, j: (i, 0))] * 3,
            out_specs=pl.BlockSpec((bm, d), lambda i, j: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m_pad, d), jnp.float32),
        interpret=interpret,
    )(labels, valid, q, p, lse, g_lse, g_pos)

    grid_p = (n_pad // bn, m_pad // bm)
    dp = pl.pallas_call(
        functools.partial(_dp_kernel, inv_tau=inv_tau, bn=bn),
        grid_spec=pl.GridSpec(
            grid=grid_p,
            in_specs=[
                pl.BlockSpec((bm, 1), lambda j, i: (i, 0)),
                pl.BlockSpec((1, bn), lambda j, i: (0, j)),
                pl.BlockSpec((bm, d), lambda j, i: (i, 0)),
                pl.BlockSpec((bn, d), lambda j, i: (j, 0)),
            ] + [pl.BlockSpec((bm, 1), lambda j, i: (i, 0))] * 3,
            out_specs=pl.BlockSpec((bn, d), lambda j, i: (j, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, d), jnp.float32),
        interpret=interpret,
    )(labels, valid, q, p, lse, g_lse, g_pos)

    return dq[:m].astype(q.dtype), dp[:n].astype(p.dtype)
