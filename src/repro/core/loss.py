"""The canonical contrastive step loss shared by every update method.

Single loss assembly covering:
  - plain in-batch negatives (DPR / GradAccum / GradCache): no extras;
  - ContAccum's extended similarity matrix (paper Eq. 5-7): dual banks;
  - pre-batch negatives ablation: passage-only bank;
  - cross-device negatives: columns are all-gathered across the DP axes and
    each device reduces over its own rows (see core/dist.py).

The row-level softmax statistics are computed by a pluggable ``LossBackend``:

  * ``dense`` (default) — materializes the (M, N) logits block with one
    einsum; exact, simple, and fine while M*N fits comfortably in HBM.
  * ``fused`` — the blocked online-softmax Pallas kernel
    (kernels/fused_infonce): streams (block_m x block_n) tiles through VMEM,
    so the extended similarity matrix of ContAccum's dual banks (up to 128k
    columns at pod scale) never touches HBM, in either direction of the
    custom VJP. Gradient-exact vs ``dense`` to fp32 tolerance
    (tests/test_fused_infonce.py); runs under ``interpret=True`` on CPU so
    the whole method matrix is testable without a TPU.

Select with ``ContrastiveConfig.loss_impl`` (threaded through
``build_step_program`` and every NegativeSource) or pass ``backend=`` here
directly. Both backends honor the same contract: per-row ``lse - pos`` with
invalid columns masked exactly, arbitrary per-row weighting (ExtraRows), and
argmax accuracy.

Column assembly is *source-driven*: a NegativeSource (core/step_program.py)
describes where its negatives come from with two declarative blocks —
``ExtraColumns`` (extra similarity columns + validity mask) and ``ExtraRows``
(extra replicated query rows + their labels into the extra-column block) —
and ``contrastive_loss`` assembles the matrix. The legacy bank-taking entry
point ``contrastive_step_loss`` is a thin wrapper that converts dual banks
into those blocks.

Row/column layout (global view):

  rows    = [ global queries (B_g) ] ++ [ extra rows (R) ]
  columns = [ global positives (B_g) ] ++ [ global hard negs (B_g*H) ]
            ++ [ extra columns (C) ]

Labels: global query i -> column i; extra row j -> column
B_g*(1+H) + extra_rows.labels[j]. Invalid extra slots are masked exactly
(warm-up phase). In distributed mode a device owns its local query rows plus
a 1/D share of the (replicated) extra rows, so the psum over devices
reproduces the global row sum exactly once.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Protocol, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core.dist import DistCtx
from repro.core.infonce import NEG_INF
from repro.core.memory_bank import BankState, aligned_valid, columns_view
from repro.core.precision import STATS_DTYPE, PrecisionPolicy, resolve_precision


class LossAux(NamedTuple):
    loss: jnp.ndarray          # global scalar loss (already psum'ed)
    accuracy: jnp.ndarray      # global accuracy over valid rows
    n_rows: jnp.ndarray        # global number of rows in the mean
    n_negatives: jnp.ndarray   # valid columns - 1 (negatives per query)
    q_global: jnp.ndarray      # gathered query reps (for bank push)
    p_global: jnp.ndarray      # gathered positive-passage reps (for bank push)


class ExtraColumns(NamedTuple):
    """Extra similarity columns owned by a negative source (e.g. a passage
    bank). ``valid`` masks slots exactly (False slots never enter the
    softmax).

    ``sharded=False`` (default): ``reps`` is the full (global) column block,
    present on every device. ``sharded=True``: ``reps`` is this device's
    ``C_global / D`` shard of a block laid out shard-major over the DP ring
    (shard s owns global columns ``[s*C_local, (s+1)*C_local)``), and the
    loss streams the shards around the ring (``loss_comm='ring'``) instead
    of all-gathering them — same math, ``O(C_global·d / D)`` peak transient
    memory."""

    reps: jnp.ndarray   # (C, d)
    valid: jnp.ndarray  # (C,) bool
    sharded: bool = False


class ExtraRows(NamedTuple):
    """Extra query rows owned by a negative source (e.g. a query bank).

    ``sharded=False`` (default): rows are replicated across devices; each
    device contributes a 1/D share so the psum reproduces their sum exactly
    once. ``sharded=True``: each device's rows are a distinct 1/D partition
    of the global row set (sharded memory banks) and enter the sum at full
    weight — the psum still counts every global row exactly once. ``labels``
    index into the source's ExtraColumns block *in its global (gathered)
    layout* (the loss adds the in-batch column offset). ``weight`` in [0, 1]
    scales each row's contribution (0 masks it out)."""

    reps: jnp.ndarray    # (R, d)
    labels: jnp.ndarray  # (R,) int32 — positive's index within ExtraColumns
    weight: jnp.ndarray  # (R,) float32
    sharded: bool = False


# --------------------------------------------------------------------------
# Loss backends: how the (rows x columns) softmax statistics are computed
# --------------------------------------------------------------------------
class LossBackend(Protocol):
    """Computes the per-row softmax statistics of one row block against the
    assembled column set. Implementations must agree to fp32 tolerance.

    Precision contract: ``q_rows``/``p_all`` may arrive in any float dtype
    (the PrecisionPolicy's compute dtype — bf16 under the ``bf16``/
    ``bf16_banks`` presets); every softmax statistic (logits, lse, pos,
    accuracy indicator) is computed and returned in fp32 (the policy's
    ``accum_dtype``) regardless, so low-precision inputs never degrade the
    statistics themselves (tests/test_precision.py pins this)."""

    name: str

    def row_stats(
        self,
        q_rows: jnp.ndarray,     # (M, d) query rows
        p_all: jnp.ndarray,      # (N, d) assembled columns
        labels: jnp.ndarray,     # (M,) int32 — positive column per row
        col_mask: jnp.ndarray,   # (N,) bool — invalid columns masked exactly
        *,
        temperature: float,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (per_row_loss, correct): ``lse - pos`` per row
        (differentiable w.r.t. q_rows / p_all) and the stop-gradient
        argmax-accuracy indicator (backends may differ on exact logit
        ties — a measure-zero, metrics-only discrepancy)."""
        ...

    def chunk_stats(
        self,
        q_rows: jnp.ndarray,     # (M, d) query rows
        p_chunk: jnp.ndarray,    # (N_c, d) one chunk of the column set
        labels: jnp.ndarray,     # (M,) int32 — chunk-local, may be out of range
        col_mask: jnp.ndarray,   # (N_c,) bool
        *,
        temperature: float,
    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        """Per-chunk carried online-softmax state ``(lse, pos, amax)`` for the
        ring-streamed loss. ``labels`` are chunk-local indices; rows whose
        positive lies in another chunk carry out-of-range labels and must get
        ``pos = 0`` with zero gradient. Stats from disjoint chunks compose
        exactly via ``kernels.fused_infonce.ops.merge_row_stats``."""
        ...


class DenseLossBackend:
    """One einsum materializes the (M, N) logits block — the reference path."""

    name = "dense"

    def row_stats(self, q_rows, p_all, labels, col_mask, *, temperature):
        logits = jnp.einsum(
            "md,nd->mn", q_rows, p_all, preferred_element_type=jnp.float32
        ) / jnp.asarray(temperature, STATS_DTYPE)
        logits = jnp.where(col_mask[None, :], logits, NEG_INF)
        lse = jax.nn.logsumexp(logits, axis=-1)
        pos = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        correct = (jnp.argmax(logits, axis=-1) == labels).astype(STATS_DTYPE)
        return lse - pos, correct

    def chunk_stats(self, q_rows, p_chunk, labels, col_mask, *, temperature):
        logits = jnp.einsum(
            "md,nd->mn", q_rows, p_chunk, preferred_element_type=jnp.float32
        ) / jnp.asarray(temperature, STATS_DTYPE)
        logits = jnp.where(col_mask[None, :], logits, NEG_INF)
        lse = jax.nn.logsumexp(logits, axis=-1)
        n = p_chunk.shape[0]
        owns = (labels >= 0) & (labels < n)
        safe = jnp.clip(labels, 0, n - 1)
        pos = jnp.take_along_axis(logits, safe[:, None], axis=-1)[:, 0]
        # non-owning rows: pos = 0 and, via where, exactly zero gradient
        pos = jnp.where(owns, pos, jnp.zeros((), STATS_DTYPE))
        return lse, pos, jnp.max(logits, axis=-1)


@dataclasses.dataclass(frozen=True)
class FusedLossBackend:
    """Blocked online-softmax Pallas kernel (kernels/fused_infonce): the
    logits block lives tile-by-tile in VMEM, never in HBM. ``interpret=None``
    auto-selects: compiled on TPU, interpreter elsewhere (CPU-testable)."""

    block_m: int = 128
    block_n: int = 128
    interpret: Optional[bool] = None

    name = "fused"

    def row_stats(self, q_rows, p_all, labels, col_mask, *, temperature):
        from repro.kernels.fused_infonce.ops import fused_infonce_stats

        # q/p may be bf16 (compute dtype); the kernel casts block loads to a
        # common dtype and keeps all statistics + VJP accumulation in fp32
        lse, pos, amax = fused_infonce_stats(
            q_rows,
            p_all,
            labels.astype(jnp.int32),
            col_mask,
            1.0 / float(temperature),
            self.block_m,
            self.block_n,
            self.interpret,
        )
        # amax is metrics-only (its VJP cotangent is discarded by the kernel).
        # Tie semantics differ from dense on exact fp32 logit ties: here a
        # tied positive counts as correct, while dense argmax breaks ties by
        # column index — losses/gradients are unaffected.
        correct = jax.lax.stop_gradient((pos >= amax).astype(STATS_DTYPE))
        return lse - pos, correct

    def chunk_stats(self, q_rows, p_chunk, labels, col_mask, *, temperature):
        from repro.kernels.fused_infonce.ops import fused_infonce_stats

        # the kernel handles out-of-range labels natively: the one-hot select
        # never fires, so pos stays 0 with zero gradient — exactly the
        # non-owning-chunk contract
        return fused_infonce_stats(
            q_rows,
            p_chunk,
            labels.astype(jnp.int32),
            col_mask,
            1.0 / float(temperature),
            self.block_m,
            self.block_n,
            self.interpret,
        )


LOSS_BACKENDS = {"dense": DenseLossBackend, "fused": FusedLossBackend}

_DENSE_BACKEND = DenseLossBackend()


def resolve_loss_backend(
    spec: Union[None, str, LossBackend] = None,
) -> LossBackend:
    """None -> dense; a registered name -> fresh instance; an instance -> as
    is. Raises ValueError for unknown names (surfaced at program build)."""
    if spec is None:
        return _DENSE_BACKEND
    if isinstance(spec, str):
        if spec not in LOSS_BACKENDS:
            raise ValueError(
                f"unknown loss_impl {spec!r}; one of {sorted(LOSS_BACKENDS)}"
            )
        return LOSS_BACKENDS[spec]()
    return spec


def contrastive_loss(
    q_local: jnp.ndarray,
    p_pos_local: jnp.ndarray,
    p_hard_local: Optional[jnp.ndarray] = None,
    *,
    extra_cols: Optional[ExtraColumns] = None,
    extra_rows: Optional[ExtraRows] = None,
    temperature: float = 1.0,
    ctx: Optional[DistCtx] = None,
    backend: Union[None, str, LossBackend] = None,
    precision: Union[None, str, PrecisionPolicy] = None,
) -> tuple[jnp.ndarray, LossAux]:
    """Returns (loss_dev, aux). ``loss_dev`` is this device's share of the
    global loss: psum(loss_dev) == global loss; in single-device mode
    loss_dev == global loss. Differentiate loss_dev, then psum the grads.
    ``backend`` selects how the softmax statistics are computed (None ->
    dense einsum; 'fused' -> the blocked Pallas kernel; or an instance).
    ``precision`` (a PrecisionPolicy or preset name) is the single place the
    loss casts: the local representations are cast to ``compute_dtype`` here,
    and the extra column/row blocks (bank buffers, possibly in a narrower
    ``bank_dtype``) are cast to match — no call site needs ad-hoc ``.astype``.
    None keeps the incoming dtypes (fp32 legacy behavior, bit-identical).
    Softmax statistics and the row reductions stay fp32 either way.
    """
    ctx = ctx or DistCtx()
    be = resolve_loss_backend(backend)
    if precision is not None:
        pol = resolve_precision(precision)
        q_local = pol.cast_compute(q_local)
        p_pos_local = pol.cast_compute(p_pos_local)
        p_hard_local = pol.cast_compute(p_hard_local)
    b_local = q_local.shape[0]

    # --- columns (gathered across DP axes) ---
    p_pos = ctx.gather(p_pos_local)
    cols = [p_pos]
    if p_hard_local is not None and p_hard_local.shape[0] > 0:
        cols.append(ctx.gather(p_hard_local))
    b_g = p_pos.shape[0]
    n_hard = 0 if len(cols) == 1 else cols[1].shape[0]

    # ring mode: extra_cols carries only this device's bank shard; the global
    # extra block is the D shards streamed around the ring, never gathered
    ring = extra_cols is not None and extra_cols.sharded
    n_extra_local = 0 if extra_cols is None else extra_cols.reps.shape[0]
    n_extra = n_extra_local * ctx.device_count() if ring else n_extra_local
    if n_extra_local > 0 and not ring:
        cols.append(extra_cols.reps.astype(p_pos.dtype))
    p_all = jnp.concatenate(cols, axis=0)

    col_mask = jnp.ones((b_g + n_hard,), dtype=bool)
    if n_extra_local > 0 and not ring:
        col_mask = jnp.concatenate([col_mask, extra_cols.valid], axis=0)

    # --- local rows: this device's queries ---
    row_offset = ctx.shard_index() * b_local  # global index of local row 0
    labels_local = row_offset + jnp.arange(b_local, dtype=jnp.int32)

    have_extra_rows = (
        extra_rows is not None and extra_rows.reps.shape[0] > 0 and n_extra > 0
    )
    if have_extra_rows:
        labels_extra = (b_g + n_hard + extra_rows.labels.astype(jnp.int32)) % (
            b_g + n_hard + n_extra
        )
        w = extra_rows.weight.astype(STATS_DTYPE)
        # replicated rows: every device computes all R rows, each contributes
        # a 1/D share; sharded rows: the R local rows are this device's own
        # partition of the global set, so they enter at full weight
        inv_d = 1.0 if extra_rows.sharded else 1.0 / ctx.device_count()

    if ring:
        # evaluate local queries and (sharded) bank rows in one ring pass:
        # block A (the gathered in-batch columns) plus D rotating bank shards
        rows = [q_local]
        labels_all = [labels_local]
        if have_extra_rows:
            rows.append(extra_rows.reps.astype(q_local.dtype))
            labels_all.append(labels_extra)
        per_row, correct = _ring_row_stats(
            jnp.concatenate(rows, axis=0),
            jnp.concatenate(labels_all, axis=0),
            p_all,
            extra_cols,
            ctx,
            be,
            temperature=temperature,
        )
        loss_sum = per_row[:b_local].sum()
        correct_sum = correct[:b_local].sum()
        n_rows_dev = jnp.asarray(b_local, STATS_DTYPE)
        if have_extra_rows:
            loss_sum = loss_sum + inv_d * jnp.sum(per_row[b_local:] * w)
            correct_sum = correct_sum + inv_d * jnp.sum(correct[b_local:] * w)
            n_rows_dev = n_rows_dev + inv_d * w.sum()
        # the global column mask never materializes: count valid bank slots
        # with a psum over the shards instead
        n_cols_valid = jnp.asarray(b_g + n_hard, STATS_DTYPE) + ctx.psum(
            extra_cols.valid.sum().astype(STATS_DTYPE)
        )
    else:
        def row_stats(q_rows, labels):
            return be.row_stats(
                q_rows, p_all, labels, col_mask, temperature=temperature
            )

        per_row_local, correct_local = row_stats(q_local, labels_local)
        loss_sum = per_row_local.sum()
        correct_sum = correct_local.sum()
        n_rows_dev = jnp.asarray(b_local, STATS_DTYPE)

        # --- extra rows (replicated; each device takes a 1/D share) ---
        if have_extra_rows:
            per_row_extra, correct_extra = row_stats(
                extra_rows.reps.astype(q_local.dtype), labels_extra
            )
            loss_sum = loss_sum + inv_d * jnp.sum(per_row_extra * w)
            correct_sum = correct_sum + inv_d * jnp.sum(correct_extra * w)
            n_rows_dev = n_rows_dev + inv_d * w.sum()
        n_cols_valid = col_mask.sum().astype(STATS_DTYPE)

    n_rows_g = jax.lax.stop_gradient(ctx.psum(n_rows_dev))
    n_rows_g = jnp.maximum(n_rows_g, 1.0)
    loss_dev = loss_sum / n_rows_g

    aux = LossAux(
        loss=jax.lax.stop_gradient(ctx.psum(loss_dev)),
        accuracy=jax.lax.stop_gradient(ctx.psum(correct_sum) / n_rows_g),
        n_rows=n_rows_g,
        n_negatives=n_cols_valid - 1.0,
        q_global=jax.lax.stop_gradient(ctx.gather(q_local)),
        p_global=jax.lax.stop_gradient(p_pos),
    )
    return loss_dev, aux


def _ring_row_stats(
    q_rows: jnp.ndarray,
    labels: jnp.ndarray,
    p_inbatch: jnp.ndarray,
    extra_cols: ExtraColumns,
    ctx: DistCtx,
    be: LossBackend,
    *,
    temperature: float,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Ring-streamed (per_row_loss, correct) over the full global column set
    [in-batch block (b_g + n_hard)] ++ [bank shard 0] ++ ... ++ [shard D-1]
    without ever materializing more than one ``C_local``-column bank chunk per
    device. ``labels`` are global column indices.

    Each of the 1 + D chunk evaluations produces the backend's carried
    online-softmax state ``(lse, pos, amax)``; ``merge_row_stats`` composes
    them into the exact full-set statistics. The bank shard (reps in the
    bank's storage dtype + validity mask) hops the DP ring D-1 times via
    ``DistCtx.ring_rotate``: at hop k device i holds shard ``(i - k) mod D``,
    whose global column offset positions its chunk-local labels. Peak
    transient memory for the extra block is ``O(C_local·d) = O(C_global·d/D)``
    vs the all-gather path's ``O(C_global·d)``.

    Backward pass: the merge's chain rule scales each chunk's lse cotangent
    by ``exp(lse_k - lse)``, making every chunk-local softmax coefficient
    global; dQ accumulates locally across the chunk calls, and any dP
    cotangent written against a visiting shard rides ppermute's transpose
    (the inverse rotation) back to the owning device. Bank buffers are
    stop_gradient'd at push, so in practice the reverse ring carries zeros —
    but the path is exact regardless.

    Accuracy uses the fused kernel's tie semantics (``pos >= amax``) for both
    backends — on exact fp32 logit ties a tied positive counts as correct,
    a measure-zero metrics-only difference from dense argmax.
    """
    n_a = p_inbatch.shape[0]

    lse_a, pos_a, amax_a = be.chunk_stats(
        q_rows, p_inbatch, labels, jnp.ones((n_a,), dtype=bool),
        temperature=temperature,
    )
    owns_a = (labels >= 0) & (labels < n_a)
    lse_s, pos_s, owns_s, amax_s = _stream_bank_chunks(
        ctx, be, n_a, temperature, q_rows, labels,
        extra_cols.reps, extra_cols.valid,
    )

    from repro.kernels.fused_infonce.ops import merge_row_stats

    lse, pos, amax = merge_row_stats(
        jnp.concatenate([lse_a[None], lse_s], axis=0),
        jnp.concatenate([pos_a[None], pos_s], axis=0),
        jnp.concatenate([owns_a[None], owns_s], axis=0),
        jnp.concatenate([amax_a[None], amax_s], axis=0),
    )
    correct = jax.lax.stop_gradient((pos >= amax).astype(STATS_DTYPE))
    return lse - pos, correct


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _stream_bank_chunks(ctx, be, n_a, temperature, q_rows, labels, reps, valid):
    """Per-chunk stats ``(lse, pos, owns, amax)``, each stacked (D, M), for
    the D bank shards streamed around the DP ring — with a **reverse-streamed
    backward pass**. Plain AD through the rotation loop would save every
    visiting shard as a residual (all D alive at once — O(N_mem*d) again,
    exactly what the ring exists to avoid); the custom VJP instead saves only
    this device's own shard and re-streams the ring during the backward pass,
    recomputing each chunk's forward on the fly (jax.vjp), so at most one
    N_mem/D chunk is resident in either direction. dQ accumulates locally
    across the hops; each visiting shard's dP cotangent accumulates in a
    buffer that travels *with* the shard and is delivered home by the final
    rotation (ppermute's transpose semantics, done by hand here).
    """
    out, _ = _stream_fwd(ctx, be, n_a, temperature, q_rows, labels, reps, valid)
    return out


def _stream_chunk_eval(be, q_rows, labels, reps, valid, offset, *, temperature):
    local_labels = labels - offset
    lse, pos, amax = be.chunk_stats(
        q_rows, reps.astype(q_rows.dtype), local_labels, valid,
        temperature=temperature,
    )
    owns = (local_labels >= 0) & (local_labels < reps.shape[0])
    return lse, pos, owns, amax


def _stream_fwd(ctx, be, n_a, temperature, q_rows, labels, reps, valid):
    d_ring = ctx.device_count()
    cap_local = reps.shape[0]
    sidx = ctx.shard_index()

    # lax.scan (not a Python loop) so the rotating shard is a loop *carry*:
    # one ping-pong buffer regardless of D. An unrolled loop emits D distinct
    # collective-permute results whose buffers stay concurrently live in the
    # compiled program — summing back to the full O(N_mem*d) footprint the
    # ring exists to avoid.
    def hop(shard, k):
        # after k hops of the (i -> i+1) rotation, device i holds the shard
        # pushed by device (i - k) mod D, i.e. global bank columns
        # [owner*cap_local, (owner+1)*cap_local)
        owner = (sidx - k) % d_ring
        reps_k, valid_k = shard
        stats = _stream_chunk_eval(
            be, q_rows, labels, reps_k, valid_k,
            n_a + owner * cap_local, temperature=temperature,
        )
        # rotate the raw storage-dtype buffer: minimal bytes on the wire.
        # Rotating every iteration keeps the scan body uniform; the final
        # hop returns the shard to its owner.
        return ctx.ring_rotate(shard), stats

    _, out = jax.lax.scan(hop, (reps, valid), jnp.arange(d_ring))
    # residuals: this device's own shard only — the visiting shards are
    # re-streamed (recomputed by a second pass around the ring) in _stream_bwd
    return out, (q_rows, labels, reps, valid)


def _stream_bwd(ctx, be, n_a, temperature, res, cotangents):
    q_rows, labels, reps, valid = res
    g_lse, g_pos, _, _ = cotangents  # owns is bool, amax metrics-only
    d_ring = ctx.device_count()
    cap_local = reps.shape[0]
    sidx = ctx.shard_index()

    def hop(carry, inp):
        (reps_k, valid_k), d_reps_k, dq = carry
        k, g_lse_k, g_pos_k = inp
        owner = (sidx - k) % d_ring

        def f(qr, pc):
            lse, pos, _, amax = _stream_chunk_eval(
                be, qr, labels, pc, valid_k,
                n_a + owner * cap_local, temperature=temperature,
            )
            return lse, pos, amax

        # recompute this chunk's forward on the fly (the fwd saved only the
        # local shard): at most one visiting shard plus its cotangent buffer
        # is resident at a time
        _, vjp_fn = jax.vjp(f, q_rows, reps_k)
        dq_k, dp_k = vjp_fn((g_lse_k, g_pos_k, jnp.zeros_like(g_lse_k)))
        # the shard's cotangent buffer travels *with* the shard: every
        # device deposits its contribution as the pair passes through, and
        # the final hop (k = D-1) delivers the accumulated dP to its owner
        rotated = ctx.ring_rotate(
            ((reps_k, valid_k), d_reps_k + dp_k.astype(d_reps_k.dtype))
        )
        return rotated + (dq + dq_k.astype(dq.dtype),), None

    carry0 = (
        (reps, valid),
        jnp.zeros_like(reps),
        jnp.zeros(q_rows.shape, STATS_DTYPE),
    )
    (_, d_reps, dq), _ = jax.lax.scan(
        hop, carry0, (jnp.arange(d_ring), g_lse, g_pos)
    )
    return dq.astype(q_rows.dtype), None, d_reps, None


_stream_bank_chunks.defvjp(_stream_fwd, _stream_bwd)


def bank_extra_columns(bank_p: Optional[BankState]) -> Optional[ExtraColumns]:
    """Passage bank -> extra similarity columns (None when disabled)."""
    if bank_p is None or bank_p.buf.shape[0] == 0:
        return None
    reps, valid = columns_view(bank_p)
    return ExtraColumns(reps=reps, valid=valid)


def bank_extra_rows(
    bank_q: Optional[BankState], bank_p: Optional[BankState]
) -> Optional[ExtraRows]:
    """Dual banks -> extra query rows labeled with their lockstep-aligned
    positives in the passage bank (None unless both banks are enabled)."""
    if bank_q is None or bank_q.buf.shape[0] == 0:
        return None
    if bank_p is None or bank_p.buf.shape[0] == 0:
        return None
    cq = bank_q.buf.shape[0]
    return ExtraRows(
        reps=bank_q.buf,
        labels=jnp.arange(cq, dtype=jnp.int32),
        weight=aligned_valid(bank_q, bank_p).astype(STATS_DTYPE),
    )


def sharded_bank_extra_columns(
    bank_p: Optional[BankState], ctx: DistCtx, comm: str = "all_gather"
) -> Optional[ExtraColumns]:
    """Shard-local passage bank -> extra columns, under the selected
    communication strategy (``ContrastiveConfig.loss_comm``):

    * ``"all_gather"`` — rows and validity are all-gathered over the DP axes
      into the *global* block (shard-major concatenation matches the bank's
      global ring layout — see memory_bank.shard_push). Transient memory per
      loss eval is O(N_mem*d) regardless of D.
    * ``"ring"`` — the shard stays local (``sharded=True``) and the loss
      streams the D shards around the DP ring with ppermute + online-softmax
      merges: same math, O(N_mem*d/D) transient memory. Falls back to the
      gather in single-device mode (where the shard already *is* the bank).
    """
    if bank_p is None or bank_p.buf.shape[0] == 0:
        return None
    if comm == "ring" and ctx.is_distributed:
        return ExtraColumns(reps=bank_p.buf, valid=bank_p.valid, sharded=True)
    return ExtraColumns(reps=ctx.gather(bank_p.buf), valid=ctx.gather(bank_p.valid))


def sharded_bank_extra_rows(
    bank_q: Optional[BankState], bank_p: Optional[BankState], ctx: DistCtx
) -> Optional[ExtraRows]:
    """Shard-local dual banks -> this device's partition of the extra query
    rows. No gather is needed: each device evaluates only its own bank rows
    (labels offset into the gathered column block by the shard's global slot
    offset), and the psum sums every global row exactly once."""
    if bank_q is None or bank_q.buf.shape[0] == 0:
        return None
    if bank_p is None or bank_p.buf.shape[0] == 0:
        return None
    cap_local = bank_q.buf.shape[0]
    offset = jnp.asarray(ctx.shard_index(), jnp.int32) * cap_local
    return ExtraRows(
        reps=bank_q.buf,
        labels=offset + jnp.arange(cap_local, dtype=jnp.int32),
        weight=aligned_valid(bank_q, bank_p).astype(STATS_DTYPE),
        sharded=True,
    )


def contrastive_step_loss(
    q_local: jnp.ndarray,
    p_pos_local: jnp.ndarray,
    p_hard_local: Optional[jnp.ndarray],
    bank_q: Optional[BankState],
    bank_p: Optional[BankState],
    *,
    temperature: float = 1.0,
    ctx: Optional[DistCtx] = None,
    backend: Union[None, str, LossBackend] = None,
    precision: Union[None, str, PrecisionPolicy] = None,
) -> tuple[jnp.ndarray, LossAux]:
    """Legacy bank-taking entry point: dual banks -> extras -> loss."""
    return contrastive_loss(
        q_local,
        p_pos_local,
        p_hard_local,
        extra_cols=bank_extra_columns(bank_p),
        extra_rows=bank_extra_rows(bank_q, bank_p),
        temperature=temperature,
        ctx=ctx,
        backend=backend,
        precision=precision,
    )
