"""Composable contrastive update construction: the `StepProgram` API.

The paper's four methods (and the useful configurations beyond them) are
points in a 2-D design space:

  * **where negatives come from** — a ``NegativeSource``: in-batch only,
    dual FIFO memory banks (ContAccum), a passage-only bank (pre-batch
    negatives), or cross-device-gathered in-batch negatives. A source owns
    its slice of the similarity matrix (extra columns / rows + masks, built
    on core/loss.py's ExtraColumns/ExtraRows) and the bank state carried
    across accumulation chunks.

  * **how the backward pass is scheduled** — a ``BackpropStrategy``: direct
    (one forward/backward over the whole batch), scan-accumulate (K chunks,
    loss restricted to each chunk — paper Eq. 4), or rep-cache VJP
    (GradCache's decomposition, Gao et al. 2021: representation-only
    forward, loss differentiated w.r.t. the representations, per-chunk VJPs
    through the encoders — full-batch gradients at chunked memory).

``build_step_program(encoder, tx, cfg)`` combines one of each into an
``update(state, batch) -> (state, StepMetrics)`` that also owns metric
assembly and bank pushes; all programs are pure and jit/shard_map
compatible. The legacy ``method=`` strings are a thin registry over
compositions (COMPOSITIONS):

    dpr            = direct          x in-batch
    grad_accum     = scan-accumulate x in-batch
    grad_cache     = rep-cache VJP   x in-batch
    contaccum      = scan-accumulate x dual-bank     (the paper's method)
    contcache      = rep-cache VJP   x dual-bank     (new: exact full-batch
                     backprop *and* bank-extended negatives)
    prebatch       = scan-accumulate x passage-bank  (pre-batch ablation)
    prebatch_cache = rep-cache VJP   x passage-bank  (new)
    dpr_xdev       = direct          x gathered      (cross-device in-batch)
    mined          = direct          x mined         (ANCE-style mined
    mined_accum    = scan-accumulate x mined          negatives, injected as
    mined_cache    = rep-cache VJP   x mined          passage_hard columns by
                     repro/mining's asynchronous refresh pipeline)

The four legacy compositions are gradient-exact against the original
monolithic implementations (tests/test_step_program.py).

Orthogonal to both axes, ``cfg.loss_impl`` picks the **LossBackend**
(core/loss.py): 'dense' (einsum logits block, default) or 'fused' (the
blocked online-softmax Pallas kernel) — every source x strategy composition
runs on either backend, gradient-exact to fp32 tolerance
(tests/test_fused_infonce.py).

Orthogonal to everything above, ``cfg.precision`` selects the
**PrecisionPolicy** (core/precision.py): presets ``fp32`` (default,
bit-identical to the historical behavior), ``bf16`` (bf16 encoder compute +
representations, fp32 masters/banks/statistics) and ``bf16_banks`` (bf16
compute *and* bf16 bank buffers). The policy is threaded through every
source x strategy composition: the loss casts representations and bank
blocks to ``compute_dtype`` in one place, the rep_cache representation store
is kept in ``compute_dtype``, bank rings are allocated in ``bank_dtype``,
and softmax statistics / metric reductions / gradient accumulation stay in
``accum_dtype`` (fp32 in every preset). bf16 trajectories track the fp32
reference within documented tolerance for the full matrix
(tests/test_precision.py).

Also orthogonal, ``cfg.shard_banks`` picks the bank **distribution mode**
under shard_map: replicated (default — every device carries the full rings
and pushes the gathered global rows) or sharded (each device owns a
``capacity/D`` ring-slot block; pushes write only local rows, the loss
gathers the passage-bank columns over ``cfg.dp_axis`` and evaluates only the
local query-bank rows). Both modes are trajectory-identical to the
single-device replicated run (tests/test_distributed.py); sharded mode cuts
per-device bank HBM and extra-row compute by 1/D.

On top of sharded banks, ``cfg.loss_comm`` picks how the shard-local passage
columns reach each loss evaluation: ``'all_gather'`` (default) materializes
the global (N_mem, d) block on every device, ``'ring'`` streams the D shards
around the DP ring with ppermute and merges each N_mem/D chunk into the
backend's carried online-softmax state (core/loss.py ``_ring_row_stats``) —
the same loss and gradients (fp summation-order tolerance,
tests/test_ring_parity.py) at O(N_mem*d/D) transient memory per eval.
``'ring'`` requires a bank-consuming source with ``shard_banks=True``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Protocol, Tuple

import jax
import jax.numpy as jnp

from repro.common.treemath import tree_add, tree_scale, tree_zeros_like, tree_global_norm
from repro.core.dist import DistCtx
from repro.core.loss import (
    LossAux,
    LossBackend,
    bank_extra_columns,
    bank_extra_rows,
    contrastive_loss,
    resolve_loss_backend,
    sharded_bank_extra_columns,
    sharded_bank_extra_rows,
)
from repro.core.memory_bank import (
    BankState,
    clear,
    init_bank,
    push,
    push_pair,
    shard_push,
    shard_push_pair,
)
from repro.core.precision import STATS_DTYPE, resolve_precision
from repro.core.types import (
    ContrastiveConfig,
    ContrastiveState,
    DualEncoder,
    RetrievalBatch,
    StepMetrics,
    chunk_tree,
    flatten_hard,
    subtree_norm,
)
from repro.optim.adamw import GradientTransformation, apply_updates

# Bank state threaded across chunks by every program: (bank_q, bank_p).
# Sources without banks carry 0-capacity rings so the scan carry keeps a
# uniform pytree structure.
Carry = Tuple[BankState, BankState]

LOSS_COMMS = ("all_gather", "ring")


def _validate_loss_comm(cfg: ContrastiveConfig, *, uses_banks: bool) -> None:
    """Shared loss_comm checks, surfaced at program build."""
    if cfg.loss_comm not in LOSS_COMMS:
        raise ValueError(
            f"unknown loss_comm {cfg.loss_comm!r}; one of {sorted(LOSS_COMMS)}"
        )
    if cfg.loss_comm == "ring":
        if not uses_banks:
            raise ValueError(
                "loss_comm='ring' streams sharded bank columns around the DP "
                "ring, but this negatives source has no bank columns — use a "
                "bank-consuming source (dual_bank / passage_bank) or leave "
                "loss_comm='all_gather'"
            )
        if not cfg.shard_banks:
            raise ValueError(
                "loss_comm='ring' needs shard_banks=True (each device must "
                "own one N_mem/D shard to stream); replicated banks already "
                "hold the full column block locally"
            )


# --------------------------------------------------------------------------
# NegativeSource protocol + implementations
# --------------------------------------------------------------------------
class NegativeSource(Protocol):
    """Where the negatives of one loss evaluation come from."""

    name: str
    uses_banks: bool   # does this source read/write the FIFO banks?
    needs_mesh: bool   # does this source require cfg.dp_axis (a mesh)?

    def bank_sizes(self, cfg: ContrastiveConfig) -> Tuple[int, int]:
        """(capacity_q, capacity_p) this source wants allocated in state."""
        ...

    def validate(self, cfg: ContrastiveConfig) -> None:
        """Raise ValueError for configs this source cannot serve."""
        ...

    def begin(self, state: ContrastiveState, cfg: ContrastiveConfig) -> Carry:
        """Bank carry at the start of one update."""
        ...

    def loss(
        self,
        q: jnp.ndarray,
        pp: jnp.ndarray,
        ph: Optional[jnp.ndarray],
        carry: Carry,
        *,
        cfg: ContrastiveConfig,
        ctx: DistCtx,
        backend: Optional[LossBackend] = None,
    ) -> Tuple[jnp.ndarray, LossAux]:
        """One loss evaluation with this source's columns/rows/masks,
        computed by ``backend`` (None -> dense). ``cfg`` carries the
        temperature and the bank distribution mode (``shard_banks``)."""
        ...

    def push(
        self,
        carry: Carry,
        aux: LossAux,
        step: jnp.ndarray,
        *,
        cfg: ContrastiveConfig,
        ctx: DistCtx,
    ) -> Carry:
        """Update carried state after one loss evaluation (bank pushes).
        Shard-aware: with ``cfg.shard_banks`` each device writes only its own
        ring-slot block of the gathered global rows."""
        ...


class InBatchNegatives:
    """Plain in-batch negatives (DPR / GradAccum / GradCache): no extras.

    Banks in state are allocated per cfg for layout compatibility but never
    read or written."""

    name = "in_batch"
    uses_banks = False
    needs_mesh = False

    def bank_sizes(self, cfg):
        return cfg.resolved_bank_sizes()

    def validate(self, cfg):
        _validate_loss_comm(cfg, uses_banks=False)

    def begin(self, state, cfg):
        return (state.bank_q, state.bank_p)

    def loss(self, q, pp, ph, carry, *, cfg, ctx, backend=None):
        return contrastive_loss(
            q, pp, ph, temperature=cfg.temperature, ctx=ctx, backend=backend,
            precision=cfg.resolved_precision(),
        )

    def push(self, carry, aux, step, *, cfg, ctx):
        return carry


class MinedNegatives(InBatchNegatives):
    """ANCE-style globally-mined hard negatives (``negatives="mined"``).

    The asynchronous miner (repro/mining) publishes per-query negative ids;
    batch assembly (data/loader.py ``MinedNegativeInjector``) joins them in
    as extra ``passage_hard`` columns *before* the batch reaches the
    program. Inside the update the mined passages are therefore ordinary
    hard-negative columns — the loss math is identical to in-batch, which
    is exactly why this source composes with every BackpropStrategy
    unchanged, and why bank sources pick mined columns up for free
    (contaccum x mined = ``method='contaccum'`` + the injector: the mined
    columns ride ``passage_hard`` while the banks keep extending the
    matrix). The class exists to state the intent in the registry and to
    give the composition a first-class name."""

    name = "mined"


class GatheredInBatch(InBatchNegatives):
    """Cross-device in-batch negatives: identical math to ``in_batch`` (the
    loss all-gathers columns whenever cfg.dp_axis names mesh axes) but states
    the intent and refuses to build without a DP axis."""

    name = "gathered"
    needs_mesh = True

    def validate(self, cfg):
        super().validate(cfg)
        if cfg.dp_axis is None:
            raise ValueError(
                "negatives='gathered' needs cfg.dp_axis naming the mesh axes "
                "to all-gather representations over"
            )


class DualBankNegatives:
    """The paper's dual FIFO memory banks (Sec. 3.2): the passage bank
    extends the columns, the query bank adds extra rows labeled with their
    lockstep-aligned bank positives; both are pushed after every loss
    evaluation."""

    name = "dual_bank"
    uses_banks = True
    needs_mesh = False

    def bank_sizes(self, cfg):
        return cfg.resolved_bank_sizes()

    def validate(self, cfg):
        # bank-less dual-bank degrades exactly to in-batch; allowed (the
        # warm-up / reduction identities rely on it)
        nq, np_ = self.bank_sizes(cfg)
        if nq and np_ and nq != np_:
            raise ValueError(
                f"dual banks need equal non-zero capacities to stay "
                f"ring-aligned (got bank_size_q={nq}, bank_size_p={np_}): "
                f"heads advance mod different capacities, so after a wrap "
                f"row i of M_q no longer holds the query whose positive is "
                f"row i of M_p. Use bank_size=, or disable one bank "
                f"(capacity 0) for the pre-batch ablation."
            )
        if cfg.shard_banks and cfg.dp_axis is None:
            raise ValueError(
                "shard_banks=True needs cfg.dp_axis naming the mesh axes the "
                "bank rows are sharded over (single-device banks are already "
                "'sharded' into one shard — just leave shard_banks off)"
            )
        _validate_loss_comm(cfg, uses_banks=True)

    def begin(self, state, cfg):
        if cfg.reset_banks_each_update:
            return (clear(state.bank_q), clear(state.bank_p))
        return (state.bank_q, state.bank_p)

    def _sharded(self, cfg, ctx) -> bool:
        return cfg.shard_banks and ctx.is_distributed

    def loss(self, q, pp, ph, carry, *, cfg, ctx, backend=None):
        bank_q, bank_p = carry
        if self._sharded(cfg, ctx):
            # shard-local banks: columns reach the loss either gathered to
            # the global block or ring-streamed shard by shard (loss_comm);
            # rows are evaluated locally either way (each device owns a
            # distinct 1/D partition)
            extra_cols = sharded_bank_extra_columns(bank_p, ctx, cfg.loss_comm)
            extra_rows = sharded_bank_extra_rows(bank_q, bank_p, ctx)
        else:
            extra_cols = bank_extra_columns(bank_p)
            extra_rows = bank_extra_rows(bank_q, bank_p)
        return contrastive_loss(
            q,
            pp,
            ph,
            extra_cols=extra_cols,
            extra_rows=extra_rows,
            temperature=cfg.temperature,
            ctx=ctx,
            backend=backend,
            precision=cfg.resolved_precision(),
        )

    def push(self, carry, aux, step, *, cfg, ctx):
        bank_q, bank_p = carry
        if self._sharded(cfg, ctx):
            # each device writes only its own ring-slot block of the global
            # rows; the replicated global head advances identically everywhere
            return shard_push_pair(
                bank_q, bank_p, aux.q_global, aux.p_global, step,
                shard_index=ctx.shard_index(), num_shards=ctx.device_count(),
            )
        # Enqueue the *global* representations (identical on all devices in
        # distributed mode -> banks stay replicated).
        return push_pair(bank_q, bank_p, aux.q_global, aux.p_global, step)


class PassageBankNegatives(DualBankNegatives):
    """Passage-only bank — the 'pre-batch negatives' ablation (w/o M_q,
    Table 2): columns are extended, no extra rows, only passages pushed."""

    name = "passage_bank"

    def bank_sizes(self, cfg):
        # query bank disabled; the passage bank is the whole source
        _, np_ = cfg.resolved_bank_sizes()
        return 0, np_

    def loss(self, q, pp, ph, carry, *, cfg, ctx, backend=None):
        _, bank_p = carry
        extra_cols = (
            sharded_bank_extra_columns(bank_p, ctx, cfg.loss_comm)
            if self._sharded(cfg, ctx)
            else bank_extra_columns(bank_p)
        )
        return contrastive_loss(
            q,
            pp,
            ph,
            extra_cols=extra_cols,
            temperature=cfg.temperature,
            ctx=ctx,
            backend=backend,
            precision=cfg.resolved_precision(),
        )

    def push(self, carry, aux, step, *, cfg, ctx):
        bank_q, bank_p = carry
        if self._sharded(cfg, ctx):
            return bank_q, shard_push(
                bank_p, aux.p_global, step,
                shard_index=ctx.shard_index(), num_shards=ctx.device_count(),
            )
        return bank_q, push(bank_p, aux.p_global, step)


# --------------------------------------------------------------------------
# BackpropStrategy protocol + implementations
# --------------------------------------------------------------------------
class BackpropStrategy(Protocol):
    """How encoder gradients are obtained from the source's loss."""

    name: str

    def validate(self, cfg: ContrastiveConfig) -> None:
        ...

    def compute(
        self,
        encoder: DualEncoder,
        params: Any,
        batch: RetrievalBatch,
        source: NegativeSource,
        carry: Carry,
        step: jnp.ndarray,
        cfg: ContrastiveConfig,
        ctx: DistCtx,
    ) -> Tuple[Any, LossAux, Carry, dict]:
        """Returns (psum'ed grads, reduced aux, final carry, the encoder's
        counters summed over the update). A shared tower's grads come as
        ``Sides``."""
        ...


# Named scopes mark the update's layers (towers, loss, grad_accum,
# bank_push, optimizer) in the compiled program's op_name metadata, and so
# in a profiler trace of it: each device op carries at most one of them.
# Differentiation keeps the name, as ``jvp(towers)`` and
# ``transpose(jvp(towers))``. They are metadata only: the compiled code and
# the compilation cache's key do not change.
def _encode(encoder: DualEncoder, params, side: str, batch):
    """(reps, counters) of one encode call; counters are {} for encoders
    that keep none."""
    if encoder.encode_counters is not None:
        return encoder.encode_counters(params, side, batch)
    fn = encoder.encode_query if side == "query" else encoder.encode_passage
    return fn(params, batch), {}


def _add_counters(*counters):
    counters = [c for c in counters if c]
    if not counters:
        return {}
    return jax.tree_util.tree_map(lambda *xs: sum(xs[1:], xs[0]), *counters)


def _encode_chunk(encoder: DualEncoder, params, chunk: RetrievalBatch, q=None):
    """(q, pp, ph, counters) of one chunk. A given ``q`` (the shared
    tower's query side, encoded apart) is used as is. A shared tower
    encodes the positives and the hard negatives in one call, so that one
    backward pass, and one gradient of the backbone, serves both."""
    with jax.named_scope("towers"):
        cq = {}
        if q is None:
            q, cq = _encode(encoder, params, "query", chunk.query)
        if encoder.shared and chunk.passage_hard is not None:
            n = jax.tree_util.tree_leaves(chunk.passage_pos)[0].shape[0]
            both = jax.tree_util.tree_map(lambda a, b: jnp.concatenate([a, b]),
                                          chunk.passage_pos, flatten_hard(chunk.passage_hard))
            reps, cp = _encode(encoder, params, "passage", both)
            return q, reps[:n], reps[n:], _add_counters(cq, cp)
        pp, cp = _encode(encoder, params, "passage", chunk.passage_pos)
        ph, ch = None, {}
        if chunk.passage_hard is not None:
            ph, ch = _encode(encoder, params, "passage", flatten_hard(chunk.passage_hard))
        return q, pp, ph, _add_counters(cq, cp, ch)


# A shared tower's gradient is one tree, the sum of the two sides'
# contributions; the strategies hand it over split, as Sides, so that the
# metrics can give each side's norm. The query side is taken apart from the
# passage side: the passage side's gradient is taken with the query
# encodings as inputs, their cotangents kept (one row per query), and the
# query side is one VJP through the query encodings of the whole batch,
# seeded with them. That recomputes the queries' forward once and holds no
# second gradient tree across the chunks.
class Sides(NamedTuple):
    query: Any
    passage: Any


def _query_side(encoder: DualEncoder, params, queries, dq):
    """The query side's gradient: d(loss)/d(query encodings) ``dq``, pulled
    back through the encodings of ``queries``."""
    def enc(p):
        with jax.named_scope("towers"):
            return _encode(encoder, p, "query", queries)[0]

    out, vjp_fn = jax.vjp(enc, params)
    (g,) = vjp_fn(dq.astype(out.dtype))
    return g


def _loss(source: NegativeSource, q, pp, ph, carry, **kw):
    with jax.named_scope("loss"):
        return source.loss(q, pp, ph, carry, **kw)


def _push(source: NegativeSource, carry, aux, step, **kw):
    with jax.named_scope("bank_push"):
        return source.push(carry, aux, step, **kw)


def _accumulate(grads_acc, g):
    with jax.named_scope("grad_accum"):
        return tree_add(grads_acc, g)


def _chunk_batch(batch: RetrievalBatch, k: int) -> RetrievalBatch:
    return RetrievalBatch(
        query=chunk_tree(batch.query, k),
        passage_pos=chunk_tree(batch.passage_pos, k),
        passage_hard=None
        if batch.passage_hard is None
        else chunk_tree(batch.passage_hard, k),
    )


def _reduce_scanned_aux(auxs: LossAux) -> LossAux:
    """Reduce per-chunk aux to update-level metrics. Each chunk's loss /
    accuracy is already a mean over that chunk's rows, and the row counts
    differ while the banks warm up (later chunks see more valid extra rows) —
    so the chunks are recombined weighted by ``n_rows``, giving the exact
    mean over every row of the update rather than a mean of chunk means."""
    n = auxs.n_rows
    n_total = jnp.maximum(n.sum(), 1.0)
    return LossAux(
        loss=(auxs.loss * n).sum() / n_total,
        accuracy=(auxs.accuracy * n).sum() / n_total,
        n_rows=n.sum(),
        n_negatives=auxs.n_negatives.mean(),
        q_global=auxs.q_global,
        p_global=auxs.p_global,
    )


def _chunk_grads(encoder, params, chunk, source, carry, cfg, ctx, backend):
    """((aux, counters), grads, dq) of one loss evaluation. For a shared
    tower ``grads`` is the passage side's alone and ``dq`` the loss's
    cotangent of the query encodings (see ``Sides``); otherwise ``dq`` is
    None."""
    q = None
    cq = {}
    if encoder.shared:
        with jax.named_scope("towers"):
            q, cq = _encode(encoder, params, "query", chunk.query)

    def loss_fn(p, q_in):
        q_, pp, ph, counters = _encode_chunk(encoder, p, chunk, q_in)
        loss, aux = _loss(source, q_, pp, ph, carry, cfg=cfg, ctx=ctx, backend=backend)
        return loss, (aux, _add_counters(cq, counters))

    if encoder.shared:
        (_, out), (g, dq) = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(params, q)
        return out, g, dq
    (_, out), g = jax.value_and_grad(loss_fn, has_aux=True)(params, None)
    return out, g, None


def _sum_counters(counters):
    """Counters stacked over the chunks -> summed over the update."""
    return jax.tree_util.tree_map(lambda x: x.sum(0), counters)


class DirectBackprop:
    """One forward/backward over the whole batch (full activation memory)."""

    name = "direct"

    def validate(self, cfg):
        pass

    def compute(self, encoder, params, batch, source, carry, step, cfg, ctx):
        backend = resolve_loss_backend(cfg.loss_impl)
        (aux, counters), grads, dq = _chunk_grads(
            encoder, params, batch, source, carry, cfg, ctx, backend)
        if encoder.shared:
            grads = Sides(_query_side(encoder, params, batch.query, dq), grads)
        grads = ctx.psum_tree(grads)
        carry = _push(source, carry, aux, step, cfg=cfg, ctx=ctx)
        return grads, aux, carry, counters


class ScanAccumulate:
    """K chunks under jax.lax.scan, loss restricted to each chunk (paper
    Eq. 4); the source's carry (banks) threads through the scan, so each
    chunk sees every previous chunk's pushes."""

    name = "scan"

    def validate(self, cfg):
        if cfg.accumulation_steps < 1:
            raise ValueError("accumulation_steps must be >= 1")

    def compute(self, encoder, params, batch, source, carry, step, cfg, ctx):
        k = cfg.accumulation_steps
        chunks = _chunk_batch(batch, k)
        backend = resolve_loss_backend(cfg.loss_impl)

        def body(c, chunk):
            grads_acc, carry_ = c
            (aux, counters), g, dq = _chunk_grads(
                encoder, params, chunk, source, carry_, cfg, ctx, backend)
            carry_ = _push(source, carry_, aux, step, cfg=cfg, ctx=ctx)
            return (_accumulate(grads_acc, g), carry_), (aux, counters, dq)

        (grads, carry), (auxs, counters, dqs) = jax.lax.scan(
            body, (tree_zeros_like(params), carry), chunks
        )
        with jax.named_scope("grad_accum"):
            grads = tree_scale(grads, 1.0 / k)
        if encoder.shared:
            dq = dqs.reshape((-1,) + dqs.shape[2:]) / k
            grads = Sides(_query_side(encoder, params, batch.query, dq), grads)
        grads = ctx.psum_tree(grads)
        return grads, _reduce_scanned_aux(auxs), carry, _sum_counters(counters)


class RepCacheVJP:
    """GradCache's decomposed backprop (Gao et al. 2021): representations are
    computed chunk-wise without stored activations, the source's loss is
    differentiated w.r.t. the representations only (the "gradient cache"),
    and per-chunk VJPs inject those cotangents back through the encoders.
    Gradients are *exactly* the direct full-batch gradients of the same loss
    (tested) at chunked activation memory — composed with a bank source this
    yields full-batch backprop *plus* bank-extended negatives."""

    name = "rep_cache"

    def validate(self, cfg):
        if cfg.accumulation_steps < 1:
            raise ValueError("accumulation_steps must be >= 1")

    def compute(self, encoder, params, batch, source, carry, step, cfg, ctx):
        k = cfg.accumulation_steps
        chunks = _chunk_batch(batch, k)
        has_hard = batch.passage_hard is not None
        backend = resolve_loss_backend(cfg.loss_impl)

        # Stage 1: representation-only forward, chunk by chunk, no stored
        # activations for the loss graph (stop_gradient == GradCache's
        # torch.no_grad forward).
        def fwd(_, chunk):
            q, pp, ph, counters = _encode_chunk(encoder, params, chunk)
            ph = jnp.zeros((0, q.shape[-1]), q.dtype) if ph is None else ph
            return None, ((q, pp, ph), counters)

        _, ((qs, pps, phs), counters) = jax.lax.scan(fwd, None, chunks)
        # the cached representation store lives in the policy's compute dtype
        # (bf16 halves the (B_g + banks, d) cache this strategy carries)
        pol = cfg.resolved_precision()
        qs, pps, phs = (
            pol.cast_compute(jax.lax.stop_gradient(x)) for x in (qs, pps, phs)
        )

        def merge(x):  # (K, local, d) -> (K*local, d)
            return x.reshape((-1, x.shape[-1]))

        # Stage 2: d loss / d representations (the "gradient cache"), with
        # the source's extra columns/rows in the matrix.
        def rep_loss(q_all, pp_all, ph_all):
            return _loss(
                source,
                q_all,
                pp_all,
                ph_all if has_hard else None,
                carry,
                cfg=cfg,
                ctx=ctx,
                backend=backend,
            )

        (_, aux), rep_grads = jax.value_and_grad(rep_loss, argnums=(0, 1, 2), has_aux=True)(
            merge(qs), merge(pps), merge(phs)
        )
        gq = rep_grads[0].reshape(qs.shape)
        gpp = rep_grads[1].reshape(pps.shape)
        gph = rep_grads[2].reshape(phs.shape)

        # Stage 3: per-chunk VJP through the encoders, seeded with the cached
        # representation gradients. Activations exist for one chunk at a time.
        def bwd(grads_acc, inp):
            chunk, (gq_k, gpp_k, gph_k) = inp

            def enc(p):
                # a shared tower's query side is pulled back apart (Sides)
                q_in = gq_k if encoder.shared else None
                q, pp, ph, _ = _encode_chunk(encoder, p, chunk, q_in)
                ph = jnp.zeros((0, q.shape[-1]), q.dtype) if ph is None else ph
                return (pp, ph) if encoder.shared else (q, pp, ph)

            outs, vjp_fn = jax.vjp(enc, params)
            # cached cotangents are in compute dtype; the encoder's native
            # output dtype may differ (fp32 towers under a bf16 policy) —
            # seed the VJP in the primal dtype it expects
            cots = (gpp_k, gph_k) if encoder.shared else (gq_k, gpp_k, gph_k)
            seeds = tuple(g.astype(o.dtype) for g, o in zip(cots, outs))
            (g,) = vjp_fn(seeds)
            return _accumulate(grads_acc, g), None

        grads, _ = jax.lax.scan(
            bwd, tree_zeros_like(params), (chunks, (gq, gpp, gph))
        )
        if encoder.shared:
            grads = Sides(_query_side(encoder, params, batch.query, merge(gq)), grads)
        grads = ctx.psum_tree(grads)
        carry = _push(source, carry, aux, step, cfg=cfg, ctx=ctx)
        return grads, aux, carry, _sum_counters(counters)


# --------------------------------------------------------------------------
# Registries + resolution
# --------------------------------------------------------------------------
SOURCES: dict[str, NegativeSource] = {
    s.name: s
    for s in (
        InBatchNegatives(),
        MinedNegatives(),
        GatheredInBatch(),
        DualBankNegatives(),
        PassageBankNegatives(),
    )
}

STRATEGIES: dict[str, BackpropStrategy] = {
    s.name: s for s in (DirectBackprop(), ScanAccumulate(), RepCacheVJP())
}

# method name -> (negatives, backprop). The first four are the paper's
# methods (gradient-exact vs. the original implementations); the rest are
# compositions the monolithic API could not express.
COMPOSITIONS: dict[str, Tuple[str, str]] = {
    "dpr": ("in_batch", "direct"),
    "grad_accum": ("in_batch", "scan"),
    "grad_cache": ("in_batch", "rep_cache"),
    "contaccum": ("dual_bank", "scan"),
    "contcache": ("dual_bank", "rep_cache"),
    "prebatch": ("passage_bank", "scan"),
    "prebatch_cache": ("passage_bank", "rep_cache"),
    "dpr_xdev": ("gathered", "direct"),
    "mined": ("mined", "direct"),
    "mined_accum": ("mined", "scan"),
    "mined_cache": ("mined", "rep_cache"),
}


def available_methods() -> list[str]:
    """Registered method names (legacy four + new compositions)."""
    return sorted(COMPOSITIONS)


def method_composition(method: str) -> Tuple[str, str]:
    """Legacy-string resolution: method name -> (negatives, backprop)."""
    if method not in COMPOSITIONS:
        raise ValueError(
            f"unknown method {method!r}; one of {available_methods()}"
        )
    return COMPOSITIONS[method]


def method_uses_banks(method: str) -> bool:
    """Does this method's negative source read/write the FIFO banks?"""
    return SOURCES[method_composition(method)[0]].uses_banks


def method_needs_mesh(method: str) -> bool:
    """Does this method's negative source require cfg.dp_axis (a mesh)?"""
    return SOURCES[method_composition(method)[0]].needs_mesh


def resolve_composition(cfg: ContrastiveConfig) -> Tuple[NegativeSource, BackpropStrategy]:
    """cfg -> (source, strategy). Explicit ``negatives=``/``backprop=``
    fields win; unset fields fall back to the legacy ``method=`` string."""
    neg, bp = cfg.resolved_composition_names()
    if neg not in SOURCES:
        raise ValueError(f"unknown negatives {neg!r}; one of {sorted(SOURCES)}")
    if bp not in STRATEGIES:
        raise ValueError(f"unknown backprop {bp!r}; one of {sorted(STRATEGIES)}")
    return SOURCES[neg], STRATEGIES[bp]


# --------------------------------------------------------------------------
# The generic program builder
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class StepProgram:
    """A built contrastive update: ``update(state, batch) -> (state,
    StepMetrics)`` plus the composition it was built from."""

    update: Callable[[ContrastiveState, RetrievalBatch], Tuple[ContrastiveState, StepMetrics]]
    source: NegativeSource
    strategy: BackpropStrategy
    cfg: ContrastiveConfig

    @property
    def name(self) -> str:
        for m, (neg, bp) in COMPOSITIONS.items():
            if (neg, bp) == (self.source.name, self.strategy.name):
                return m
        return f"{self.source.name}*{self.strategy.name}"


def _tower_metrics(counters: dict) -> dict:
    """Scalar counters as they are; an array counter as its max and mean."""
    out = {}
    for name, v in counters.items():
        v = jnp.asarray(v)
        if v.ndim:
            out[f"{name}_max"] = v.max().astype(STATS_DTYPE)
            out[f"{name}_mean"] = v.mean(dtype=STATS_DTYPE)
        else:
            out[name] = v.astype(STATS_DTYPE)
    return out


def _metrics(
    grads,
    aux: LossAux,
    bank_q: BankState,
    bank_p: BankState,
    *,
    ctx: Optional[DistCtx] = None,
    sharded_banks: bool = False,
    sides: Optional[Sides] = None,
    counters: Optional[dict] = None,
) -> StepMetrics:
    if sides is not None:
        gq, gp = tree_global_norm(sides.query), tree_global_norm(sides.passage)
    else:
        gq = subtree_norm(grads, "query")
        gp = subtree_norm(grads, "passage")

    def fill(bank: BankState) -> jnp.ndarray:
        if not bank.buf.shape[0]:
            return jnp.zeros(())
        f = bank.valid.sum().astype(STATS_DTYPE)
        # shard-local fills differ across devices mid-warm-up (low ring slots
        # fill first); psum to the replicated global fill
        return ctx.psum(f) if sharded_banks and ctx is not None else f

    return StepMetrics(
        loss=aux.loss,
        accuracy=aux.accuracy,
        grad_norm=tree_global_norm(grads),
        grad_norm_query=gq,
        grad_norm_passage=gp,
        grad_norm_ratio=gp / jnp.maximum(gq, 1e-12),
        n_negatives=aux.n_negatives,
        bank_fill_q=fill(bank_q),
        bank_fill_p=fill(bank_p),
        tower=_tower_metrics(counters or {}),
    )


def _apply(state: ContrastiveState, grads, tx, bank_q, bank_p) -> ContrastiveState:
    with jax.named_scope("optimizer"):
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = apply_updates(state.params, updates)
    return ContrastiveState(
        step=state.step + 1,
        params=params,
        opt_state=opt_state,
        bank_q=bank_q,
        bank_p=bank_p,
    )


def build_step_program(
    encoder: DualEncoder, tx: GradientTransformation, cfg: ContrastiveConfig
) -> StepProgram:
    """Compose cfg's negative source and backprop strategy into one update
    program. The program owns chunking, loss assembly, bank pushes, the
    optimizer application and metric assembly; it is pure and serves
    single-device, shard_map/GSPMD and dry-run paths unchanged.
    ``cfg.loss_impl`` selects the loss backend (dense einsum vs the fused
    Pallas kernel) orthogonally to the composition."""
    source, strategy = resolve_composition(cfg)
    source.validate(cfg)
    strategy.validate(cfg)
    resolve_loss_backend(cfg.loss_impl)  # fail fast on unknown loss_impl
    resolve_precision(cfg.precision)     # fail fast on unknown precision
    ctx = DistCtx(cfg.dp_axis)

    def update(state: ContrastiveState, batch: RetrievalBatch):
        carry = source.begin(state, cfg)
        grads, aux, carry, counters = strategy.compute(
            encoder, state.params, batch, source, carry, state.step, cfg, ctx
        )
        sides = None
        if isinstance(grads, Sides):
            sides = grads
            with jax.named_scope("grad_accum"):
                grads = tree_add(sides.query, sides.passage)
        bank_q, bank_p = carry
        new_state = _apply(state, grads, tx, bank_q, bank_p)
        return new_state, _metrics(
            grads, aux, bank_q, bank_p,
            ctx=ctx, sharded_banks=cfg.shard_banks and ctx.is_distributed,
            sides=sides, counters=counters,
        )

    return StepProgram(update=update, source=source, strategy=strategy, cfg=cfg)


def init_state(
    rng: jax.Array,
    encoder: DualEncoder,
    tx: GradientTransformation,
    cfg: ContrastiveConfig,
    params: Optional[Any] = None,
    bank_dim: Optional[int] = None,
) -> ContrastiveState:
    """Initial train state with the bank capacities the cfg's negative
    source asks for; bank rings are allocated in the precision policy's
    ``bank_dtype`` (or the explicit ``cfg.bank_dtype`` override)."""
    if params is None:
        params = encoder.init(rng)
    source, _ = resolve_composition(cfg)
    nq, np_ = source.bank_sizes(cfg)
    d = bank_dim or encoder.rep_dim
    bank_dtype = cfg.resolved_bank_dtype()
    return ContrastiveState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=tx.init(params),
        bank_q=init_bank(nq, d, bank_dtype),
        bank_p=init_bank(np_, d, bank_dtype),
    )
