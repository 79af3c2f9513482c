"""Kernel-parity suite: the fused (Pallas) loss backend vs the dense einsum.

Mirrors the seed-parity pattern of tests/seed_methods.py at the backend
level: for every NegativeSource x BackpropStrategy composition in the
registry, a multi-step trajectory with ``loss_impl='fused'`` must track the
``loss_impl='dense'`` trajectory to fp32 tolerance — same params, same
banks, same metrics. That covers both VJPs (dQ through the query tower, dP
through the passage tower), masked warm-up bank slots, and weighted
ExtraRows. Everything runs in interpret mode on CPU.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ContrastiveConfig,
    DenseLossBackend,
    ExtraColumns,
    ExtraRows,
    FusedLossBackend,
    RetrievalBatch,
    SOURCES,
    STRATEGIES,
    build_step_program,
    contrastive_loss,
    init_state,
    resolve_loss_backend,
)
from repro.kernels.fused_infonce.ops import fused_infonce_stats
from repro.kernels.fused_infonce.ref import NEG_INF, infonce_stats_ref
from repro.optim import chain, clip_by_global_norm, sgd

from helpers import make_batch, make_mlp_encoder

ALL_COMPOSITIONS = [
    (neg, bp) for neg in sorted(SOURCES) for bp in sorted(STRATEGIES)
]

FUSED = FusedLossBackend(interpret=True)
DENSE = DenseLossBackend()


def _tx():
    return chain(clip_by_global_norm(2.0), sgd(0.1))


def _cfg(neg, bp, loss_impl):
    return ContrastiveConfig(
        negatives=neg,
        backprop=bp,
        accumulation_steps=1 if bp == "direct" else 2,
        # bank > one update's pushes: the warm-up phase (masked invalid
        # slots) stays in play across the whole trajectory
        bank_size=12 if neg in ("dual_bank", "passage_bank") else 0,
        dp_axis="dp" if neg == "gathered" else None,
        loss_impl=loss_impl,
    )


def _run_trajectory(neg, bp, loss_impl, batches):
    enc = make_mlp_encoder()
    cfg = _cfg(neg, bp, loss_impl)
    tx = _tx()
    program = build_step_program(enc, tx, cfg)
    state = init_state(jax.random.PRNGKey(0), enc, tx, cfg)
    if neg == "gathered":
        from jax.sharding import Mesh, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
        spec = RetrievalBatch(query=P("dp"), passage_pos=P("dp"),
                              passage_hard=P("dp"))
        update = jax.jit(jax.shard_map(
            program.update, mesh=mesh, in_specs=(P(), spec),
            out_specs=(P(), P()), check_vma=False,
        ))
    else:
        update = jax.jit(program.update)
    metrics = []
    for b in batches:
        state, m = update(state, b)
        metrics.append(m)
    return state, metrics


def _assert_tree_close(a, b, msg, rtol=3e-5, atol=1e-6):
    for la, lb in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_allclose(
            np.asarray(la), np.asarray(lb), rtol=rtol, atol=atol, err_msg=msg
        )


# ------------------------------------------------------- registry-wide parity
@pytest.mark.parametrize("neg,bp", ALL_COMPOSITIONS)
def test_fused_backend_matches_dense_across_registry(neg, bp):
    """3-step trajectories per composition: params, banks and metrics under
    loss_impl='fused' must track 'dense' (both encoder VJPs, warm-up masks,
    weighted rows all exercised through the real update programs)."""
    batches = [make_batch(jax.random.PRNGKey(100 + i), 8, n_hard=1)
               for i in range(3)]
    s_dense, m_dense = _run_trajectory(neg, bp, "dense", batches)
    s_fused, m_fused = _run_trajectory(neg, bp, "fused", batches)
    _assert_tree_close(s_dense.params, s_fused.params, f"{neg}x{bp}: params")
    for bank in ("bank_q", "bank_p"):
        _assert_tree_close(
            getattr(s_dense, bank), getattr(s_fused, bank), f"{neg}x{bp}: {bank}"
        )
    for md, mf in zip(m_dense, m_fused):
        for field in ("loss", "accuracy", "grad_norm", "grad_norm_ratio",
                      "n_negatives", "bank_fill_q", "bank_fill_p"):
            np.testing.assert_allclose(
                float(getattr(md, field)), float(getattr(mf, field)),
                rtol=1e-4, atol=1e-6, err_msg=f"{neg}x{bp}: metric {field}",
            )


# ------------------------------------------------- loss-level fwd/VJP parity
def test_loss_level_parity_masked_columns_weighted_rows():
    """contrastive_loss forward value, accuracy, and the VJPs w.r.t. every
    input block agree between backends — with invalid extra columns (warm-up
    masking) and fractionally weighted ExtraRows (the replicated-bank-row
    1/D shares)."""
    ks = jax.random.split(jax.random.PRNGKey(5), 6)
    b, d, c, r = 8, 16, 10, 6
    q = jax.random.normal(ks[0], (b, d))
    pp = jax.random.normal(ks[1], (b, d))
    ph = jax.random.normal(ks[2], (2 * b, d))
    cols = ExtraColumns(
        reps=jax.random.normal(ks[3], (c, d)),
        valid=jnp.arange(c) < 7,                  # 3 masked warm-up slots
    )
    rows = ExtraRows(
        reps=jax.random.normal(ks[4], (r, d)),
        labels=jnp.arange(r, dtype=jnp.int32),    # into the extra-col block
        weight=jax.random.uniform(ks[5], (r,)),   # fractional weights
    )

    def make_loss(backend):
        def loss(q_, pp_, ph_, cr_, rr_):
            l, aux = contrastive_loss(
                q_, pp_, ph_,
                extra_cols=ExtraColumns(reps=cr_, valid=cols.valid),
                extra_rows=ExtraRows(reps=rr_, labels=rows.labels,
                                     weight=rows.weight),
                temperature=0.7,
                backend=backend,
            )
            return l, aux
        return loss

    args = (q, pp, ph, cols.reps, rows.reps)
    (ld, auxd), gd = jax.value_and_grad(make_loss(DENSE), argnums=(0, 1, 2, 3, 4),
                                        has_aux=True)(*args)
    (lf, auxf), gf = jax.value_and_grad(make_loss(FUSED), argnums=(0, 1, 2, 3, 4),
                                        has_aux=True)(*args)
    np.testing.assert_allclose(float(ld), float(lf), rtol=1e-5)
    np.testing.assert_allclose(float(auxd.accuracy), float(auxf.accuracy), rtol=1e-6)
    for name, a, b_ in zip(("dq", "dpp", "dph", "dcols", "drows"), gd, gf):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-6,
            err_msg=f"VJP mismatch: {name}",
        )
    # masked extra columns must receive exactly zero gradient on both paths
    np.testing.assert_array_equal(np.asarray(gd[3][7:]), 0.0)
    np.testing.assert_array_equal(np.asarray(gf[3][7:]), 0.0)


# ------------------------------------------------------ ragged-shape padding
@pytest.mark.parametrize(
    "m,n,d,bm,bn",
    [
        (96, 200, 64, 128, 128),   # the ISSUE's regression shape
        (1, 333, 16, 128, 128),    # single row, ragged columns
        (130, 70, 8, 64, 32),      # both dims ragged vs the blocks
        (257, 129, 32, 128, 128),  # one past the block boundary
    ],
)
def test_odd_shapes_are_padded_internally(m, n, d, bm, bn):
    """No more `m % block_m == 0` assert: padded columns are masked to
    NEG_INF, padded rows are dropped, stats and both VJPs stay exact."""
    ks = jax.random.split(jax.random.PRNGKey(m * 7 + n), 4)
    q = jax.random.normal(ks[0], (m, d))
    p = jax.random.normal(ks[1], (n, d))
    labels = jax.random.randint(ks[2], (m,), 0, n)
    valid = jax.random.bernoulli(ks[3], 0.8, (n,)).at[labels].set(True)
    lse, pos, amax = fused_infonce_stats(q, p, labels, valid, 1.3, bm, bn, True)
    lse_r, pos_r, amax_r = infonce_stats_ref(q, p, labels, valid, inv_tau=1.3)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(pos), np.asarray(pos_r), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(amax), np.asarray(amax_r), rtol=1e-5)

    w = jax.random.uniform(ks[3], (m,))

    def k_loss(q_, p_):
        l, po, _ = fused_infonce_stats(q_, p_, labels, valid, 1.3, bm, bn, True)
        return jnp.sum((l - po) * w)

    def r_loss(q_, p_):
        l, po, _ = infonce_stats_ref(q_, p_, labels, valid, inv_tau=1.3)
        return jnp.sum((l - po) * w)

    gk = jax.grad(k_loss, argnums=(0, 1))(q, p)
    gr = jax.grad(r_loss, argnums=(0, 1))(q, p)
    for name, a, b in zip(("dq", "dp"), gk, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=5e-6,
            err_msg=f"odd-shape VJP mismatch: {name}",
        )


@pytest.mark.parametrize(
    "m,n,bm,bn,tail,label_lo,label_hi",
    [
        (37, 300, 16, 128, 77, 0, 223),      # ragged M/N, masked bank tail
        (64, 256, 32, 128, 128, 0, 128),     # a whole column block masked
        (24, 200, 8, 64, 0, -200, 400),      # ring chunk: labels owned elsewhere
    ],
)
def test_blocked_labels_and_validity(m, n, bm, bn, tail, label_lo, label_hi):
    """labels arrive as a (bm, 1) column block and validity as a (1, bn) row
    block per grid step: a masked tail never enters the softmax, and a label
    outside [0, n) (a row whose positive lives in another ring chunk) gives
    pos 0 with no gradient through it."""
    ks = jax.random.split(jax.random.PRNGKey(m + n + tail), 4)
    q = jax.random.normal(ks[0], (m, 16))
    p = jax.random.normal(ks[1], (n, 16))
    labels = jax.random.randint(ks[2], (m,), label_lo, label_hi)
    valid = jnp.arange(n) < n - tail
    w = jax.random.uniform(ks[3], (m,))

    def ref(q_, p_):
        lse, _, amax = infonce_stats_ref(q_, p_, jnp.zeros_like(labels), valid,
                                         inv_tau=1.3)
        logits = jnp.where(valid[None, :], 1.3 * q_ @ p_.T, NEG_INF)
        pos = jnp.sum(jax.nn.one_hot(labels, n) * logits, axis=-1)
        return lse, pos, amax

    def kern(q_, p_):
        return fused_infonce_stats(q_, p_, labels, valid, 1.3, bm, bn, True)

    for got, want in zip(kern(q, p), ref(q, p)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)
    gk = jax.grad(lambda a, b: jnp.sum((kern(a, b)[0] - kern(a, b)[1]) * w),
                  argnums=(0, 1))(q, p)
    gr = jax.grad(lambda a, b: jnp.sum((ref(a, b)[0] - ref(a, b)[1]) * w),
                  argnums=(0, 1))(q, p)
    for name, a, b in zip(("dq", "dp"), gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=5e-6, err_msg=name)
    if tail:
        np.testing.assert_array_equal(np.asarray(gk[1])[n - tail:], 0.0)


@pytest.mark.slow
def test_large_bank_sweep_parity():
    """Large-shape sweep (bank-scale column counts) — slow, interpret mode."""
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    m, n, d = 256, 8192 + 57, 64
    q = jax.random.normal(ks[0], (m, d))
    p = jax.random.normal(ks[1], (n, d))
    labels = jax.random.randint(ks[2], (m,), 0, n)
    valid = jnp.arange(n) < (n - 100)
    lse, pos, amax = fused_infonce_stats(q, p, labels, valid, 1.0, 128, 512, True)
    lse_r, pos_r, amax_r = infonce_stats_ref(q, p, labels, valid)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_r), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(amax), np.asarray(amax_r), rtol=1e-5)


def test_merge_row_stats_composes_chunked_softmax_exactly():
    """(lse, pos, amax) are sufficient statistics: computing them per column
    chunk and logsumexp-merging must reproduce the whole-matrix stats — both
    values and gradients (the chain rule through the merge rescales each
    chunk's cotangent by exp(lse_k - lse), making chunk-local softmax
    coefficients global). This identity is what lets the ring loss stream
    one bank shard at a time."""
    from repro.kernels.fused_infonce.ops import merge_row_stats

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    m, n, d, n_chunks = 16, 48, 8, 4
    q = jax.random.normal(ks[0], (m, d))
    p = jax.random.normal(ks[1], (n, d))
    labels = jax.random.randint(ks[2], (m,), 0, n)
    valid = jnp.arange(n) % 7 != 0  # masked columns inside chunks

    def whole(q, p):
        return infonce_stats_ref(q, p, labels, valid)

    def chunked(q, p):
        c = n // n_chunks
        parts = []
        for k in range(n_chunks):
            lse, pos, amax = infonce_stats_ref(
                q, p[k * c:(k + 1) * c],
                jnp.clip(labels - k * c, 0, c - 1),
                valid[k * c:(k + 1) * c],
            )
            owns = (labels >= k * c) & (labels < (k + 1) * c)
            pos = jnp.where(owns, pos, 0.0)
            parts.append((lse, pos, owns, amax))
        lse, pos, owns, amax = (jnp.stack(x) for x in zip(*parts))
        return merge_row_stats(lse, pos, owns, amax)

    for a, b in zip(whole(q, p), chunked(q, p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    # gradients of the actual training objective mean(lse - pos)
    def loss(stats_fn):
        def f(q, p):
            lse, pos, _ = stats_fn(q, p)
            return jnp.mean(lse - pos)
        return f

    gq_w, gp_w = jax.grad(loss(whole), argnums=(0, 1))(q, p)
    gq_c, gp_c = jax.grad(loss(chunked), argnums=(0, 1))(q, p)
    np.testing.assert_allclose(np.asarray(gq_w), np.asarray(gq_c), rtol=1e-5,
                               atol=1e-7)
    np.testing.assert_allclose(np.asarray(gp_w), np.asarray(gp_c), rtol=1e-5,
                               atol=1e-7)


# ----------------------------------------------------------------- plumbing
def test_default_backend_is_dense():
    assert ContrastiveConfig().loss_impl == "dense"
    assert resolve_loss_backend(None).name == "dense"
    assert resolve_loss_backend("fused").name == "fused"
    # instances pass through
    be = FusedLossBackend(block_n=64, interpret=True)
    assert resolve_loss_backend(be) is be


def test_unknown_loss_impl_raises_at_build():
    enc = make_mlp_encoder()
    with pytest.raises(ValueError, match="unknown loss_impl"):
        build_step_program(enc, _tx(), ContrastiveConfig(loss_impl="nope"))


def test_fused_cell_is_registered_and_traces():
    """The dpr-bert-base fused cell builds and abstract-evals (the Pallas
    call shape-checks without a TPU)."""
    from jax.sharding import Mesh

    from repro.launch.steps import build_cell

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    prog = build_cell("dpr-bert-base", "paper_batch_fused", mesh)
    assert prog.static_info["loss_impl"] == "fused"
    assert prog.static_info["method"] == "contaccum"
    out = jax.eval_shape(prog.fn, *prog.args)
    assert out is not None


def test_example_driver_runs_fused():
    """examples/train_retriever.py drives loss_impl='fused' end to end."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "examples", "train_retriever.py")
    spec = importlib.util.spec_from_file_location("example_train_retriever", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.main([
        "--method", "contaccum",
        "--loss-impl", "fused",
        "--steps", "2",
        "--warmup-steps", "1",
        "--total-batch", "8",
        "--local-batch", "4",
        "--bank", "12",
        "--corpus", "64",
    ])
