"""The `StepProgram` composition API (core/step_program.py).

  * Exact gradient parity: the four legacy ``method=`` strings, resolved
    through the composed (negative source x backprop strategy) registry,
    must reproduce the seed monolithic implementations (tests/seed_methods.py)
    bit-for-bit-close over multi-step trajectories — with and without hard
    negatives and banks.
  * Registry: every advertised composition builds and jits.
  * New compositions: ``contcache`` (rep-cache x dual-bank) and
    ``prebatch_cache`` (rep-cache x passage-only-bank) train end-to-end and
    reduce to DPR when the banks are empty.
"""

import importlib.util
import os

import jax
import numpy as np
import pytest

from repro.core import (
    COMPOSITIONS,
    ContrastiveConfig,
    RetrievalBatch,
    available_methods,
    build_step_program,
    init_state,
    make_update_fn,
    method_composition,
)
from repro.optim import adamw, chain, clip_by_global_norm, sgd

from helpers import make_batch, make_mlp_encoder
from seed_methods import SEED_BUILDERS

LEGACY = ["dpr", "grad_accum", "grad_cache", "contaccum"]


def _tx(cfg: ContrastiveConfig):
    return chain(clip_by_global_norm(cfg.grad_clip_norm), sgd(0.1))


def _assert_state_close(sa, sb, msg, rtol=1e-6, atol=1e-8):
    for a, b in zip(jax.tree_util.tree_leaves(sa), jax.tree_util.tree_leaves(sb)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=rtol, atol=atol, err_msg=msg
        )


def _run_trajectory(update, state, batches):
    metrics = []
    for b in batches:
        state, m = update(state, b)
        metrics.append(m)
    return state, metrics


@pytest.mark.parametrize("method", LEGACY)
@pytest.mark.parametrize("n_hard", [0, 2])
def test_composed_program_matches_seed_implementation(method, n_hard):
    """3-step trajectories: params, banks and metrics must track the seed
    implementation exactly (same inputs, same optimizer)."""
    enc = make_mlp_encoder()
    kw = dict(accumulation_steps=1, bank_size=0)
    if method in ("grad_accum", "grad_cache"):
        kw = dict(accumulation_steps=4, bank_size=0)
    if method == "contaccum":
        kw = dict(accumulation_steps=4, bank_size=12)
    cfg = ContrastiveConfig(method=method, **kw)
    tx = _tx(cfg)

    batches = [make_batch(jax.random.PRNGKey(100 + i), 16, n_hard=n_hard) for i in range(3)]

    state0 = init_state(jax.random.PRNGKey(0), enc, tx, cfg)
    seed_update = jax.jit(SEED_BUILDERS[method](enc, tx, cfg))
    new_update = jax.jit(build_step_program(enc, tx, cfg).update)

    s_seed, m_seed = _run_trajectory(seed_update, state0, batches)
    s_new, m_new = _run_trajectory(new_update, state0, batches)

    _assert_state_close(s_seed.params, s_new.params, f"{method}: params diverge")
    _assert_state_close(s_seed.opt_state, s_new.opt_state, f"{method}: opt state")
    for bank in ("bank_q", "bank_p"):
        _assert_state_close(
            getattr(s_seed, bank), getattr(s_new, bank), f"{method}: {bank}"
        )
    # contaccum's reported loss/accuracy intentionally diverge from the seed:
    # the seed averaged per-chunk means unweighted, mis-weighting warm-up
    # chunks whose extra-row counts differ; the program weights by n_rows
    # (test_scanned_metrics_are_row_weighted pins the fixed value). Gradients,
    # params and banks remain exact.
    fields = ("loss", "accuracy", "grad_norm", "grad_norm_ratio",
              "n_negatives", "bank_fill_q", "bank_fill_p")
    if method == "contaccum":
        fields = tuple(f for f in fields if f not in ("loss", "accuracy"))
    for ms, mn in zip(m_seed, m_new):
        for field in fields:
            np.testing.assert_allclose(
                float(getattr(ms, field)), float(getattr(mn, field)),
                rtol=1e-5, err_msg=f"{method}: metric {field}",
            )


@pytest.mark.parametrize("method", ["contaccum"])
def test_parity_under_ablation_flags(method):
    """Seed parity also holds for the bank ablations (reset-each-update /
    passage-only via use_query_bank=False)."""
    enc = make_mlp_encoder()
    for flags in (dict(reset_banks_each_update=True), dict(use_query_bank=False)):
        cfg = ContrastiveConfig(
            method=method, accumulation_steps=2, bank_size=8, **flags
        )
        tx = _tx(cfg)
        state0 = init_state(jax.random.PRNGKey(0), enc, tx, cfg)
        batches = [make_batch(jax.random.PRNGKey(i), 8) for i in range(3)]
        s_seed, _ = _run_trajectory(jax.jit(SEED_BUILDERS[method](enc, tx, cfg)), state0, batches)
        s_new, _ = _run_trajectory(jax.jit(build_step_program(enc, tx, cfg).update), state0, batches)
        _assert_state_close(s_seed.params, s_new.params, f"{flags}: params")
        _assert_state_close(s_seed.bank_p, s_new.bank_p, f"{flags}: bank_p")


def test_scanned_metrics_are_row_weighted():
    """Regression: _reduce_scanned_aux must weight per-chunk loss/accuracy by
    each chunk's row count. During bank warm-up the chunks see different
    numbers of valid extra rows (chunk 0: none; chunk 1: the rows chunk 0
    pushed), so the unweighted mean of chunk means is NOT the mean over the
    update's rows — the fixed metric must match a hand-computed reference."""
    from repro.core import contrastive_step_loss, init_bank, push_pair

    enc = make_mlp_encoder()
    cfg = ContrastiveConfig(method="contaccum", accumulation_steps=2, bank_size=4)
    tx = _tx(cfg)
    state = init_state(jax.random.PRNGKey(0), enc, tx, cfg)
    batch = make_batch(jax.random.PRNGKey(7), 8)
    _, m = jax.jit(build_step_program(enc, tx, cfg).update)(state, batch)

    # hand-computed reference: replay the two chunk evaluations + pushes
    q = enc.encode_query(state.params, batch.query)
    p = enc.encode_passage(state.params, batch.passage_pos)
    bq, bp = init_bank(4, enc.rep_dim), init_bank(4, enc.rep_dim)
    losses, accs, ns = [], [], []
    for k in range(2):
        qk, pk = q[4 * k : 4 * (k + 1)], p[4 * k : 4 * (k + 1)]
        _, aux = contrastive_step_loss(qk, pk, None, bq, bp)
        losses.append(float(aux.loss))
        accs.append(float(aux.accuracy))
        ns.append(float(aux.n_rows))
        bq, bp = push_pair(bq, bp, qk, pk)
    assert ns == [4.0, 8.0]  # warm-up: chunk 1 gained 4 aligned bank rows
    want_loss = sum(l * n for l, n in zip(losses, ns)) / sum(ns)
    want_acc = sum(a * n for a, n in zip(accs, ns)) / sum(ns)
    # the old unweighted mean of chunk means is a genuinely different number
    assert abs(want_loss - np.mean(losses)) > 1e-6
    np.testing.assert_allclose(float(m.loss), want_loss, rtol=1e-6)
    np.testing.assert_allclose(float(m.accuracy), want_acc, rtol=1e-6)


def test_unequal_nonzero_dual_bank_capacities_rejected():
    """Regression: bank_size_q != bank_size_p (both > 0) silently corrupted
    extra-row labels once either ring wrapped (heads advance mod different
    capacities). The dual-bank source must refuse to build such a config;
    disabling one bank entirely (the pre-batch ablation) stays allowed."""
    enc = make_mlp_encoder()
    cfg = ContrastiveConfig(
        method="contaccum", accumulation_steps=2, bank_size_q=4, bank_size_p=6
    )
    with pytest.raises(ValueError, match="equal non-zero capacities"):
        build_step_program(enc, _tx(cfg), cfg)
    # zero-capacity query bank (pre-batch shape) still builds
    ok = ContrastiveConfig(
        method="contaccum", accumulation_steps=2, bank_size=6, use_query_bank=False
    )
    build_step_program(enc, _tx(ok), ok)


def test_shard_banks_requires_dp_axis():
    enc = make_mlp_encoder()
    cfg = ContrastiveConfig(
        method="contaccum", accumulation_steps=2, bank_size=8, shard_banks=True
    )
    with pytest.raises(ValueError, match="shard_banks"):
        build_step_program(enc, _tx(cfg), cfg)


def test_loss_comm_validated_at_build():
    enc = make_mlp_encoder()
    base = dict(method="contaccum", accumulation_steps=2, bank_size=8)
    cfg = ContrastiveConfig(**base, loss_comm="carrier_pigeon")
    with pytest.raises(ValueError, match="unknown loss_comm"):
        build_step_program(enc, _tx(cfg), cfg)
    # ring streams bank shards — meaningless without sharded banks ...
    cfg = ContrastiveConfig(**base, dp_axis="dp", loss_comm="ring")
    with pytest.raises(ValueError, match="loss_comm"):
        build_step_program(enc, _tx(cfg), cfg)
    # ... or without banks at all
    cfg = ContrastiveConfig(method="dpr", dp_axis="dp", loss_comm="ring")
    with pytest.raises(ValueError, match="loss_comm"):
        build_step_program(enc, _tx(cfg), cfg)


def test_every_advertised_composition_builds_and_jits():
    enc = make_mlp_encoder()
    batch = make_batch(jax.random.PRNGKey(5), 8, n_hard=1)
    for method in available_methods():
        neg, bp = method_composition(method)
        cfg = ContrastiveConfig(
            method=method,
            accumulation_steps=2 if bp != "direct" else 1,
            bank_size=8 if neg in ("dual_bank", "passage_bank") else 0,
            dp_axis="dp" if neg == "gathered" else None,
        )
        tx = _tx(cfg)
        program = build_step_program(enc, tx, cfg)
        assert program.name == method
        assert program.source.name == neg and program.strategy.name == bp
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
        state, m = update(state, batch)
        assert np.isfinite(float(m.loss)), method
        for leaf in jax.tree_util.tree_leaves(state.params):
            assert np.all(np.isfinite(np.asarray(leaf))), method


def test_explicit_axes_override_method_string():
    """negatives=/backprop= fields compose freely and win over method=."""
    enc = make_mlp_encoder()
    batch = make_batch(jax.random.PRNGKey(9), 8, n_hard=1)
    # dpr + backprop=rep_cache is grad_cache
    cfg_a = ContrastiveConfig(method="dpr", backprop="rep_cache", accumulation_steps=2)
    cfg_b = ContrastiveConfig(method="grad_cache", accumulation_steps=2)
    tx = _tx(cfg_a)
    state0 = init_state(jax.random.PRNGKey(0), enc, tx, cfg_a)
    s_a, _ = jax.jit(build_step_program(enc, tx, cfg_a).update)(state0, batch)
    s_b, _ = jax.jit(build_step_program(enc, tx, cfg_b).update)(state0, batch)
    _assert_state_close(s_a.params, s_b.params, "override != grad_cache")
    assert build_step_program(enc, tx, cfg_a).name == "grad_cache"


def test_unknown_names_raise():
    enc = make_mlp_encoder()
    tx = _tx(ContrastiveConfig())
    with pytest.raises(ValueError, match="unknown method"):
        build_step_program(enc, tx, ContrastiveConfig(method="nope"))
    with pytest.raises(ValueError, match="unknown negatives"):
        build_step_program(enc, tx, ContrastiveConfig(negatives="nope", backprop="scan"))
    with pytest.raises(ValueError, match="unknown backprop"):
        build_step_program(enc, tx, ContrastiveConfig(negatives="in_batch", backprop="nope"))
    with pytest.raises(ValueError, match="dp_axis"):
        build_step_program(enc, tx, ContrastiveConfig(method="dpr_xdev"))


@pytest.mark.parametrize("method", ["contcache", "prebatch_cache"])
def test_cache_compositions_reduce_to_dpr_with_empty_banks(method):
    """rep-cache backprop is exact: with no bank entries both new cache
    compositions must produce DPR's full-batch gradients."""
    enc = make_mlp_encoder()
    batch = make_batch(jax.random.PRNGKey(4), 16, n_hard=1)
    cfg_dpr = ContrastiveConfig(method="dpr")
    cfg_new = ContrastiveConfig(method=method, accumulation_steps=4, bank_size=0)
    tx = _tx(cfg_dpr)
    s0 = init_state(jax.random.PRNGKey(0), enc, tx, cfg_dpr)
    s_dpr, m_dpr = jax.jit(build_step_program(enc, tx, cfg_dpr).update)(s0, batch)
    s0n = init_state(jax.random.PRNGKey(0), enc, _tx(cfg_new), cfg_new)
    s_new, m_new = jax.jit(build_step_program(enc, _tx(cfg_new), cfg_new).update)(s0n, batch)
    np.testing.assert_allclose(float(m_dpr.loss), float(m_new.loss), rtol=1e-6)
    _assert_state_close(s_dpr.params, s_new.params, method, rtol=2e-5, atol=1e-7)


def test_contcache_trains_with_bank_extended_negatives():
    """contcache: full-batch loss (rep-cache) + dual banks. After warm-up the
    negative count exceeds the in-batch total, banks stay in lockstep, and
    the loss is finite over a short training run."""
    enc = make_mlp_encoder()
    cfg = ContrastiveConfig(method="contcache", accumulation_steps=4, bank_size=32)
    tx = chain(clip_by_global_norm(2.0), adamw(1e-2))
    state = init_state(jax.random.PRNGKey(0), enc, tx, cfg)
    update = jax.jit(build_step_program(enc, tx, cfg).update)
    for i in range(4):
        state, m = update(state, make_batch(jax.random.PRNGKey(20 + i), 16))
    # one full-batch loss per update: columns = B + N_mem -> 16 + 32 - 1
    assert float(m.n_negatives) == 16 + 32 - 1
    assert float(m.bank_fill_q) == 32.0 and float(m.bank_fill_p) == 32.0
    assert np.isfinite(float(m.loss))


def test_prebatch_cache_has_no_query_bank():
    enc = make_mlp_encoder()
    cfg = ContrastiveConfig(method="prebatch_cache", accumulation_steps=2, bank_size=16)
    tx = _tx(cfg)
    state = init_state(jax.random.PRNGKey(0), enc, tx, cfg)
    assert state.bank_q.buf.shape[0] == 0        # passage-only source
    assert state.bank_p.buf.shape[0] == 16
    update = jax.jit(build_step_program(enc, tx, cfg).update)
    for i in range(3):
        state, m = update(state, make_batch(jax.random.PRNGKey(i), 8))
    assert float(m.bank_fill_p) == 16.0
    assert float(m.bank_fill_q) == 0.0
    assert float(m.n_negatives) == 8 + 16 - 1    # full batch + passage bank


def test_make_update_fn_is_thin_registry_over_programs():
    """The legacy factory and the program builder return the same update."""
    enc = make_mlp_encoder()
    cfg = ContrastiveConfig(method="contaccum", accumulation_steps=2, bank_size=8)
    tx = _tx(cfg)
    batch = make_batch(jax.random.PRNGKey(3), 8)
    state = init_state(jax.random.PRNGKey(0), enc, tx, cfg)
    s_a, m_a = jax.jit(make_update_fn(enc, tx, cfg))(state, batch)
    s_b, m_b = jax.jit(build_step_program(enc, tx, cfg).update)(state, batch)
    np.testing.assert_allclose(float(m_a.loss), float(m_b.loss), rtol=0)
    _assert_state_close(s_a.params, s_b.params, "factory != program")


def test_registry_covers_full_matrix_of_shipped_methods():
    """Every (source, strategy) pair the paper + the new methods need is an
    advertised composition; names resolve both ways."""
    cells = {method_composition(m) for m in available_methods()}
    for want in [
        ("in_batch", "direct"), ("in_batch", "scan"), ("in_batch", "rep_cache"),
        ("dual_bank", "scan"), ("dual_bank", "rep_cache"),
        ("passage_bank", "scan"), ("passage_bank", "rep_cache"),
        ("gathered", "direct"),
    ]:
        assert want in cells, want
    assert COMPOSITIONS["contaccum"] == ("dual_bank", "scan")


# ------------------------------------------------------------------ drivers
def _load_example(name):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "examples", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"example_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("method", ["contcache", "prebatch_cache"])
def test_new_methods_train_end_to_end_through_example_driver(method):
    """examples/train_retriever.py drives the new compositions unchanged."""
    mod = _load_example("train_retriever")
    mod.main([
        "--method", method,
        "--steps", "3",
        "--warmup-steps", "2",
        "--total-batch", "16",
        "--local-batch", "8",
        "--bank", "16",
        "--corpus", "64",
    ])


def test_contrastive_cell_serves_new_compositions():
    """launch/steps.py builds the contrastive cell for the new methods; the
    program traces with the cell's sharded abstract inputs."""
    from jax.sharding import Mesh

    from repro.launch.steps import build_cell

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    for shape in ("contcache_batch", "prebatch_cache_batch"):
        prog = build_cell("dpr-bert-base", shape, mesh)
        assert prog.static_info["method"] == shape.replace("_batch", "")
        out = jax.eval_shape(prog.fn, *prog.args)
        assert out is not None
    # the shard_map (xdev) cells trace with sharded-bank state specs too
    for shape in ("contaccum_xdev", "contcache_xdev"):
        prog = build_cell("dpr-bert-base", shape, mesh)
        assert prog.static_info["method"] == shape.replace("_xdev", "")
        assert prog.static_info["xdev"] and prog.static_info["shard_banks"]
        out = jax.eval_shape(prog.fn, *prog.args)
        assert out is not None


def _shared(enc):
    """``enc``'s query tower as one backbone that both sides encode with."""
    return enc._replace(
        init=lambda rng: {"shared": enc.init(rng)["query"]},
        encode_query=lambda p, x: enc.encode_query({"query": p["shared"]}, x),
        encode_passage=lambda p, x: enc.encode_passage({"passage": p["shared"]}, x),
        shared=True,
    )


@pytest.mark.parametrize("method", ["dpr", "contaccum", "contcache"])
def test_shared_tower_is_one_copy_with_the_sum_of_both_gradients(method):
    """A shared tower is stored once, in the parameters and in the
    optimizer's state, and moves by the sum of what each side contributes:
    under SGD its update is that of two towers that start equal, added. Its
    grad_norm_query and grad_norm_passage are the norms of the two sides'
    contributions, the two towers' own gradient norms."""
    two = make_mlp_encoder()
    one = _shared(two)
    cfg = ContrastiveConfig(method=method, accumulation_steps=2, bank_size=8,
                            grad_clip_norm=1e9)
    tx = sgd(0.1)
    p = two.init(jax.random.PRNGKey(0))["query"]
    s1 = init_state(None, one, tx, cfg, params={"shared": p})
    s2 = init_state(None, two, tx, cfg, params={"query": p, "passage": p})
    u1 = jax.jit(build_step_program(one, tx, cfg).update)
    u2 = jax.jit(build_step_program(two, tx, cfg).update)
    for i in range(2):
        batch = make_batch(jax.random.PRNGKey(10 + i), 8, n_hard=1)
        before = s1.params["shared"]
        s1, m1 = u1(s1, batch)
        s2, m2 = u2(s2, batch)
        for name in ("loss", "grad_norm_query", "grad_norm_passage"):
            np.testing.assert_allclose(getattr(m1, name), getattr(m2, name), rtol=1e-5)
        both = jax.tree_util.tree_map(lambda q, p_, b: q + p_ - b, s2.params["query"],
                                      s2.params["passage"], before)
        for a, b in zip(jax.tree_util.tree_leaves(s1.params["shared"]),
                        jax.tree_util.tree_leaves(both)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        # two towers that moved apart no longer stand for the shared one
        s2 = s2._replace(params={"query": s1.params["shared"], "passage": s1.params["shared"]})
    n_one = len(jax.tree_util.tree_leaves(p))
    assert list(s1.params) == ["shared"]
    assert len(jax.tree_util.tree_leaves(s1.params)) == n_one
    assert len(jax.tree_util.tree_leaves(s1.opt_state)) <= len(jax.tree_util.tree_leaves(s2.opt_state)) // 2 + 1
