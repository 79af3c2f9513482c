"""Runtime-layer tests: fault-tolerant trainer (checkpoint/restart, fault
injection, straggler watchdog, preemption), elastic resharding, and the
dynamic-batching retrieval server."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.memory_bank import init_bank, push
from repro.data.loader import LoaderState, ShardedLoader
from repro.distribution.elastic import bank_to_arrays, plan_resize, reshard_bank
from repro.runtime.server import BatchingServer, blocked_topk_scores
from repro.runtime.trainer import StepFailure, Trainer, TrainerConfig


# ---------------------------------------------------------------- trainer
def _counting_step():
    """step_fn over a scalar 'state' counting applied batches."""

    def step(state, batch):
        new = state + batch
        return new, {"loss": float(jnp.asarray(new)) * 0 + 1.0}

    return step


def test_trainer_runs_and_checkpoints(tmp_path):
    step_fn = lambda s, b: (s + b, {"loss": 1.0})
    tr = Trainer(
        TrainerConfig(total_steps=10, checkpoint_dir=str(tmp_path),
                      checkpoint_every=3, log_every=100),
        step_fn,
        next_batch=lambda i: jnp.asarray(1.0),
    )
    state, report = tr.run(jnp.asarray(0.0))
    assert report.steps_run == 10
    assert float(state) == 10.0


def test_trainer_resumes_from_checkpoint(tmp_path):
    step_fn = lambda s, b: (s + b, {"loss": 1.0})
    cfg = TrainerConfig(total_steps=5, checkpoint_dir=str(tmp_path),
                        checkpoint_every=2, log_every=100)
    tr = Trainer(cfg, step_fn, next_batch=lambda i: jnp.asarray(1.0))
    state, _ = tr.run(jnp.asarray(0.0))
    # second trainer continues where the first stopped
    cfg2 = TrainerConfig(total_steps=9, checkpoint_dir=str(tmp_path),
                         checkpoint_every=2, log_every=100)
    tr2 = Trainer(cfg2, step_fn, next_batch=lambda i: jnp.asarray(1.0))
    state2, report2 = tr2.run(jnp.asarray(0.0))
    assert float(state2) == 9.0          # resumed from 5, not restarted at 0
    assert report2.steps_run < 9


def test_trainer_bank_roundtrip_is_bit_identical(tmp_path):
    """ContrastiveState checkpoint round-trip: saving mid-warm-up (banks
    partially filled, ring heads mid-buffer) and restoring must reproduce
    the uninterrupted bank trajectory bit-for-bit — BankState.head/valid/age
    are restored purely by template dtype (int32/bool/int32), so any dtype
    or layout drift in the checkpoint path would desynchronize the rings."""
    import sys

    sys.path.insert(0, "tests")
    from helpers import make_batch, make_mlp_encoder

    from repro.core import ContrastiveConfig, build_step_program, init_state
    from repro.optim import chain, clip_by_global_norm, sgd

    enc = make_mlp_encoder()
    # bank_size 24 and B=8 x K=2: after 3 steps the banks hold 24 of 24 rows
    # with head mid-ring; the interruption at step 2 lands mid-warm-up
    cfg = ContrastiveConfig(method="contaccum", accumulation_steps=2, bank_size=24)
    tx = chain(clip_by_global_norm(2.0), sgd(0.1))
    update = jax.jit(build_step_program(enc, tx, cfg).update)
    batches = {i: make_batch(jax.random.PRNGKey(40 + i), 8) for i in range(6)}

    def trainer(total_steps, ckpt_dir):
        return Trainer(
            TrainerConfig(total_steps=total_steps, checkpoint_dir=ckpt_dir,
                          checkpoint_every=2, log_every=100),
            update,
            next_batch=lambda i: batches[i],
        )

    state0 = init_state(jax.random.PRNGKey(0), enc, tx, cfg)

    # uninterrupted reference: 6 steps straight through, no checkpoint dir
    ref = state0
    for i in range(6):
        ref, _ = update(ref, batches[i])

    # interrupted run: stop after 3 steps (checkpoint at step 2 mid-warm-up),
    # then a fresh trainer restores and continues to 6
    a = str(tmp_path / "roundtrip")
    trainer(3, a).run(state0)
    resumed, report = trainer(6, a).run(state0)
    assert report.steps_run < 6  # proves it resumed, not re-ran

    assert int(resumed.step) == int(ref.step) == 6
    for bank in ("bank_q", "bank_p"):
        got, want = getattr(resumed, bank), getattr(ref, bank)
        np.testing.assert_array_equal(np.asarray(got.buf), np.asarray(want.buf),
                                      err_msg=f"{bank}.buf")
        np.testing.assert_array_equal(np.asarray(got.valid), np.asarray(want.valid))
        np.testing.assert_array_equal(np.asarray(got.age), np.asarray(want.age))
        assert got.valid.dtype == want.valid.dtype == np.bool_
        assert got.head.dtype == want.head.dtype == jnp.int32
        assert int(got.head) == int(want.head), bank
    for a_, b_ in zip(jax.tree_util.tree_leaves(resumed.params),
                      jax.tree_util.tree_leaves(ref.params)):
        np.testing.assert_array_equal(np.asarray(a_), np.asarray(b_))


def test_trainer_restores_after_injected_fault(tmp_path):
    step_fn = lambda s, b: (s + b, {"loss": 1.0})
    failures = {"at": 6, "done": False}

    def fault_hook(step):
        if step == failures["at"] and not failures["done"]:
            failures["done"] = True
            raise StepFailure("injected node failure")

    tr = Trainer(
        TrainerConfig(total_steps=10, checkpoint_dir=str(tmp_path),
                      checkpoint_every=2, max_restarts=2, log_every=100),
        step_fn,
        next_batch=lambda i: jnp.asarray(1.0),
        fault_hook=fault_hook,
    )
    state, report = tr.run(jnp.asarray(0.0))
    assert report.restarts == 1
    assert float(state) == 10.0          # replayed steps land on the same total


def test_trainer_gives_up_after_max_restarts(tmp_path):
    step_fn = lambda s, b: (s + b, {"loss": 1.0})

    def fault_hook(step):
        if step >= 3:
            raise StepFailure("persistent failure")

    tr = Trainer(
        TrainerConfig(total_steps=10, checkpoint_dir=str(tmp_path),
                      checkpoint_every=1, max_restarts=2, log_every=100),
        step_fn,
        next_batch=lambda i: jnp.asarray(1.0),
        fault_hook=fault_hook,
    )
    with pytest.raises(StepFailure):
        tr.run(jnp.asarray(0.0))


def test_trainer_aborts_restores_on_nan(tmp_path):
    calls = {"n": 0}

    def step_fn(s, b):
        calls["n"] += 1
        if calls["n"] == 4:
            return s, {"loss": float("nan")}
        return s + b, {"loss": 1.0}

    tr = Trainer(
        TrainerConfig(total_steps=6, checkpoint_dir=str(tmp_path),
                      checkpoint_every=1, max_restarts=1, log_every=100),
        step_fn,
        next_batch=lambda i: jnp.asarray(1.0),
    )
    state, report = tr.run(jnp.asarray(0.0))
    assert report.restarts == 1
    assert float(state) == 6.0


def test_straggler_watchdog():
    times = iter([1.0] * 40)  # monotonically consumed fake clock
    clock_state = {"t": 0.0}
    slow_at = 12

    def clock():
        return clock_state["t"]

    def step_fn(s, b):
        # every step advances 10ms, the straggler 200ms
        dt = 0.2 if int(s) == slow_at else 0.01
        clock_state["t"] += dt
        return s + 1, {"loss": 1.0}

    tr = Trainer(
        TrainerConfig(total_steps=20, straggler_factor=3.0,
                      straggler_warmup=3, log_every=100),
        step_fn,
        next_batch=lambda i: 0,
        clock=clock,
    )
    _, report = tr.run(jnp.asarray(0))
    assert report.stragglers == [slow_at]


def test_preemption_stop(tmp_path):
    tr = Trainer(
        TrainerConfig(total_steps=1000, checkpoint_dir=str(tmp_path),
                      log_every=10_000),
        lambda s, b: (s + b, {"loss": 1.0}),
        next_batch=lambda i: jnp.asarray(1.0),
    )

    def stopper(step):
        if step == 7:
            tr.request_stop()

    tr.fault_hook = stopper
    state, report = tr.run(jnp.asarray(0.0))
    assert 7 <= float(state) <= 8        # finished current step, then stopped
    # final checkpoint was written
    from repro.checkpoint.checkpoint import latest_step

    assert latest_step(str(tmp_path)) is not None


# ------------------------------------------------------------------ elastic
def test_elastic_loader_resize_replays_same_global_stream():
    n, gb = 512, 32
    one = ShardedLoader(n, gb, seed=3, host_id=0, n_hosts=1)
    ref = [one.next_indices() for _ in range(10)]

    # 4 hosts, resumed at step 5 with 2 hosts: union must equal the global batch
    hosts4 = [ShardedLoader(n, gb, seed=3, host_id=h, n_hosts=4) for h in range(4)]
    for step in range(5):
        parts = [h.next_indices() for h in hosts4]
        assert np.array_equal(np.sort(np.concatenate(parts)), np.sort(ref[step]))
    state = hosts4[0].state
    hosts2 = [
        ShardedLoader(n, gb, seed=3, host_id=h, n_hosts=2,
                      state=LoaderState(state.epoch, state.step))
        for h in range(2)
    ]
    for step in range(5, 10):
        parts = [h.next_indices() for h in hosts2]
        assert np.array_equal(np.sort(np.concatenate(parts)), np.sort(ref[step]))


def test_plan_resize_picks_divisible_layout():
    p = plan_resize(384, global_batch=128, tp=16)
    assert p.dp * p.tp == 384 and 128 % p.dp == 0
    p2 = plan_resize(96, global_batch=96)
    assert p2.dp * p2.tp == 96 and 96 % p2.dp == 0
    with pytest.raises(ValueError):
        plan_resize(100, global_batch=3, tp=1)


def test_reshard_bank_keeps_newest_in_order():
    bank = init_bank(8, 4)
    for i in range(11):  # wraps: slots hold entries 3..10
        bank = push(bank, jnp.full((1, 4), float(i)), step=i)
    shrunk = reshard_bank(bank_to_arrays(bank), 4)
    kept = sorted(shrunk["buf"][shrunk["valid"]][:, 0].tolist())
    assert kept == [7.0, 8.0, 9.0, 10.0]

    grown = reshard_bank(bank_to_arrays(bank), 16)
    kept = sorted(grown["buf"][grown["valid"]][:, 0].tolist())
    assert kept == [float(i) for i in range(3, 11)]
    assert int(grown["head"]) == 8       # next write appends after the newest


def test_reshard_bank_roundtrip_through_push():
    from repro.distribution.elastic import arrays_to_bank

    bank = init_bank(6, 2)
    for i in range(4):
        bank = push(bank, jnp.full((1, 2), float(i)))
    resized = arrays_to_bank(reshard_bank(bank_to_arrays(bank), 3))
    resized = push(resized, jnp.full((1, 2), 99.0))
    vals = sorted(np.asarray(resized.buf)[np.asarray(resized.valid)][:, 0].tolist())
    assert vals == [2.0, 3.0, 99.0]      # FIFO semantics survive the resize


# ------------------------------------------------------------------- server
def test_blocked_topk_matches_argsort():
    rng = np.random.default_rng(0)
    q = rng.normal(size=(5, 16)).astype(np.float32)
    idx = rng.normal(size=(1000, 16)).astype(np.float32)
    scores, ids = blocked_topk_scores(jnp.asarray(q), jnp.asarray(idx), 10, block=128)
    ref = np.argsort(-(q @ idx.T), axis=1)[:, :10]
    assert np.array_equal(np.asarray(ids), ref)


def test_batching_server_coalesces_and_answers():
    def serve(batch):  # identity "scores": payload sums
        s = batch.sum(axis=1, keepdims=True)
        ids = np.arange(len(batch))[:, None]
        return ids, np.asarray(s)

    srv = BatchingServer(serve, max_batch=8, max_wait_s=0.05).start()
    try:
        futs = [srv.submit(np.full((4,), float(i))) for i in range(20)]
        outs = [f.get(timeout=10) for f in futs]
        for i, (ids, score) in enumerate(outs):
            assert score[0] == pytest.approx(4.0 * i)
        assert max(srv.batch_sizes) > 1   # coalescing actually happened
    finally:
        srv.stop()


def test_batching_server_propagates_errors():
    def serve(batch):
        raise RuntimeError("model exploded")

    srv = BatchingServer(serve, max_batch=4, max_wait_s=0.01).start()
    try:
        fut = srv.submit(np.zeros((2,)))
        res = fut.get(timeout=10)
        assert isinstance(res, RuntimeError)
    finally:
        srv.stop()


# ---------------------------------------------------------- compile cache
@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir_is_placed_from_outside(monkeypatch, tmp_path, from_env):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and the helper sets
    no other; unset, the cache is the fixed, git-ignored checkout path."""
    from pathlib import Path

    from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, setup_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert setup_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            assert setup_compile_cache() == str(CHECKOUT_CACHE_DIR)
            assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
            root = Path(__file__).resolve().parents[1]
            assert CHECKOUT_CACHE_DIR == root / ".jax_cache"
            assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
