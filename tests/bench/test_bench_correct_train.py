"""A training run at a size the CPU holds, with the chip check skipped:
sound, it is correct; with the timed path broken underneath (a step that
returns its state unchanged; half of every batch left out, the mean over
the rest) it is not. The control is in test_bench_control.py. The
reference's fault that drops half the chunks' gradients, which
bench/control.py records and ``correct`` cannot see at bf16 (PERF.md),
keeps the first update's loss and changes its gradient norm."""

import jax.numpy as jnp
import pytest

import bench_tiny
from bench.harness import reference


@pytest.fixture(scope="module")
def sound():
    return bench_tiny.run(bench_tiny.TinyCell("train"))


def test_sound_run_is_correct(sound):
    r, res = sound
    assert res["correct"], res["checks"]
    assert res["end_to_end"]["train_pairs_per_s"] > 0 and res["attempted"] > 3
    assert list(res["checks"]) == ["loss_gap", "change_gap"]


def test_state_left_unchanged_is_caught(monkeypatch):
    import repro.core.step_program as sp

    monkeypatch.setattr(sp, "apply_updates", lambda params, updates: params)
    _, res = bench_tiny.run(bench_tiny.TinyCell("train"))
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_left_out_is_caught(monkeypatch):
    import repro.core.step_program as sp

    chunk = sp.chunk_tree

    def half_rows(tree, k):
        return jax_tree_map(lambda x: x[:, : x.shape[1] // 2], chunk(tree, k))

    monkeypatch.setattr(sp, "chunk_tree", half_rows)
    _, res = bench_tiny.run(bench_tiny.TinyCell("train"))
    assert not res["correct"], res["checks"]


def test_reference_with_half_the_chunks_gradients_dropped(sound):
    from bench.drivers import train

    r, res = sound
    batches = res["extra"]["batches"]
    k = r.workload["total_batch"] // r.workload["local_batch"]
    ref = res["extra"]["reference"]
    dropped = train.reference_readings(r, batches, grad_chunks=k // 2)
    # every chunk's loss and bank push are kept: the first update's loss,
    # before any parameter moved, is the same
    assert dropped["losses"][0] == pytest.approx(ref["losses"][0], rel=1e-6)
    # the first update's gradient is the first K/2 chunks' over K
    assert dropped["grad_norms"][0][0] < ref["grad_norms"][0][0]
    assert dropped["grad_norms"][0][0] != pytest.approx(ref["grad_norms"][0][0], rel=1e-3)


def jax_tree_map(f, tree):
    import jax

    return jax.tree_util.tree_map(f, tree)


def test_fp8_round_trip_is_coarser_than_bf16():
    x = jnp.linspace(-3.0, 3.0, 1001)
    e8 = jnp.max(jnp.abs(reference.fp8(x) - x))
    e16 = jnp.max(jnp.abs(x.astype(jnp.bfloat16).astype(jnp.float32) - x))
    assert e8 > 4 * e16
    # the per-tensor scale keeps large and small tensors alike in range
    for scale in (1e-6, 1e4):
        y = reference.fp8(x * scale)
        assert jnp.max(jnp.abs(y - x * scale)) <= 0.07 * 3 * scale
