"""The LM tower's training cell (bench/drivers/train_lm.py) at a size the
CPU holds, with the chip check skipped: the Moonlight-shaped tower of the
cell's configuration at CPU-test widths (``moonlight-tiny``), fp32. Sound,
it is correct; under the cell's own limits the fp8 control, a state left
unchanged, half of every batch left out on the timed path, and each fault
planted in the reference (tokens dropped by capacity, gates from the
biased scores, half of every batch left out, half the chunks' gradients
dropped) are not. At this size's learning rate the parameters move far
enough in three updates for every fault to show; at the chip cell's
warm-up some do not (PERF.md)."""

import json

import jax
import pytest

import bench_tiny
from bench_tiny import ROOT

CONFIG = json.loads((ROOT / "bench/configs/moonlight-16b-a3b-contaccum-ep8.json").read_text())
TINY = dict(CONFIG, hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
            moe_intermediate_size=32, num_experts_per_tok=3, n_routed_experts=4,
            num_hidden_layers=3, vocab_size=1000, driver="train_lm",
            deployment=dict(CONFIG["deployment"], experts_published=16, experts_held=[0, 4]),
            program=dict(CONFIG["program"], arch="moonlight-tiny", precision="fp32"))
WORKLOAD = dict(bench_tiny.TRAIN, total_batch=8, local_batch=4, q_len=8, p_len=16, bank=8,
                limits=bench_tiny.limits("train-moonlight-ep8"))


class TinyLMCell(bench_tiny.TinyCell):
    def __init__(self):
        super().__init__("train")
        self.name = "train-lm-tiny"
        self.config, self.workload = TINY, WORKLOAD


@pytest.fixture(scope="module")
def sound():
    return bench_tiny.run(TinyLMCell())


def test_sound_run_is_correct(sound):
    r, res = sound
    assert res["correct"], res["checks"]
    assert res["end_to_end"]["train_pairs_per_s"] > 0 and res["attempted"] > 3
    layer = res["layer"]
    assert layer["expert_load_max_over_mean"] >= 1.0
    assert layer["flops_per_update"] > 0 and layer["expert_least_s_per_update"] > 0
    assert r.cell.reader("train.expert_load_max_over_mean")(layer) >= 1.0
    # nothing traced: the trace readers find nothing and say so
    assert r.cell.reader("train.moe_device_share")(layer) is None
    assert r.cell.reader("train.expert_roofline")(layer) is None


@pytest.fixture(scope="module")
def readings(sound):
    from bench.drivers import train_lm

    r, res = sound
    return train_lm.controls(r, res)


def test_controls_and_faults_move_the_comparison(readings):
    assert set(readings) == {"control_fp8", "capacity_1.0", "biased_gates", "half_batch",
                             "grads_dropped"}
    for name, rd in readings.items():
        assert rd["loss_gap"] > 1e-6 or rd["change_gap"] > 1e-4, (name, rd)


@pytest.mark.parametrize("name", ["control_fp8", "capacity_1.0", "biased_gates", "half_batch",
                                  "grads_dropped"])
def test_control_and_faults_are_not_correct(readings, name):
    from bench.harness import compare

    checks = compare.with_limits(readings[name], WORKLOAD["limits"])
    assert not compare.all_within(checks), (name, checks)


def test_grads_dropped_keeps_the_first_loss(sound):
    """Half the chunks' gradients dropped: every chunk's loss and push are
    kept, so the first update's loss, before any parameter moved, is the
    reference's; its gradient is the first K/2 chunks' over K."""
    from bench.drivers import train_lm

    r, res = sound
    ref = res["extra"]["reference"]
    k = WORKLOAD["total_batch"] // WORKLOAD["local_batch"]
    dropped = train_lm.reference_readings(r, res["extra"]["batches"], grad_chunks=k // 2)
    assert dropped["losses"][0] == pytest.approx(ref["losses"][0], rel=1e-6)
    assert dropped["grad_norms"][0][0] != pytest.approx(ref["grad_norms"][0][0], rel=1e-3)


def test_state_left_unchanged_is_caught(monkeypatch):
    import repro.core.step_program as sp

    monkeypatch.setattr(sp, "apply_updates", lambda params, updates: params)
    _, res = bench_tiny.run(TinyLMCell())
    assert not res["correct"]
    assert res["checks"]["change_gap"]["value"] == pytest.approx(1.0)


def test_half_batch_left_out_is_caught(monkeypatch):
    """Each chunk keeps the first half of its rows, and the shared tower's
    query side, which encodes the batch's queries in one call, the same."""
    import repro.core.step_program as sp

    chunk, query_side = sp.chunk_tree, sp._query_side
    k = WORKLOAD["total_batch"] // WORKLOAD["local_batch"]

    def half_rows(tree, k):
        return jax.tree_util.tree_map(lambda x: x[:, : x.shape[1] // 2], chunk(tree, k))

    def half_queries(encoder, params, queries, dq):
        kept = half_rows(queries, k)
        return query_side(encoder, params, kept.reshape((-1,) + kept.shape[2:]), dq)

    monkeypatch.setattr(sp, "chunk_tree", half_rows)
    monkeypatch.setattr(sp, "_query_side", half_queries)
    _, res = bench_tiny.run(TinyLMCell())
    assert not res["correct"], res["checks"]


def test_moe_trace_reads_scopes_and_grouped_kernels():
    """Ops under a ``moe_*`` scope count to it, wrapped by differentiation
    or not; the ragged-dot kernels, whose op name replaces their scope
    path, count to ``moe_experts`` and, inside whole executions of the
    update, to ``grouped_program_s``; other ops count nowhere."""
    from bench.harness import moe_trace, trace

    ops = [[100, 10, "%fusion.1 = f32[8] fusion(f32[8] %a)"],
           [120, 20, "%ragged-dot-none.3 = bf16[64,32] custom-call(s32[1] %m, bf16[64,16] %x)"],
           [150, 5, "%sort.2 = s32[64] sort(s32[64] %k)"],
           [160, 7, "%fusion.4 = f32[8] fusion(f32[8] %b)"],
           [400, 30, "%ragged-dot-none.3 = bf16[64,32] custom-call(s32[1] %m, bf16[64,16] %x)"]]
    paths = {ops[0][2]: "jit(update)/while/body/transpose(jvp(towers))/moe_router/dot_general:",
             ops[2][2]: "jit(update)/jvp(towers)/moe_dispatch/sort:",
             ops[3][2]: "jit(update)/grad_accum/add:"}
    tr = {"devices": [{"name": "/device:TPU:0", "ops": ops, "op_paths": paths,
                       "modules": [[90, 200, "jit_update(123)"], [380, 200, "jit_update(123)"]]}],
          "host": [[50, 400, trace.WINDOW]]}
    got = moe_trace.reduce(tr)
    assert got["moe_scope_s"]["moe_router"] == pytest.approx(10e-9)
    assert got["moe_scope_s"]["moe_dispatch"] == pytest.approx(5e-9)
    assert got["moe_scope_s"]["moe_experts"] == pytest.approx(50e-9)
    assert got["grouped_s"] == pytest.approx(50e-9)
    # the second update runs past the window's end: its kernel is not in it
    assert got["grouped_program_s"] == pytest.approx(20e-9)
