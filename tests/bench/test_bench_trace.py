"""The trace reduction on a small committed trace: busy union, leaf
operations, kernel attribution, whole program executions, idle gaps by
host span."""

import json
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the checkout on sys.path)
from bench.harness import readers, trace

DATA = Path(__file__).parent / "data" / "trace_small.json"


@pytest.fixture(scope="module")
def red():
    return trace.reduce(json.loads(DATA.read_text()))


def test_window_and_busy_union(red):
    # [2000, 6000) while loop, [7000, 9000) convolution, [10500, 11000) clipped
    assert red["window_s"] == pytest.approx(10000e-9)
    assert red["busy_s"] == pytest.approx(6500e-9)


def test_leaves_exclude_loops_and_outside_ops(red):
    names = sorted(trace.label(n).rsplit(" (", 1)[1] for _, _, n in red["leaf_ops"][0])
    assert names == ["all-reduce)", "convolution)", "fusion)", "fusion)", "jvp__)"]


@pytest.mark.parametrize("match, seconds, calls", [
    (readers.is_infonce, 1500e-9, 1),
    (lambda n: "convolution(" in n, 2000e-9, 1),
    (lambda n: n.startswith("%while"), 0.0, 0),
])
def test_op_seconds(red, match, seconds, calls):
    t, c = trace.op_seconds(red, match)
    assert t == pytest.approx(seconds) and c == calls


def test_idle_gaps_by_host_span(red):
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps == pytest.approx({"bench.dispatch": 1000e-9, "bench.batch": 1000e-9,
                                  "host.other": 1500e-9})


def test_program_counts_whole_executions_only(red):
    assert red["program"] == {"name": "jit_update", "calls": 1, "seconds": pytest.approx(7000e-9)}


def test_breakdown_lists_ops_by_time(red):
    ops = red["breakdown"]["device_ops"]
    assert ops[0][0].startswith("convolution bf16[32,768]") and ops[0][1] == pytest.approx(2000e-9)
    assert len(ops) <= 10


@pytest.mark.parametrize("hlo, infonce", [
    ('%jvp__.24 = (f32[16384,1]{1,0:T(8,128)S(1)}) custom-call(s32[16384,1]{1,0:T(8,128)S(1)} '
     '%custom-call.69, s32[1,16512]{1,0:T(1,128)S(1)} %copy-done.162), '
     'custom_call_target="tpu_custom_call"', True),
    ('%custom-call.3 = f32[32,128]{1,0} custom-call(bf16[32,768]{1,0} %q, bf16[128,768]{1,0} %p), '
     'custom_call_target="tpu_custom_call"', False),
    ('%fusion.2 = f32[8,1]{1,0} fusion(s32[8,1]{1,0} %a, s32[1,8]{1,0} %b)', False),
])
def test_infonce_kernel_is_told_apart(hlo, infonce):
    assert readers.is_infonce(hlo) is infonce
