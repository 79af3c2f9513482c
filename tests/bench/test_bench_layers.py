"""The program's layer map read from a trace (bench/harness/layers.py): scope
seconds, the scopes inside whole program executions, idle time by the
program's host spans and each number of ``METRICS``, on a committed trace
with known answers; the op paths of an ``.xplane.pb``; the program's host
spans in a CPU profiler trace; and the server's queue waits through
``bench/layers.py`` on a tiny serving cell."""

import json
import os
import shutil
import struct
import subprocess
import sys
from pathlib import Path

import pytest

import bench_tiny
from bench.harness import layers, trace

DATA = Path(__file__).parent / "data"
SCOPED = json.loads((DATA / "trace_scoped.json").read_text())
SMALL = json.loads((DATA / "trace_small.json").read_text())


@pytest.fixture(scope="module")
def red():
    return {name: layers.reduce(tr) for name, tr in SCOPED.items()}


@pytest.mark.parametrize("name, want", [
    ("train", {"towers": 3000e-9, "loss": 1000e-9, "grad_accum": 500e-9, "bank_push": 250e-9,
               "optimizer": 750e-9}),
    ("serve", {"block_topk": 4000e-9, "shard_merge": 1125e-9}),
])
def test_scope_seconds_in_the_window(red, name, want):
    assert red[name]["scope_s"] == pytest.approx(want)


@pytest.mark.parametrize("name, calls, want", [
    # the third update runs past the window: its optimizer op counts in
    # scope_s, not inside whole executions
    ("train", 2, {"towers": 3000e-9, "loss": 1000e-9, "grad_accum": 500e-9,
                  "bank_push": 250e-9, "optimizer": 250e-9}),
    ("serve", 2, {"block_topk": 4000e-9, "shard_merge": 1125e-9}),
])
def test_scope_seconds_inside_whole_executions(red, name, calls, want):
    assert red[name]["program"]["calls"] == calls
    assert red[name]["program"]["scope_s"] == pytest.approx(want)


@pytest.mark.parametrize("name, want", [
    ("train", {"repro.train.next_batch": 1000e-9, "repro.train.fetch": 1000e-9,
               "host.other": 500e-9}),
    ("serve", {"repro.server.collect": 4250e-9, "repro.server.search": 125e-9}),
])
def test_idle_gaps_by_program_span(red, name, want):
    assert dict(red[name]["breakdown"]["idle_gaps_program"]) == pytest.approx(want)


@pytest.mark.parametrize("name, want", [
    # the third step runs past the window
    ("train", [5000e-9, 3500e-9]),
    ("serve", []),
])
def test_step_seconds_in_the_window(red, name, want):
    assert red[name]["step_s"] == pytest.approx(want)


@pytest.mark.parametrize("name, metric, want", [
    ("train", "train.tower_device_share", 40.0),
    ("train", "train.loss_device_share", 100 / 7.5),
    ("train", "train.accum_device_share", 10.0),
    ("train", "train.optimizer_device_share", 10.0),
    ("train", "serve.block_topk_device_ms", None),
    ("serve", "serve.block_topk_device_ms", 2000e-6),
    ("serve", "serve.merge_collective_ms", 562.5e-6),
    ("serve", "train.tower_device_share", None),
])
def test_metric_on_the_scoped_trace(red, name, metric, want):
    got = layers.METRICS[metric]({"trace": red[name]})
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("metric", sorted(layers.METRICS))
def test_metric_is_none_without_its_scope_span_or_counter(metric):
    read = layers.METRICS[metric]
    assert read({}) is None
    # trace_small predates the scopes: no op has a path
    assert read({"trace": layers.reduce(SMALL), "max_batch": 8}) is None


@pytest.mark.parametrize("tr", [SMALL, SCOPED["train"], SCOPED["serve"]],
                         ids=["small", "train", "serve"])
def test_the_harness_reduction_is_kept_as_it_is(tr):
    base, red = trace.reduce(tr), layers.reduce(tr)
    assert set(red) == set(base) | {"scope_s", "step_s"}
    for key in base:
        if key == "program":
            assert {k: red[key][k] for k in base[key]} == base[key]
        elif key == "breakdown":
            assert red[key]["device_ops"] == base[key]["device_ops"]
            assert red[key]["idle_gaps"] == base[key]["idle_gaps"]
        else:
            assert red[key] == base[key], key


@pytest.mark.parametrize("path, scope", [
    ("jit(update)/while/body/closed_call/transpose(jvp(towers))/dot_general:", "towers"),
    ("jit(f)/transpose(jvp())/while/body/closed_call/towers/dot_general:", "towers"),
    ("jit(update)/while/body/closed_call/jvp(loss)/add;while/body/closed_call", "loss"),
    ("jit(update)/grad_accum/mul:", "grad_accum"),
    ("jit(search)/shard_map/shard_merge/psum:", "shard_merge"),
    ("jit(update)/towers_extra/add:", None),
    ("jit(update)/reduce_sum:", None),
    ("", None),
])
def test_scope_of_a_path(path, scope):
    assert layers.scope_of(path) == scope


# -- a hand-written .xplane.pb ------------------------------------------------
def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        if not n:
            return out + bytes([b])
        out += bytes([b | 0x80])


def _msg(field, payload):
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _int(field, n):
    return _varint(field << 3) + _varint(n)


def _entry(field, key, value):
    return _msg(field, _int(1, key) + _msg(2, value))


def test_op_paths_from_the_xplane_wire_format(tmp_path):
    stat_meta = (_entry(5, 7, _int(1, 7) + _msg(2, b"tf_op"))
                 + _entry(5, 8, _int(1, 8) + _msg(2, b"flops"))
                 + _entry(5, 9, _int(1, 9) + _msg(2, b"jit(f)/optimizer/sub:")))
    double = _varint(2 << 3 | 1) + struct.pack("<d", 1.5)
    events = (
        _entry(4, 1, _int(1, 1) + _msg(2, b"%fusion.1 = f32[8]{0} fusion()")
               + _msg(5, _int(1, 8) + double)
               + _msg(5, _int(1, 7) + _msg(5, b"jit(f)/towers/dot_general:")))
        + _entry(4, 2, _int(1, 2) + _msg(2, b"%fusion.2 = f32[8]{0} fusion()")
                 + _msg(5, _int(1, 7) + _int(7, 9)))
        + _entry(4, 3, _int(1, 3) + _msg(2, b"%copy.3 = f32[8]{0} copy()")))
    line = _msg(3, _int(1, 1) + _msg(2, b"XLA Ops"))
    device = _msg(1, _int(1, 1) + _msg(2, b"/device:TPU:0") + line + events + stat_meta)
    host_meta = (_entry(4, 1, _int(1, 1) + _msg(2, b"repro.server.search"))
                 + _entry(4, 2, _int(1, 2) + _msg(2, b"train"))
                 + _entry(4, 3, _int(1, 3) + _msg(2, b"bench.window")))
    host_line = _msg(3, _int(1, 1) + _msg(2, b"python3") + _int(3, 1000)
                     + _msg(4, _int(1, 2) + _int(2, 5_000) + _int(3, 900_000))
                     + _msg(4, _int(1, 1) + _int(2, 250_000) + _int(3, 100_000))
                     + _msg(4, _int(1, 3) + _int(2, 0) + _int(3, 2_000_000)))
    host = _msg(1, _int(1, 2) + _msg(2, b"/host:CPU") + host_line + host_meta + stat_meta)
    other = _msg(1, _int(1, 3) + _msg(2, b"Task Environment") + host_line + host_meta)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(host + other + device)
    assert layers.scan(str(path)) == {
        "op_paths": {"/device:TPU:0": {
            "%fusion.1 = f32[8]{0} fusion()": "jit(f)/towers/dot_general:",
            "%fusion.2 = f32[8]{0} fusion()": "jit(f)/optimizer/sub:"}},
        # start: the line's timestamp (ns) plus the event's offset (ps)
        "program_spans": [[1250.0, 100.0, "repro.server.search"]],
        "steps": [[1005.0, 900.0, "train"]]}


def test_window_waits_keep_batches_whose_search_began_before_the_cut():
    waits, began = [(0.1, 0.2), (0.3,), (0.4, 0.5, 0.6)], [1.0, 2.0, 3.0]
    assert layers.window_waits(waits, began, 2.5).tolist() == [0.1, 0.2, 0.3]
    assert layers.queue_wait_ms({"queue_wait_s": layers.window_waits(waits, began, 0.5)}) is None
    # rings that have dropped their oldest batches line up from the newest
    assert layers.window_waits(waits[1:], [0.5] + began, 2.5).tolist() == [0.3]
    assert layers.window_waits(waits, began[1:], 2.5).tolist() == [0.3]


# -- the program's spans in a CPU profiler trace -------------------------------
def test_cpu_trace_holds_the_trainer_and_server_spans(tmp_path):
    import jax
    import numpy as np

    from repro.runtime.server import BatchingServer
    from repro.runtime.trainer import Trainer, TrainerConfig

    step = jax.jit(lambda s, b: (s + b, {"loss": (s * b).sum()}))
    trainer = Trainer(TrainerConfig(total_steps=3, log_every=100), step,
                      lambda i: np.full((4,), float(i), np.float32))
    srv = BatchingServer(lambda x: (x[:, :1], x[:, :1]), max_batch=2, max_wait_s=0.001)
    trainer.run(np.zeros((4,), np.float32))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        trainer.run(np.zeros((4,), np.float32))
        srv.start()
        try:
            for i in range(4):
                srv.query(np.full((3,), float(i)), timeout=10)
        finally:
            srv.stop()
    jax.profiler.stop_trace()
    tr = layers.read(str(tmp_path))
    names = [n for _, _, n in tr["program_spans"]]
    for span in ("repro.train.next_batch", "repro.train.update", "repro.train.fetch"):
        assert names.count(span) == 3, names
    for span in ("repro.server.collect", "repro.server.search", "repro.server.deliver"):
        assert span in names
    assert len(tr["steps"]) == 3
    assert len(srv.queue_wait_s) == 4 and len(srv.batch_sizes) == 4
    # the one pass over the file reads the events as ProfileData does
    from jax.profiler import ProfileData

    want = sorted([e.start_ns, e.duration_ns, e.name]
                  for p in ProfileData.from_file(layers.newest_xplane(str(tmp_path))).planes
                  if p.name.startswith("/host:") for line in p.lines for e in line.events
                  if e.name.startswith(layers.PREFIX) or e.name == layers.STEP)
    assert sorted(tr["program_spans"] + tr["steps"]) == want


def test_tiny_serving_run_reads_its_queue_waits(monkeypatch, capsys):
    """The ``layers`` line comes first, then the result line, from the one
    report of a traced run, as ``bench/layers.py`` prints them."""
    import repro.retrieval

    import bench.layers as tool
    from bench.harness import peaks, session

    monkeypatch.setattr(repro.retrieval, "make_server", repro.retrieval.make_server)
    monkeypatch.setattr(session, "report", session.report)
    # the tiny run lends the CPU a TPU's peaks: keep that to this test
    monkeypatch.setattr(peaks, "PEAKS", dict(peaks.PEAKS))
    seen = tool.measure_before_report()
    r, res = bench_tiny.run(bench_tiny.TinyCell("serve"), trace=True)
    session.report(r, res)
    first, last = capsys.readouterr().out.strip().splitlines()[-2:]
    out, result = json.loads(first)["layers"], json.loads(last)
    assert res["correct"] and result["correct"], res["checks"]
    assert len(seen["began"]) == len(seen["waits"]) > 0
    assert out["metrics"]["serve.queue_wait_ms"] > 0
    # the CPU trace has no device plane, so no scope reads
    assert out["metrics"]["serve.block_topk_device_ms"] is None and out["scope_s"] == {}
    assert out["steps"]["count"] == 0 and out["xplane_bytes"] > 0
    assert "serve.batch_occupancy" in result["metrics"]
    shutil.rmtree(r.trace_dir, ignore_errors=True)


def test_layers_tool_exits_nonzero_without_a_tpu():
    root = Path(__file__).resolve().parents[2]
    p = subprocess.run([sys.executable, "bench/layers.py", "--workload", "serve-msmarco",
                        "--seed", "1", "--seconds", "1"], cwd=root, capture_output=True,
                       text=True, timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2 and p.stdout.strip() == "", p.stderr[-2000:]
    assert "needs 1 TPU chip" in p.stderr
