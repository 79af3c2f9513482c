"""BENCHMARK.json against the files it names, and the shape of its
entries; bench/run.py on a machine without a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench_tiny import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = [c["name"] for c in BENCH["configs"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and re.fullmatch(r"[A-Za-z0-9_./\-]{1,200}", p)


@pytest.mark.parametrize("name", CONFIGS)
def test_config_has_its_file(name):
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith("bench/")
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == name and cfg["reduced"] == entry["reduced"]
    assert (ROOT / "bench" / "drivers" / f"{cfg['driver']}.py").is_file()


@pytest.mark.parametrize("name", CELLS)
def test_cell_has_its_files(name):
    w = next(w for w in BENCH["workloads"] if w["name"] == name)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["config"] in CONFIGS and w["chips"] in (1, 4) and len(w["why"]) <= 200
    wl = json.loads((ROOT / "bench" / "workloads" / f"{w['traffic']}.json").read_text())
    # a limit of 0 is an exact comparison (a count that must stay nought)
    assert wl["limits"] and all(v >= 0 for v in wl["limits"].values())


@pytest.mark.parametrize("name", CELLS)
def test_cell_reports_setup_another_metric_and_a_layer(name):
    e2e = [m["name"] for m in BENCH["end_to_end"] if name in m.get("workloads", [name])]
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = [m for m in BENCH["per_layer"] if name in m.get("workloads", [])]
    assert layers and all(m["moves"] in e2e for m in layers)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metric_has_its_reader(metric):
    import bench_tiny  # noqa: F401
    from bench.harness import spec

    read = spec.load_module(ROOT / "bench" / "metrics" / f"{metric}.py").read
    assert read({}) is None


@pytest.mark.parametrize("m", METRICS, ids=[m["name"] for m in METRICS])
def test_metric_shape(m):
    keys = {"name", "unit", "better", "source"}
    if m in BENCH["end_to_end"]:
        keys |= {"bound"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    else:
        keys |= {"layer", "moves"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    assert set(m) - {"workloads"} == keys
    assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_names_are_unique_and_valid():
    names = CELLS + CONFIGS + [m["name"] for m in METRICS]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2)


def test_layers_name_one_layer_per_name():
    by_layer = {}
    for m in BENCH["per_layer"]:
        by_layer.setdefault(m["layer"], set()).add(m["name"].split(".")[0])
    assert all(len(v) <= 2 for v in by_layer.values())
    assert all(0 < len(k) <= 200 and "\n" not in k for k in by_layer)


def _run_cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed", "2147483659",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_exits_nonzero_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""
