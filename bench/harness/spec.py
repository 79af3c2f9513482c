"""Finds every piece of a cell by its name: the cell in ``BENCHMARK.json``,
its traffic in ``bench/workloads/<cell>.json``, its configuration in the
file the configuration entry names, its driver in
``bench/drivers/<driver>.py`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``. Adding a cell, a configuration or a metric
adds files; nothing here changes."""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One workload of BENCHMARK.json with everything it names."""

    def __init__(self, name: str, root: Path = ROOT):
        bench = load_benchmark(root)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        with open(root / self.config_entry["file"]) as f:
            self.config = json.load(f)
        with open(BENCH / "workloads" / f"{self.entry['traffic']}.json") as f:
            self.workload = json.load(f)
        self.driver_path = BENCH / "drivers" / f"{self.config['driver']}.py"
        self.end_to_end = [m for m in bench["end_to_end"] if self._reports(m)]
        moved = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if (name in m["workloads"] if "workloads" in m
                              else m["moves"] in moved)]

    def _reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def driver(self):
        return load_module(self.driver_path)

    def reader(self, metric_name: str):
        return load_module(BENCH / "metrics" / f"{metric_name}.py").read
