"""A benchmark cell at a size the CPU holds (the bert-tiny tower), for the
tests: the same drivers, comparisons and readers as the chip cells, with
the harness's look for a chip skipped."""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# the cells' configuration with the bert-tiny tower's sizes
TINY = dict(json.loads((ROOT / "bench/configs/dpr-bert-base-contaccum.json").read_text())["model"],
            vocab_size=1000, hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
            intermediate_size=128, max_position_embeddings=64)

TRAIN = dict(total_batch=16, local_batch=8, q_len=8, p_len=16, n_hard=1, steps=100, lr=1e-3,
             corpus_size=64, checked_steps=3, trace_seconds=1, bank=32, loss_impl="dense")
SERVE = dict(shards=1, q_len=8, top_k=10, max_batch=8, search_impl="dense", drain_s=10,
             warmup_batches=2, check_requests=16, trace_seconds=1, index_rows=5000, rate_qps=40.0)


def limits(cell: str) -> dict:
    """The limits of a cell of BENCHMARK.json, as its workload file sets them."""
    with open(ROOT / "bench" / "workloads" / f"{cell}.json") as f:
        return json.load(f)["limits"]


class TinyCell:
    """Looks like ``bench.harness.spec.Cell`` to a driver and to ``report``."""

    def __init__(self, kind: str, precision: str = "fp32", chips: int = 1, **workload):
        from bench.harness import spec

        self.name, self.chips = f"{kind}-tiny", chips
        program = {"arch": "bert-tiny", "precision": precision}
        if kind == "train":
            program["method"] = "contaccum"
            wl = dict(TRAIN, limits=limits("train-paper"))
        else:
            program["index_dtype"] = "bfloat16"
            wl = dict(SERVE, limits=limits("serve-msmarco"))
        wl.update(workload)
        self.config = {"driver": kind, "model": TINY, "program": program}
        self.workload = wl
        bench = spec.load_benchmark()
        self.end_to_end = [m for m in bench["end_to_end"]
                           if "workloads" not in m or any(w.startswith(kind) for w in m["workloads"])]
        self.per_layer = [m for m in bench["per_layer"] if m["name"].startswith(kind)]
        self._spec = spec

    def driver(self):
        return self._spec.load_module(self._spec.BENCH / "drivers" / f"{self.config['driver']}.py")

    def reader(self, name):
        return self._spec.load_module(self._spec.BENCH / "metrics" / f"{name}.py").read


def run(cell: TinyCell, seed: int = 2**31 + 11, seconds: float = 1.0, trace: bool = False):
    """Drive one run on the CPU; returns (run context, driver result)."""
    import jax

    from bench.harness import peaks, session

    kind = jax.devices()[0].device_kind
    peaks.PEAKS.setdefault(kind, peaks.PEAKS["TPU v5 lite"])
    r = session.Run(cell, seed, seconds, trace, jax.devices()[: cell.chips], time.perf_counter())
    return r, cell.driver().run(r)
