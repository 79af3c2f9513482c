#!/usr/bin/env python3
"""The one-time knee sweep of a serving cell, on the chip, in one process.

    python3 bench/sweep.py --workload <cell> --rates 100,150,200 --seconds 10

Builds the cell once as ``bench/run.py`` does, then offers open-loop
Poisson load at each rate for ``--seconds`` and prints one line per rate:
requests delivered, median and 95th-percentile latency, the sustained rate,
and whether the backlog grew (requests due in the last quarter of the
window waited more than twice as long, at the median, as those due in the
first). The knee is the highest rate with every request delivered and no
growing backlog; the cell's rate is set to four fifths of it.
"""

import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


class Clock:
    """The part of the run context that ``open_loop`` uses."""

    def __init__(self, seconds):
        self.seconds = seconds
        self.t0 = None

    def maybe_stop_trace(self, now=None):
        pass


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.harness.spec import Cell

    cell = Cell(args.workload)
    import jax
    import numpy as np

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"sweep: cell {cell.name} needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    # the reference's program is over JAX's default 192 MiB entry limit
    jax.config.update("jax_compilation_cache_max_size", 4 << 30)
    from bench.drivers import serve
    from bench.harness import traffic
    from bench.harness.session import Run

    r = Run(cell, args.seed, args.seconds, False, devices[: cell.chips], time.perf_counter())
    wl = cell.workload
    retriever, server, _, _ = serve.build(r)
    tokens = traffic.query_tokens(4096, wl["q_len"], cell.config["model"]["vocab_size"], args.seed)
    for _ in range(wl["warmup_batches"]):
        retriever.search(tokens[: wl["max_batch"]])
    server.start()
    rows = []
    for rate in (float(x) for x in args.rates.split(",")):
        offsets = traffic.poisson_offsets(rate, args.seconds, args.seed)
        clock = Clock(args.seconds)
        clock.t0 = time.perf_counter() + 0.5
        n0 = len(server.batch_sizes)
        sent, done, _ = serve.open_loop(clock, server, tokens[np.arange(len(offsets)) % len(tokens)],
                                        offsets, 30.0)
        due = clock.t0 + offsets
        ok = np.isfinite(done)
        lat = (done - due)[ok]
        q = len(offsets) // 4
        early, late = np.median((done - due)[:q]), np.median((done - due)[-q:])
        row = {"rate_qps": rate, "requests": len(offsets), "delivered": int(ok.sum()),
               "p50_ms": float(np.median(lat) * 1e3), "p95_ms": float(np.percentile(lat, 95) * 1e3),
               "sustained_qps": float(ok.sum() / (np.nanmax(done) - clock.t0)),
               "early_median_ms": float(early * 1e3), "late_median_ms": float(late * 1e3),
               "growing": bool(late > 2 * early or not ok.all()),
               "mean_batch": float(np.mean(server.batch_sizes[n0:]))}
        rows.append(row)
        print(json.dumps(row), flush=True)
    server.stop()
    good = [x["rate_qps"] for x in rows if not x["growing"]]
    knee = max(good) if good else None
    print(json.dumps({"knee_qps": knee, "rate_qps": 0.8 * knee if knee else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
