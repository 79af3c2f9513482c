#!/usr/bin/env python3
"""One traced run of a cell, read for the program's layer map as well.

    python3 bench/layers.py --workload <cell> --seed <n> --seconds <s> [--keep DIR]

Runs ``bench/run.py --trace 1`` in this process and prints its result line
last, as that does. Before it, one line ``layers {...}`` holds what the
program's own instrumentation shows in the same trace
(bench/harness/layers.py): the device seconds of each named scope, idle
time by the program's ``repro.*`` host spans, the host seconds of each
training step, the server's queue waits, and each number of
``layers.METRICS``; also the window's end-to-end numbers and the size of
the ``.xplane.pb``. With ``--keep`` the trace file is copied into DIR
first. A program without the instrumentation gives nulls where it has no
scope, span or counter.
"""

import collections
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as bench_run  # noqa: E402  (takes the process start time)


def watch_server(seen: dict):
    """Wrap ``make_server`` to keep each new server's counters, and the
    times at which its searches began, without keeping the server."""
    import repro.retrieval as retrieval

    make = retrieval.make_server

    def make_server(*a, **kw):
        server = make(*a, **kw)
        began = collections.deque(maxlen=getattr(server, "RECORD", None))
        serve_fn = server.serve_fn

        def timed(payloads):
            began.append(time.perf_counter())
            return serve_fn(payloads)

        server.serve_fn = timed
        seen.update(waits=getattr(server, "queue_wait_s", None), began=began)
        return server

    retrieval.make_server = make_server


def measure_before_report(keep=None) -> dict:
    """Have ``session.report`` print the ``layers`` line first, while the
    run's trace is still on disk; returns what the servers were seen to
    record."""
    from bench.harness import session

    seen, report = {}, session.report
    watch_server(seen)

    def report_with_layers(run, result):
        print(json.dumps({"layers": measure(run, result, seen, keep)}, default=float), flush=True)
        report(run, result)

    session.report = report_with_layers
    return seen


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--keep", default=None)
    args, rest = ap.parse_known_args(argv)
    measure_before_report(args.keep)
    return bench_run.main(rest + ["--trace", "1"])


def measure(run, result, seen: dict, keep=None) -> dict:
    from bench.harness import layers

    path = layers.newest_xplane(str(run.trace_dir))
    if keep:
        os.makedirs(keep, exist_ok=True)
        shutil.copy(path, os.path.join(keep, f"{run.cell.name}-{run.seed}.xplane.pb"))
    red = layers.reduce(layers.read(str(run.trace_dir)))
    d = dict(result["layer"], trace=red, peaks=run.peaks, device_kind=run.kind)
    if seen.get("waits") is not None:
        d["queue_wait_s"] = layers.window_waits(seen["waits"], seen["began"], run.host_window_end())
    steps = sorted(red["step_s"])
    return {
        "metrics": {name: read(d) for name, read in layers.METRICS.items()},
        "scope_s": red["scope_s"],
        "program": red["program"],
        "busy_s": red["busy_s"],
        "window_s": red["window_s"],
        "steps": {"count": len(steps), "median_s": steps[len(steps) // 2] if steps else None,
                  "max_s": steps[-1] if steps else None},
        "idle_gaps_program": red["breakdown"]["idle_gaps_program"],
        "idle_gaps": red["breakdown"]["idle_gaps"],
        "end_to_end": result["end_to_end"],
        "window_host_s": run.window_s(),
        "xplane_bytes": os.path.getsize(path),
    }


if __name__ == "__main__":
    sys.exit(main())
