#!/usr/bin/env python3
"""One run of one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic, driver and per-layer readers are all
found by name from ``BENCHMARK.json`` (bench/harness/spec.py). Set-up
(imports, weights and data made from the seed, compilation) runs until the
driver opens the measured window; ``setup_s`` is the time from process
start to that point. With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` a profiler trace of the first
``trace_seconds`` of the window gives its per-layer metrics instead.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
a trace), then ``checks``: every number compared with its limit, which
also end standard error. Without a TPU, or with fewer chips than the cell
asks for, the run prints no result and exits 2.
"""

import os
import sys
import time
from pathlib import Path


def process_start() -> float:
    """When this process started, on the ``time.perf_counter`` clock."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        started = ticks / os.sysconf("SC_CLK_TCK")
        return time.perf_counter() - (time.clock_gettime(time.CLOCK_BOOTTIME) - started)
    except (OSError, ValueError, IndexError):
        return time.perf_counter()


T_PROCESS = process_start()
ROOT = Path(__file__).resolve().parents[1]


def parse(argv):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the compile cache lives at one fixed path inside the checkout, which
    # the program's own placement (launch/compile_cache.py) then takes too
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # libtpu would otherwise log to the fixed path /tmp/tpu_logs
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.harness.spec import Cell

    cell = Cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: cell {cell.name} needs {cell.chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    # the reference's program is over JAX's default 192 MiB entry limit
    jax.config.update("jax_compilation_cache_max_size", 4 << 30)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from bench.harness.session import Run, report

    run = Run(cell, args.seed, args.seconds, bool(args.trace), devices[: cell.chips], T_PROCESS)
    result = cell.driver().run(run)
    report(run, result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
