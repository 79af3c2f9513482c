"""The context one run hands its driver, and the result line it prints.

The driver builds its cell, calls ``open_window()`` when set-up is done,
wraps the program's calls in ``span(...)``, and calls ``close_window()``
when the measured work has ended. With a trace, the profiler records from
the window's start for the workload's ``trace_seconds`` (a driver calls
``maybe_stop_trace`` between units of work); the span ``bench.window``
marks that stretch in the trace.
"""

from __future__ import annotations

import contextlib
import json
import math
import shutil
import sys
import time

from bench.harness import peaks as peaks_mod
from bench.harness import trace as trace_mod
from bench.harness.clock import CompileClock
from bench.harness.spec import ROOT


class Run:
    def __init__(self, cell, seed: int, seconds: float, trace: bool, devices, t_process: float):
        import jax

        self.cell = cell
        self.config = cell.config
        self.workload = cell.workload
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices
        self.kind = devices[0].device_kind
        self.peaks = peaks_mod.peaks(self.kind)
        self.t_process = t_process
        self.clock = CompileClock(jax)
        self.t0 = self.t_end = None
        self.in_window = False
        self.setup = None
        self._window_span = None
        self._tracing = False
        self.t_trace_end = None
        self.trace_dir = ROOT / ".bench_trace" / f"{cell.name}-{seed}"

    # -- spans and the window ---------------------------------------------
    @property
    def tracing(self) -> bool:
        return self._tracing

    def span(self, name: str):
        if not self._tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def open_window(self):
        import jax

        if self.trace:
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.trace_dir), profiler_options=opts)
            self._tracing = True
            self._window_span = jax.profiler.TraceAnnotation(trace_mod.WINDOW)
            self._window_span.__enter__()
        self.setup = self.clock.mark()
        self.t0 = time.perf_counter()
        self.in_window = True

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def maybe_stop_trace(self, now=None):
        if self._tracing and (now or time.perf_counter()) - self.t0 >= self.workload["trace_seconds"]:
            self.stop_trace()

    def stop_trace(self):
        import jax

        if not self._tracing:
            return
        self.t_trace_end = time.perf_counter()
        self._window_span.__exit__(None, None, None)
        self._tracing = False
        jax.profiler.stop_trace()

    def close_window(self, at=None):
        self.t_end = at if at is not None else time.perf_counter()
        self.in_window = False
        self.window_compiles = self.clock.since(self.setup)
        self.stop_trace()

    def window_s(self) -> float:
        return self.t_end - self.t0

    def host_window_end(self) -> float:
        """Where host-clock readings of a traced run stop: stopping the
        profiler holds the host for seconds, so what follows it is not
        read."""
        return self.t_trace_end if self.t_trace_end is not None else self.t_end

    def setup_s(self) -> float:
        return self.t0 - self.t_process

    def note(self, *parts):
        """A line of diagnostics on standard error (before the checks)."""
        print("bench:", *parts, file=sys.stderr, flush=True)

    # -- device facts -------------------------------------------------------
    def least_time(self, flops: float, bytes_: float) -> float:
        return peaks_mod.least_time_s(flops, bytes_, self.kind)

    def memory_peak_bytes(self) -> int:
        return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in self.devices)


def finite(v: float) -> float:
    return float(v) if math.isfinite(v) else 1.7976931348623157e308


def report(run: Run, result: dict):
    """Print the result line (and the checks at the end of stderr)."""
    import jax

    cell = run.cell
    layer = dict(result["layer"])
    device = {
        "platform": run.devices[0].platform,
        "kind": run.kind,
        "device_kind": run.kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": layer["memory_peak_bytes"],
    }
    out = {"correct": bool(result["correct"]), "attempted": int(result["attempted"]),
           "failed": int(result["failed"])}
    e2e = dict(result["end_to_end"], setup_s=run.setup_s())
    if run.trace:
        tr = trace_mod.read_xplane(str(run.trace_dir))
        red = trace_mod.reduce(tr)
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        layer.update(trace=red, peaks=run.peaks, device_kind=run.kind)
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(layer)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        out.update(metrics=metrics, device=device, breakdown=red["breakdown"])
    else:
        out.update(metrics={m["name"]: {"value": float(e2e[m["name"]]), "unit": m["unit"]}
                            for m in cell.end_to_end},
                   device=device)
    # a number that is not finite (a request never answered) is printed as
    # the largest double, so the line stays JSON
    out["checks"] = {k: {"value": finite(c["value"]), "limit": c["limit"]}
                     for k, c in result["checks"].items()}
    compiles = getattr(run, "window_compiles", {})
    print(f"bench: setup {run.setup_s():.3f} s; window {run.window_s():.3f} s; "
          f"compiles in window {compiles.get('compiles')}; set-up compile "
          f"{run.setup[0]:.3f} s with {run.setup[2]} cache hits", file=sys.stderr)
    for name, c in result["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
