"""From a profiler trace to numbers: device busy time, the time of chosen
operations, and idle gaps attributed to what the host was doing.

``read_xplane`` turns the newest ``.xplane.pb`` under a directory into a
plain dict (kept small enough to commit for a test):

    {"devices": [{"name": "/device:TPU:0",
                  "modules": [[start_ns, dur_ns, program], ...],
                  "ops": [[start_ns, dur_ns, hlo_text], ...]}],
     "host": [[start_ns, dur_ns, span_name], ...]}

``modules`` are the executions of whole compiled programs ("XLA Modules"),
``ops`` the events of a device's "XLA Ops" line. They nest: a while
loop's event spans the events of its body, so busy time is the union of
intervals and an operation's own time is taken from leaves only. ``host`` keeps
the benchmark's own spans (names starting ``bench.``); the span
``bench.window`` marks the traced window.
"""

from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"


def read_xplane(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = {"devices": [], "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key] = [[e.start_ns, e.duration_ns, e.name] for e in line.events]
            out["devices"].append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        out["host"].append([e.start_ns, e.duration_ns, e.name])
    out["devices"].sort(key=lambda d: int(d["name"].rsplit(":", 1)[1]))
    return out


def window(tr: dict):
    spans = [(s, s + d) for s, d, n in tr["host"] if n == WINDOW]
    if not spans:
        raise ValueError("trace has no bench.window span")
    return spans[0]


def union_length(intervals) -> float:
    total, end = 0.0, None
    start = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            if end is not None:
                total += end - start
            start, end = s, e
        else:
            end = max(end, e)
    if end is not None:
        total += end - start
    return total


def clip(events, lo, hi):
    out = []
    for s, d, n in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b, n))
    return out


def leaves(events):
    """Events that contain no other event (the operations that do the
    work, not the loops around them)."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    has_child = [False] * len(evs)
    stack = []
    for i, (s, e, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][1]:
            has_child[stack[-1]] = True
        stack.append(i)
    return [ev for ev, c in zip(evs, has_child) if not c]


def label(hlo: str) -> str:
    """A readable name: the instruction's kind, its output shape without
    layouts, and its name without the instance number."""
    m = re.match(r"%?([\w.\-]+) = (.*)", hlo)
    if not m:
        return hlo[:120]
    name, rest = m.groups()
    rest = re.sub(r"\{[^{}]*\}", "", rest)
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = rest[: i + 1], rest[i + 1:]
    else:
        shape, _, rest = rest.partition(" ")
    kind = re.match(r"\s*([\w\-]+)", rest)
    kind = kind.group(1) if kind else "?"
    return f"{kind} {shape} ({re.sub(r'[.][0-9]+$', '', name)})"[:160]


def reduce(tr: dict) -> dict:
    """Per-device busy time and operation times inside the traced window,
    averaged over the devices, and idle time by host span."""
    lo, hi = window(tr)
    spans = [(s, s + d, n) for s, d, n in tr["host"] if n != WINDOW]
    n_dev = max(len(tr["devices"]), 1)
    busy, op_s, idle = 0.0, {}, {}
    leaf_ops = []
    for dev in tr["devices"]:
        ops = clip(dev["ops"], lo, hi)
        busy += union_length((s, e) for s, e, _ in ops)
        lv = leaves(ops)
        leaf_ops.append(lv)
        for s, e, n in lv:
            key = label(n)
            op_s[key] = op_s.get(key, 0.0) + (e - s) / n_dev
        for a, b in gaps(ops, lo, hi):
            name = cover(spans, a, b)
            idle[name] = idle.get(name, 0.0) + (b - a) / n_dev
    per_module = {}
    for dev in tr["devices"]:
        for s, d, n in dev.get("modules", []):
            if s >= lo and s + d <= hi:
                m = per_module.setdefault(re.sub(r"\(.*", "", n), [0, 0.0])
                m[0] += 1 / n_dev
                m[1] += d * 1e-9 / n_dev
    main = max(per_module.items(), key=lambda kv: kv[1][1], default=(None, [0, 0.0]))
    top = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    top_idle = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy / n_dev * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "n_devices": n_dev,
        "leaf_ops": leaf_ops,
        "host_spans": spans,
        "program": {"name": main[0], "calls": main[1][0], "seconds": main[1][1]},
        "breakdown": {"device_ops": [[k, v * 1e-9] for k, v in top],
                      "idle_gaps": [[k, v * 1e-9] for k, v in top_idle]},
    }


def gaps(ops, lo, hi):
    out, t = [], lo
    for s, e in sorted((s, e) for s, e, _ in ops):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def cover(spans, a, b) -> str:
    """The host span that overlaps the interval most ("host.other": none)."""
    best, name = 0.0, "host.other"
    for s, e, n in spans:
        o = min(b, e) - max(a, s)
        if o > best:
            best, name = o, n
    return name


def op_seconds(red: dict, match) -> tuple:
    """(seconds per device, calls per device) of the leaf operations whose
    HLO text ``match`` accepts."""
    n_dev = red["n_devices"]
    t = c = 0
    for lv in red["leaf_ops"]:
        for s, e, n in lv:
            if match(n):
                t += e - s
                c += 1
    return t / n_dev * 1e-9, c / n_dev
