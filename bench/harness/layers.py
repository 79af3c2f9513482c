"""The program's layer map in a profiler trace: the device time of each
named scope of the compiled programs, idle time by the program's own host
spans, and the numbers that read them.

The program names its layers with ``jax.named_scope`` (device work:
``towers``, ``loss``, ``grad_accum``, ``bank_push``, ``optimizer`` in the
update; ``block_topk``, ``shard_merge`` in the search) and with host spans
named ``repro.<layer>.<what>`` (``repro.train.*`` in ``Trainer.run``,
``repro.server.*`` in ``BatchingServer``), and each of ``Trainer.run``'s
steps with the step marker ``StepTraceAnnotation("train")``, which is also
what TensorBoard's profile plugin draws its step-time graph from. A TPU
trace keeps an op's scope path, its ``op_name``, as the ``tf_op`` stat of
the op's event metadata, which ``jax.profiler.ProfileData`` does not show;
``scan`` reads it, the spans and the steps from the ``.xplane.pb`` itself.

``read(trace_dir)`` is ``trace.read_xplane`` with these keys added:

    "program_spans": [[start_ns, dur_ns, name], ...]   host spans repro.*
    "steps": [[start_ns, dur_ns, "train"], ...]         the step markers
    each device's "op_paths": {event name: op_name path}

``reduce(tr)`` is ``trace.reduce`` with, added:

    "scope_s": {scope: device seconds of its leaf ops in the window,
                averaged over the devices}
    "program": {..., "scope_s": the same, inside the whole executions of
                the program that ``program`` names}
    "step_s": [host seconds of each step wholly inside the window]
    "breakdown": {..., "idle_gaps_program": [[span, seconds], ...]}

An op belongs to a scope when a component of its path is the scope's name,
or ends in it wrapped by differentiation: ``jvp(loss)``,
``transpose(jvp(towers))``. ``METRICS`` maps each number read from the
map to its reader, which takes a run's layer data (the reduced trace under
``trace``) and returns None where the trace has no op of its scopes.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

import numpy as np

from bench.harness import readers
from bench.harness import trace as trace_mod

SCOPES = ("towers", "loss", "grad_accum", "bank_push", "optimizer", "block_topk", "shard_merge")
PREFIX = "repro."
# the step marker of Trainer.run (a StepTraceAnnotation)
STEP = "train"
_SCOPE = {s: re.compile(r"(?:\w+\()*%s\)*" % s) for s in SCOPES}


def scope_of(path: str):
    """The innermost scope a path names, or None."""
    for c in reversed(re.split(r"[/;]", path)):
        for s, pat in _SCOPE.items():
            if pat.fullmatch(c):
                return s
    return None


# -- the xplane wire format, as far as ``scan`` needs it ------------------
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of a protobuf message: ints for varints,
    byte slices for length-delimited fields; fixed-width fields skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, v


def _host_events(lines, wanted: dict) -> list:
    """[[start_ns, dur_ns, name], ...] of the events of a host plane's
    lines whose metadata id ``wanted`` names: XLine.timestamp_ns (3),
    .events (4); XEvent.metadata_id (1), .offset_ps (2), .duration_ps (3).
    Any other event is skipped after its first field."""
    out = []
    for line in lines:
        t0, events = 0, []
        for f, v in _fields(line):
            if f == 3:
                t0 = v
            elif f == 4:
                events.append(v)
        for ev in events:
            it = _fields(ev)
            first = next(it, (None, None))
            if first[0] != 1 or first[1] not in wanted:
                continue
            st = dict(it)
            out.append([t0 + st.get(2, 0) / 1e3, st.get(3, 0) / 1e3, wanted[first[1]]])
    return out


def scan(path: str) -> dict:
    """What ``jax.profiler.ProfileData`` does not give, in one pass over an
    ``.xplane.pb``: XSpace.planes (1); XPlane.name (2), .lines (3),
    .event_metadata (4) and .stat_metadata (5), maps of key (1) to value
    (2); XEventMetadata.name (2), .stats (5); XStat.metadata_id (1),
    .str_value (5), .ref_value (7); XStatMetadata.name (2).

        {"op_paths": {device plane name: {op event name: op_name path}},
         "program_spans": [[start_ns, dur_ns, name], ...]  host, repro.*
         "steps": [[start_ns, dur_ns, name], ...]}         host, STEP
    """
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {"op_paths": {}, "program_spans": [], "steps": []}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, lines, events, stat_names = "", [], [], {}
        for f2, v in _fields(plane):
            if f2 == 2:
                name = bytes(v).decode()
                if not name.startswith(("/device:TPU:", "/host:")):
                    break
            elif f2 == 3:
                lines.append(v)
            elif f2 == 4:
                events.append(dict(_fields(v)))
            elif f2 == 5:
                entry = dict(_fields(v))
                stat_names[entry.get(1)] = bytes(dict(_fields(entry.get(2, b""))).get(2, b"")).decode()
        if name.startswith("/host:"):
            names = {e.get(1): bytes(dict(_fields(e.get(2, b""))).get(2, b"")).decode(errors="replace")
                     for e in events}
            found = _host_events(lines, {k: n for k, n in names.items()
                                         if n.startswith(PREFIX) or n == STEP})
            out["program_spans"] += [e for e in found if e[2] != STEP]
            out["steps"] += [e for e in found if e[2] == STEP]
        elif name.startswith("/device:TPU:"):
            out["op_paths"][name] = _op_paths(events, stat_names)
    out["program_spans"].sort()
    out["steps"].sort()
    return out


def _op_paths(events, stat_names) -> dict:
    """{op event name: the ``tf_op`` stat of its metadata} of a device plane."""
    tf_op = [k for k, n in stat_names.items() if n == "tf_op"]
    paths = {}
    for entry in events:
        ev_name, op = "", None
        for f3, v in _fields(entry.get(2, b"")):
            if f3 == 2:
                ev_name = bytes(v).decode(errors="replace")
            elif f3 == 5 and tf_op:
                st = dict(_fields(v))
                if st.get(1) == tf_op[0]:
                    op = bytes(st[5]).decode() if 5 in st else stat_names.get(st.get(7), "")
        if op:
            paths[ev_name] = op
    return paths


def newest_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read(trace_dir: str) -> dict:
    """``trace.read_xplane`` and ``scan`` of the same file."""
    tr = trace_mod.read_xplane(trace_dir)
    found = scan(newest_xplane(trace_dir))
    tr["program_spans"], tr["steps"] = found["program_spans"], found["steps"]
    for dev in tr["devices"]:
        dev["op_paths"] = found["op_paths"].get(dev["name"], {})
    return tr


def reduce(tr: dict) -> dict:
    red = trace_mod.reduce(tr)
    lo, hi = trace_mod.window(tr)
    n_dev = red["n_devices"]
    main = red["program"]["name"]
    scope_s, in_program = {}, {}
    spans = [(s, s + d, n) for s, d, n in tr.get("program_spans", [])]
    idle = {}
    for dev, leaves in zip(tr["devices"], red["leaf_ops"]):
        paths = dev.get("op_paths", {})
        runs = sorted((s, s + d) for s, d, n in dev.get("modules", [])
                      if re.sub(r"\(.*", "", n) == main and s >= lo and s + d <= hi)
        starts = [a for a, _ in runs]
        for s, e, n in leaves:
            scope = scope_of(paths.get(n, ""))
            if scope is None:
                continue
            scope_s[scope] = scope_s.get(scope, 0.0) + (e - s) / n_dev
            j = bisect.bisect_right(starts, s) - 1
            if j >= 0 and e <= runs[j][1]:
                in_program[scope] = in_program.get(scope, 0.0) + (e - s) / n_dev
        for a, b in trace_mod.gaps(trace_mod.clip(dev["ops"], lo, hi), lo, hi):
            name = trace_mod.cover(spans, a, b)
            idle[name] = idle.get(name, 0.0) + (b - a) / n_dev
    red["scope_s"] = {k: v * 1e-9 for k, v in scope_s.items()}
    red["program"]["scope_s"] = {k: v * 1e-9 for k, v in in_program.items()}
    top = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    red["breakdown"]["idle_gaps_program"] = [[k, v * 1e-9] for k, v in top]
    red["step_s"] = [d * 1e-9 for s, d, _ in tr.get("steps", []) if s >= lo and s + d <= hi]
    return red


# -- the numbers the map gives ----------------------------------------------
def busy_share(d: dict, *scopes):
    """Device time of the scopes' ops, in percent of busy time."""
    tr, b = d.get("trace"), readers.busy(d)
    if tr is None or b is None or b[0] <= 0:
        return None
    scope_s = tr.get("scope_s", {})
    found = [scope_s[s] for s in scopes if s in scope_s]
    return 100.0 * sum(found) / b[0] if found else None


def per_search_ms(d: dict, scope: str):
    """Device time of the scope's ops per execution of the search, over
    the executions ``serve.search_device_ms`` counts."""
    tr = d.get("trace")
    if tr is None or not tr["program"]["calls"]:
        return None
    t = tr["program"].get("scope_s", {}).get(scope)
    return None if t is None else t / tr["program"]["calls"] * 1e3


def queue_wait_ms(d: dict):
    waits = d.get("queue_wait_s")
    return readers.p95(waits) * 1e3 if waits is not None and len(waits) else None


METRICS = {
    "train.tower_device_share": lambda d: busy_share(d, "towers"),
    "train.loss_device_share": lambda d: busy_share(d, "loss"),
    "train.accum_device_share": lambda d: busy_share(d, "grad_accum", "bank_push"),
    "train.optimizer_device_share": lambda d: busy_share(d, "optimizer"),
    "serve.queue_wait_ms": queue_wait_ms,
    "serve.block_topk_device_ms": lambda d: per_search_ms(d, "block_topk"),
    "serve.merge_collective_ms": lambda d: per_search_ms(d, "shard_merge"),
}


def window_waits(waits, began, cut) -> np.ndarray:
    """The queue waits of the requests in batches whose search began before
    ``cut``: ``waits`` holds each batch's waits, ``began`` when each
    batch's search began, both oldest first and ending at the same batch."""
    waits, began = list(waits), list(began)
    n = min(len(waits), len(began))
    out = [w for ws, t in zip(waits[len(waits) - n:], began[len(began) - n:]) if t < cut for w in ws]
    return np.asarray(out, float)
