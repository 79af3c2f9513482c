"""Shared arithmetic of the per-layer readers (bench/metrics/*.py): each
reader takes the driver's layer data, with the reduced trace under
``trace`` in a traced run, and returns its number or None when it finds
nothing to read."""

from __future__ import annotations

import re

import numpy as np

from bench.harness import trace as trace_mod

# the fused InfoNCE Pallas kernel (kernels/fused_infonce): a tpu_custom_call
# whose first operands are the (M, 1) label column and the (1, N) validity row
INFONCE = re.compile(r"custom-call\(s32\[\d+,1\](\{[^}]*\})? [^,]*, s32\[1,\d+\]")


def is_infonce(hlo: str) -> bool:
    return "tpu_custom_call" in hlo and bool(INFONCE.search(hlo))


def program_calls(d: dict):
    """(calls, device seconds per call) of the program that took most device
    time in the traced window (the update, or the search), counting only
    executions that lie wholly inside it; None without such a call."""
    tr = d.get("trace")
    if tr is None or not tr["program"]["calls"]:
        return None
    p = tr["program"]
    return p["calls"], p["seconds"] / p["calls"]


def busy(d: dict):
    tr = d.get("trace")
    if tr is None or tr["window_s"] <= 0:
        return None
    return tr["busy_s"], tr["window_s"]


def idle_share_pct(d: dict):
    b = busy(d)
    return None if b is None else 100.0 * (1.0 - b[0] / b[1])


def infonce_seconds(d: dict):
    tr = d.get("trace")
    if tr is None:
        return None
    t, calls = trace_mod.op_seconds(tr, is_infonce)
    return t if calls else None


def p95(x) -> float:
    return float(np.percentile(np.asarray(x, float), 95))
