"""Device time of the dropless expert layer in a profiler trace.

The program names the layer's parts with ``jax.named_scope``
(models/moe.py): ``moe_router``, ``moe_dispatch`` (sort, gathers),
``moe_experts`` (the grouped products), ``moe_shared`` and ``moe_combine``
(the scatter back to tokens). A TPU trace keeps an op's scope path as the
``tf_op`` stat of its metadata (``bench/harness/layers.py`` reads it). The
grouped products are the chip's ragged-dot kernels, whose own op name
(``ragged-dot-none``, ``ragged-dot-metadata``) replaces the scope path; they
are found by that name and counted under ``moe_experts``.

``seconds(trace_dir)`` gives, for the leaf ops inside the traced window
averaged over the devices: ``moe_scope_s`` ({scope: seconds}),
``grouped_s`` (the ragged-dot kernels' seconds), and ``grouped_program_s``,
those of them inside the whole executions of the window's main program
(the ones ``readers.program_calls`` counts). A program without the layer
gives zeros.
"""

from __future__ import annotations

import re

from bench.harness import layers
from bench.harness import trace as trace_mod

MOE_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_shared", "moe_combine")
_SCOPE = {s: re.compile(r"(?:\w+\()*%s\)*:?" % s) for s in MOE_SCOPES}
_GROUPED = re.compile(r"%?ragged-dot[\w.\-]* = ")


def is_grouped(hlo: str) -> bool:
    return bool(_GROUPED.match(hlo))


def scope_of(path: str):
    for c in reversed(re.split(r"[/;]", path)):
        for s, pat in _SCOPE.items():
            if pat.fullmatch(c):
                return s
    return None


def seconds(trace_dir: str) -> dict:
    return reduce(layers.read(trace_dir))


def reduce(tr: dict) -> dict:
    """``seconds`` of a trace as ``layers.read`` gives it."""
    red = trace_mod.reduce(tr)
    lo, hi = trace_mod.window(tr)
    n_dev = red["n_devices"]
    main = red["program"]["name"]
    scope_s = dict.fromkeys(MOE_SCOPES, 0.0)
    grouped = in_program = 0.0
    for dev, leaves in zip(tr["devices"], red["leaf_ops"]):
        paths = dev.get("op_paths", {})
        runs = [(s, s + d) for s, d, n in dev.get("modules", [])
                if re.sub(r"\(.*", "", n) == main and s >= lo and s + d <= hi]
        for s, e, n in leaves:
            if is_grouped(n):
                scope, grouped = "moe_experts", grouped + (e - s)
                if any(a <= s and e <= b for a, b in runs):
                    in_program += e - s
            else:
                scope = scope_of(paths.get(n, ""))
            if scope is not None:
                scope_s[scope] += e - s
    return {"moe_scope_s": {k: v * 1e-9 / n_dev for k, v in scope_s.items()},
            "grouped_s": grouped * 1e-9 / n_dev,
            "grouped_program_s": in_program * 1e-9 / n_dev}
