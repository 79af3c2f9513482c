"""RPL005 jit hazards.

Inside a ``jax.jit``-ed (or ``shard_map``-ped) function, Python control flow
on traced values raises ``TracerBoolConversionError`` at runtime — but only
on the first call that reaches the branch, which for rarely-taken paths can
be deep into a training run. Host side effects (``print``, ``open``,
``np.random``, wall-clock reads) silently execute at *trace* time only, and
``global``/``nonlocal`` writes mutate Python state once per trace, not once
per step. All three are statically visible; this rule flags them at the
definition site.

Static arguments (``static_argnums``/``static_argnames``) are excluded from
the traced set, as are shape/dtype/ndim attribute probes, ``is None`` tests,
``isinstance``/``len`` checks — those are concrete under tracing.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from tools.reprolint.astutil import call_name, dotted_name, function_param_names
from tools.reprolint.engine import FileContext, RepoContext, Violation

_JIT_SUFFIXES = ("jit",)                    # jax.jit, jit, pjit
_SHARD_MAP_NAMES = {"shard_map"}            # jax.shard_map / bare shard_map

#: calls that are host-only side effects under a trace
_HOST_CALLS = {"print", "input", "breakpoint", "open"}
_HOST_MODULES = {"np.random", "numpy.random", "random", "time"}

#: refresh entry points of the mining subsystem (repro/mining): the whole
#: refresh pipeline is host-side by construction — a corpus re-encode, a
#: worker thread, numpy table writes and an atomic buffer swap. Called from
#: jitted code it would run once at trace time and bake the then-current
#: table in as a compile-time constant. Matched as <...miner/mining...>.<entry>
#: so e.g. ``self.miner.refresh_async(...)`` or ``mining.refresh(...)`` fire
#: while an unrelated ``cache.refresh()`` does not.
_MINING_ENTRY_ATTRS = {"refresh", "refresh_async", "refresh_hook", "wait", "poll"}
_MINING_OWNER_HINTS = ("miner", "mining")

#: attribute probes that are static (concrete) on tracers
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "aval"}


def _is_jit_name(node: ast.AST) -> bool:
    name = dotted_name(node)
    return name is not None and name.split(".")[-1].endswith(_JIT_SUFFIXES)


def _static_args_from(call_or_dec: ast.Call) -> Tuple[Set[int], Set[str]]:
    nums: Set[int] = set()
    names: Set[str] = set()
    for kw in call_or_dec.keywords:
        if kw.arg == "static_argnums":
            for sub in ast.walk(kw.value):
                if isinstance(sub, ast.Constant) and isinstance(sub.value, int):
                    nums.add(sub.value)
        elif kw.arg == "static_argnames":
            for sub in ast.walk(kw.value):
                if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
                    names.add(sub.value)
    return nums, names


class JitHazardRule:
    rule_id = "RPL005"
    name = "jit-hazard"
    doc = (
        "no Python if/while on traced values, host side effects, or "
        "global/nonlocal mutation inside jitted/shard_mapped functions"
    )

    def check(self, fc: FileContext, repo: RepoContext) -> Iterable[Violation]:
        out: List[Violation] = []
        for fn, traced in self._jitted_functions(fc):
            out.extend(self._check_body(fc, fn, traced))
        return out

    # ------------------------------------------------------------ discovery
    def _jitted_functions(
        self, fc: FileContext
    ) -> Iterable[Tuple[ast.FunctionDef, Set[str]]]:
        defs: Dict[str, ast.FunctionDef] = {
            n.name: n for n in ast.walk(fc.tree) if isinstance(n, ast.FunctionDef)
        }
        seen: Set[int] = set()

        # decorator style: @jax.jit / @partial(jax.jit, static_argnums=...)
        for fn in defs.values():
            for dec in fn.decorator_list:
                static_nums: Set[int] = set()
                static_names: Set[str] = set()
                hit = False
                if _is_jit_name(dec) or (
                    isinstance(dec, ast.Name) and dec.id in _SHARD_MAP_NAMES
                ):
                    hit = True
                elif isinstance(dec, ast.Call):
                    if _is_jit_name(dec.func):
                        hit = True
                        static_nums, static_names = _static_args_from(dec)
                    elif call_name(dec) == "partial" and dec.args and _is_jit_name(
                        dec.args[0]
                    ):
                        hit = True
                        static_nums, static_names = _static_args_from(dec)
                if hit and id(fn) not in seen:
                    seen.add(id(fn))
                    yield fn, self._traced_params(fn, static_nums, static_names)

        # call style: jax.jit(f, ...) / sm(f, mesh=..., ...)
        for node in ast.walk(fc.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            is_jit = _is_jit_name(node.func)
            is_sm = (
                isinstance(node.func, ast.Name) and node.func.id in _SHARD_MAP_NAMES
            ) or (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in _SHARD_MAP_NAMES
            )
            if not (is_jit or is_sm):
                continue
            target = node.args[0]
            if not isinstance(target, ast.Name):
                continue
            fn = defs.get(target.id)
            if fn is None or id(fn) in seen:
                continue
            seen.add(id(fn))
            static_nums, static_names = _static_args_from(node)
            yield fn, self._traced_params(fn, static_nums, static_names)

    def _traced_params(
        self, fn: ast.FunctionDef, static_nums: Set[int], static_names: Set[str]
    ) -> Set[str]:
        params = function_param_names(fn)
        traced = {
            p
            for i, p in enumerate(params)
            if i not in static_nums and p not in static_names
        }
        return traced - {"self", "cls"}

    # ------------------------------------------------------------- checking
    def _check_body(
        self, fc: FileContext, fn: ast.FunctionDef, traced: Set[str]
    ) -> Iterable[Violation]:
        for node in ast.walk(fn):
            if isinstance(node, (ast.If, ast.While)):
                name = self._dynamic_traced_ref(fc, node.test, traced)
                if name is not None:
                    kind = "if" if isinstance(node, ast.If) else "while"
                    yield self._violation(
                        fc,
                        node,
                        f"Python `{kind}` on traced argument '{name}' of "
                        f"jitted '{fn.name}' — use jax.lax.cond/while_loop, "
                        "jnp.where, or mark the argument static",
                    )
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                names = ", ".join(node.names)
                yield self._violation(
                    fc,
                    node,
                    f"{type(node).__name__.lower()} write to '{names}' inside "
                    f"jitted '{fn.name}' runs at trace time only — return the "
                    "value or carry it in explicit state",
                )
            elif isinstance(node, ast.Call):
                host = self._host_call(node)
                if host is not None:
                    yield self._violation(
                        fc,
                        node,
                        f"host call {host}(...) inside jitted '{fn.name}' "
                        "executes at trace time only — use jax.debug.print / "
                        "jax.experimental.io_callback, or hoist it out",
                    )
                    continue
                mining = self._mining_refresh_call(node)
                if mining is not None:
                    yield self._violation(
                        fc,
                        node,
                        f"mining refresh entry point {mining}(...) inside "
                        f"jitted '{fn.name}' runs the host-side refresh "
                        "pipeline (corpus re-encode, worker thread, np table "
                        "swap) at trace time only, baking a stale negative "
                        "table in as a constant — drive the miner from a "
                        "trainer PeriodicHook outside the jitted step",
                    )

    def _violation(self, fc: FileContext, node: ast.AST, msg: str) -> Violation:
        return Violation(
            path=fc.relpath,
            line=node.lineno,
            col=node.col_offset,
            rule=self.rule_id,
            message=msg,
        )

    def _host_call(self, node: ast.Call) -> Optional[str]:
        if isinstance(node.func, ast.Name) and node.func.id in _HOST_CALLS:
            return node.func.id
        full = dotted_name(node.func)
        if full is not None:
            for mod in _HOST_MODULES:
                if full.startswith(mod + "."):
                    return full
        return None

    def _mining_refresh_call(self, node: ast.Call) -> Optional[str]:
        """``<owner>.<entry>`` where the owner chain names the miner — the
        mining-subsystem extension of the host-call net (see
        _MINING_ENTRY_ATTRS above)."""
        full = dotted_name(node.func)
        if full is None:
            return None
        parts = full.split(".")
        if len(parts) < 2 or parts[-1] not in _MINING_ENTRY_ATTRS:
            return None
        if any(h in p.lower() for p in parts[:-1] for h in _MINING_OWNER_HINTS):
            return full
        return None

    def _dynamic_traced_ref(
        self, fc: FileContext, test: ast.AST, traced: Set[str]
    ) -> Optional[str]:
        """First traced-parameter reference in ``test`` that is not a
        statically-resolvable probe (shape/dtype attrs, is-None, isinstance,
        len)."""
        for node in ast.walk(test):
            if not (
                isinstance(node, ast.Name)
                and isinstance(node.ctx, ast.Load)
                and node.id in traced
            ):
                continue
            if self._is_static_use(fc, node):
                continue
            return node.id
        return None

    def _is_static_use(self, fc: FileContext, name: ast.Name) -> bool:
        parent = fc.parent(name)
        if isinstance(parent, ast.Attribute) and parent.attr in _STATIC_ATTRS:
            return True
        if isinstance(parent, ast.Call):
            fname = call_name(parent)
            if fname in {"isinstance", "len", "callable", "hasattr", "getattr", "type"}:
                return True
        if isinstance(parent, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in parent.ops):
                return True
        # x.shape[0] == n: Name -> Attribute handled above; Name -> Subscript
        # of a static attr
        if isinstance(parent, ast.Subscript):
            gp = fc.parent(parent)
            if isinstance(gp, ast.Attribute) and gp.attr in _STATIC_ATTRS:
                return True
        return False
