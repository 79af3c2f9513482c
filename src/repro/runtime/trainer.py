"""Fault-tolerant training loop.

Production concerns handled here (the update step itself stays pure and
jit-compiled — see core/methods.py and launch/steps.py):

  * **Checkpoint/restart** — periodic async checkpoints of (train state,
    loader state); on start the trainer resumes from the newest valid
    checkpoint, skipping corrupt/partial ones (checkpoint/checkpoint.py).
  * **Step-level fault tolerance** — a failing step (device error, NaN loss
    if ``abort_on_nan``) triggers restore-from-last-checkpoint and replay,
    up to ``max_restarts`` times. Fault-injection hooks make this testable.
  * **Straggler watchdog** — per-step wall time is tracked with an EMA; steps
    slower than ``straggler_factor`` x EMA are logged with their step index
    (on a real pod the log feeds the reshard-and-restart runbook; here it is
    also the hook tests use).
  * **Preemption handling** — ``request_stop()`` (wire to SIGTERM in the
    launcher) finishes the current step, writes a final checkpoint, exits
    cleanly.
  * **Profiler spans** — each step is a ``StepTraceAnnotation("train")``
    (the step boundaries of TensorBoard's step-time graph, and the step
    seconds bench/harness/layers.py reads) holding the host spans
    ``repro.train.next_batch``, ``repro.train.update`` and
    ``repro.train.fetch``, on the clock of a JAX profiler trace; with no
    profiler running they cost about a microsecond a step.

The trainer is deliberately agnostic of what the step computes: it takes
``step_fn(state, batch) -> (state, metrics)`` plus a ``next_batch()``
callable, so the same loop drives the paper's ContAccum dual-encoder runs,
the causal-LM cells, GNN and recsys training.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.checkpoint.checkpoint import CheckpointManager, latest_step
from repro.data.loader import LoaderState


@dataclasses.dataclass
class TrainerConfig:
    """Loop-level knobs only — *what* a step computes (the method
    composition, the loss backend, the PrecisionPolicy) lives entirely in
    the jitted ``step_fn`` the trainer is handed (core/step_program.py), so
    every precision preset checkpoints, restores and replays through this
    loop unchanged: the checkpoint payload carries the state's dtypes (bf16
    bank rings included), and ``abort_on_nan`` reads the fp32 loss metric
    the accum-dtype contract guarantees.

    total_steps: run length in optimizer updates.
    checkpoint_dir/checkpoint_every/keep_checkpoints: periodic async
        checkpoints of (train state, loader state); None disables.
    max_restarts: restore-and-replay budget for failing steps.
    straggler_factor/straggler_warmup/ema_decay: step-time watchdog (steps
        slower than factor x EMA are logged after the warm-up).
    abort_on_nan: treat a non-finite loss as a step failure (restore).
    log_every: metric print cadence.
    eval_every: periodic-eval cadence (0 disables). Every ``eval_every``
        steps the trainer calls its ``eval_fn(state, step) -> dict`` hook —
        the ANCE-style loop of re-encoding and searching the corpus with
        the *training-time* encoder (wire it to
        ``repro.evaluation.evaluate_topk`` via a Retriever). Results are
        merged into the step's history row under ``eval/`` keys.
        ``eval_every``/``eval_fn`` are sugar over the generic ``hooks=``
        mechanism below (a ``PeriodicHook(prefix='eval/')``); the mining
        refresh (repro/mining ``HardNegativeMiner.refresh_hook``) rides the
        same mechanism, so eval and miner refresh share one cadence path.
    """

    total_steps: int
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 100
    keep_checkpoints: int = 3
    max_restarts: int = 3
    straggler_factor: float = 3.0
    straggler_warmup: int = 5          # steps before the EMA is trusted
    ema_decay: float = 0.9
    abort_on_nan: bool = True
    log_every: int = 10
    eval_every: int = 0


class StepFailure(RuntimeError):
    """Raised inside the loop to trigger restore-and-replay."""


@dataclasses.dataclass
class PeriodicHook:
    """A callback the loop fires every ``every`` steps (after the step, when
    ``(step + 1) % every == 0``; 0 disables).

    ``fn(state, step)`` may return a metric dict — values are merged into
    the step's history row under ``prefix``. ``advisory`` hooks (eval,
    miner refresh) must never consume the restore-and-replay budget of the
    training path: their exceptions are logged and swallowed (a
    deterministic hook error would otherwise replay the same healthy step
    until max_restarts kills the run). Non-advisory hooks raise
    ``StepFailure`` and go through the normal restore path."""

    every: int
    fn: Callable[[Any, int], Optional[Dict[str, float]]]
    prefix: str = ""
    name: str = "hook"
    advisory: bool = True


@dataclasses.dataclass
class TrainerReport:
    steps_run: int
    restarts: int
    stragglers: List[int]
    final_metrics: Dict[str, float]
    history: List[Dict[str, float]]


class Trainer:
    def __init__(
        self,
        cfg: TrainerConfig,
        step_fn: Callable[[Any, Any], Any],
        next_batch: Callable[[int], Any],
        *,
        loader_state: Optional[LoaderState] = None,
        eval_fn: Optional[Callable[[Any, int], Dict[str, float]]] = None,
        hooks: Sequence[PeriodicHook] = (),
        aux_state: Optional[Any] = None,
        # test hooks ------------------------------------------------------
        fault_hook: Optional[Callable[[int], None]] = None,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.cfg = cfg
        self.step_fn = step_fn
        self.next_batch = next_batch
        self.loader_state = loader_state or LoaderState()
        self.eval_fn = eval_fn
        # aux_state: an optional side object riding the checkpoint payload —
        # anything with state_to_save() -> fixed-structure np pytree and
        # load_saved_state(tree) (e.g. the mining subsystem's table)
        self.aux_state = aux_state
        self._hooks: List[PeriodicHook] = list(hooks)
        if eval_fn is not None:
            # legacy sugar: eval is just one more periodic hook
            self._hooks.append(
                PeriodicHook(
                    every=cfg.eval_every, fn=eval_fn, prefix="eval/", name="eval"
                )
            )
        self.fault_hook = fault_hook
        self.clock = clock
        self._stop = False
        self.stragglers: List[int] = []
        self.restarts = 0
        self.history: List[Dict[str, float]] = []
        self._ckpt = (
            CheckpointManager(
                cfg.checkpoint_dir, keep=cfg.keep_checkpoints, async_save=True
            )
            if cfg.checkpoint_dir
            else None
        )

    # -- public control -----------------------------------------------------
    def request_stop(self):
        """Preemption notice: finish the current step, checkpoint, exit."""
        self._stop = True

    # -- checkpoint plumbing --------------------------------------------------
    def _save(self, step: int, state, *, block: bool = False):
        if self._ckpt is None:
            return
        ls = self.loader_state
        payload = {
            "state": state,
            "loader": np.asarray(
                [ls.epoch, ls.step, ls.mined_step, ls.mined_version], np.int64
            ),
        }
        if self.aux_state is not None:
            payload["aux"] = self.aux_state.state_to_save()
        self._ckpt.save(step, payload, block=block)

    def _restore(self, template_state):
        if self._ckpt is None or latest_step(self.cfg.checkpoint_dir) is None:
            return None
        payload = {
            "state": template_state,
            "loader": np.zeros((4,), np.int64),
        }
        if self.aux_state is not None:
            # the current aux pytree is its own template (fixed structure)
            payload["aux"] = self.aux_state.state_to_save()
        restored, step = self._ckpt.restore_latest(payload)
        ls = self.loader_state
        ls.epoch, ls.step, ls.mined_step, ls.mined_version = (
            int(v) for v in restored["loader"]
        )
        if self.aux_state is not None:
            self.aux_state.load_saved_state(restored["aux"])
        return restored["state"], step

    # -- the loop -------------------------------------------------------------
    def run(self, state) -> tuple[Any, TrainerReport]:
        cfg = self.cfg
        start = 0
        resumed = self._restore(state)
        if resumed is not None:
            state, start = resumed
            start += 1

        ema = None
        step = start
        last_metrics: Dict[str, float] = {}
        while step < cfg.total_steps and not self._stop:
            try:
                if self.fault_hook is not None:
                    self.fault_hook(step)  # may raise (injected fault)
                with jax.profiler.StepTraceAnnotation("train", step_num=step):
                    with jax.profiler.TraceAnnotation("repro.train.next_batch"):
                        batch = self.next_batch(step)
                    t0 = self.clock()
                    with jax.profiler.TraceAnnotation("repro.train.update"):
                        state, metrics = self.step_fn(state, batch)
                    with jax.profiler.TraceAnnotation("repro.train.fetch"):
                        metrics = jax.device_get(metrics)
                    dt = self.clock() - t0

                if cfg.abort_on_nan:
                    loss = float(np.asarray(getattr(metrics, "loss", metrics.get("loss", 0.0)) if isinstance(metrics, dict) else metrics.loss))
                    if not np.isfinite(loss):
                        raise StepFailure(f"non-finite loss at step {step}: {loss}")

                # straggler watchdog
                if ema is not None and step - start >= cfg.straggler_warmup:
                    if dt > cfg.straggler_factor * ema:
                        self.stragglers.append(step)
                ema = dt if ema is None else cfg.ema_decay * ema + (1 - cfg.ema_decay) * dt

                last_metrics = self._log(step, metrics, dt)
                for hook in self._hooks:
                    if not hook.every or (step + 1) % hook.every:
                        continue
                    try:
                        res = hook.fn(state, step)
                    except Exception as e:
                        if not hook.advisory:
                            raise StepFailure(
                                f"{hook.name} hook failed at step {step}: {e}"
                            ) from e
                        print(f"step {step}: {hook.name} failed ({e})", flush=True)
                    else:
                        vals = {
                            f"{hook.prefix}{k}": float(v)
                            for k, v in (res or {}).items()
                        }
                        if vals:
                            last_metrics.update(vals)  # history row, in place
                            msg = " ".join(
                                f"{k}={v:.4f}" for k, v in vals.items()
                            )
                            print(f"step {step}: {msg}", flush=True)
                if cfg.checkpoint_dir and (step + 1) % cfg.checkpoint_every == 0:
                    self._save(step, state)
                step += 1
            except (StepFailure, jax.errors.JaxRuntimeError, FloatingPointError) as e:
                self.restarts += 1
                if self.restarts > cfg.max_restarts or self._ckpt is None:
                    raise
                resumed = self._restore(state)
                if resumed is None:
                    raise RuntimeError(
                        f"step {step} failed ({e}) with no checkpoint to restore"
                    ) from e
                state, ck_step = resumed
                step = ck_step + 1

        if self._ckpt is not None:
            self._save(max(step - 1, 0), state, block=True)
            self._ckpt.wait()
        return state, TrainerReport(
            steps_run=step - start,
            restarts=self.restarts,
            stragglers=self.stragglers,
            final_metrics=last_metrics,
            history=self.history,
        )

    def _log(self, step: int, metrics, dt: float) -> Dict[str, float]:
        items = metrics if isinstance(metrics, dict) else metrics._asdict()
        flat = {}
        for k, v in items.items():
            # a group of metrics (StepMetrics.tower) is logged by its keys
            for kk, vv in (v.items() if isinstance(v, dict) else [(k, v)]):
                if np.ndim(vv) == 0:
                    flat[kk] = float(np.asarray(vv))
        flat["step"] = step
        flat["step_time_s"] = dt
        self.history.append(flat)
        if step % self.cfg.log_every == 0:
            keys = [k for k in ("loss", "accuracy", "grad_norm_ratio") if k in flat]
            msg = " ".join(f"{k}={flat[k]:.4f}" for k in keys)
            print(f"step {step}: {msg} ({dt*1e3:.1f} ms)", flush=True)
        return flat
