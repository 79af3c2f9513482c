"""Training cells: ContAccum updates through ``repro.launch.train.build``
and ``Trainer.run``, closed loop (each update follows the last).

Set-up builds one object, the compiled update with its state and the
Trainer that feeds it, with the weights the benchmark made from the seed.
The same ``Trainer.run`` then drives the first ``checked_steps`` updates
(compiling the update on the first) and, without a pause, the measured
window: updates go on until ``--seconds`` have passed. The harness only
wraps the Trainer's two calls, ``next_batch`` and the update, from here.

``correct`` compares the first updates with the plain float32 reference
run from the same seed over the same batches (the token ids the program's
loader fed them): each update's loss, and the per-leaf norms of the
parameters' change over the checked updates. The gradient norms of each
update go to standard error beside the reference's, and are not compared
(bench/harness/compare.py).
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench.harness import compare, counts, reference, weights


def program_args(cfg: dict, wl: dict, seed: int):
    from repro.launch import train

    p = cfg["program"]
    return train.parse_args([
        "--arch", p["arch"], "--method", p["method"], "--precision", p["precision"],
        "--total-batch", str(wl["total_batch"]), "--local-batch", str(wl["local_batch"]),
        "--bank", str(wl["bank"]), "--q-len", str(wl["q_len"]), "--p-len", str(wl["p_len"]),
        "--loss-impl", wl["loss_impl"], "--steps", str(wl["steps"]), "--lr", str(wl["lr"]),
        "--corpus-size", str(wl["corpus_size"]), "--seed", str(seed),
    ])


def check_widths(tower, model: dict):
    """The program's registered tower has the configuration's widths."""
    want = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
            "n_heads": "num_attention_heads", "d_ff": "intermediate_size",
            "vocab_size": "vocab_size", "max_position": "max_position_embeddings",
            "type_vocab": "type_vocab_size", "norm_eps": "layer_norm_eps"}
    bad = {k: (getattr(tower, k), model[v]) for k, v in want.items()
           if getattr(tower, k) != model[v]}
    if bad:
        raise ValueError(f"program tower differs from the configuration: {bad}")


def optimizer(wl: dict) -> dict:
    """AdamW as the program's train driver builds it: clip 2.0, b1 0.9,
    b2 0.999, eps 1e-8, no weight decay, warm-up steps // 10."""
    return {"lr": wl["lr"], "warmup": wl["steps"] // 10, "total": wl["steps"],
            "clip": 2.0, "b1": 0.9, "b2": 0.999, "eps": 1e-8}


class Loop:
    """Wraps the Trainer's ``next_batch`` and update calls: keeps the
    checked updates' batches and the parameters' change over them, opens
    the window after them and asks the Trainer to stop once ``seconds``
    have passed."""

    def __init__(self, run, trainer, update, checked: int, params0, b1: float):
        self.run, self.trainer, self.update = run, trainer, update
        self.checked = checked
        self.params0 = params0
        self.b1 = b1
        self.calls = 0
        self.batches = []
        self.change = None
        self.grad1_norms = None
        self.grad1 = None
        self.window_updates = 0
        self.batch_s = []
        self.step_at = []
        self.next_batch = trainer.next_batch

    def batch(self, step):
        t = time.perf_counter()
        with self.run.span("bench.batch"):
            b = self.next_batch(step)
        if self.run.in_window:
            self.batch_s.append(time.perf_counter() - t)
        elif self.calls < self.checked:
            self.batches.append(tuple(np.asarray(x) for x in (b.query, b.passage_pos, b.passage_hard)))
        return b

    def step(self, state, batch):
        import jax

        i = self.calls
        self.calls += 1
        with self.run.span("bench.dispatch"):
            state, metrics = self.update(state, batch)
        if self.run.in_window:
            self.window_updates += 1
            self.step_at.append(time.perf_counter() - self.run.t0)
            if self.run.tracing:
                with self.run.span("bench.fetch"):
                    jax.block_until_ready(metrics)
                self.run.maybe_stop_trace()
            if self.run.elapsed() >= self.run.seconds:
                self.trainer.request_stop()
            return state, metrics
        if i == 0:
            # Adam's first moment after one update is (1 - b1) times the
            # gradient it was handed
            mu = next(s.mu for s in state.opt_state if hasattr(s, "mu"))
            self.grad1_norms = [n / (1 - self.b1) for n in tower_norms(mu)]
            self.grad1 = {k: v / (1 - self.b1) for k, v in
                          flatten(jax.device_get(reference._leaf_norms(mu))).items()}
        if i == self.checked - 1:
            delta = jax.tree_util.tree_map(lambda a, b: a - b, state.params, self.params0)
            self.change = flatten(jax.device_get(reference._leaf_norms(delta)))
            del delta
            self.params0 = None
            jax.block_until_ready(state)
            self.run.open_window()
        return state, metrics


def longest(times, n=3):
    """The ``n`` longest intervals between consecutive times, with where
    each ended: stalls show here."""
    gaps = sorted(zip(np.diff(times), times[1:]), reverse=True)[:n]
    return [(round(float(t), 3), round(float(g), 3)) for g, t in gaps]


def tower_norms(tree) -> list:
    """[global, query tower, passage tower] L2 norms of a params-shaped tree."""
    import jax

    sq = {t: sum(float(v) ** 2 for v in jax.tree_util.tree_leaves(
        jax.device_get(reference._leaf_norms(tree[t])))) for t in ("query", "passage")}
    return [float(np.sqrt(sq["query"] + sq["passage"])), float(np.sqrt(sq["query"])),
            float(np.sqrt(sq["passage"]))]


def flatten(tree) -> dict:
    import jax

    return {jax.tree_util.keystr(k): float(v)
            for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def run(r):
    """One run of a training cell; ``r`` is the harness's run context."""
    import jax

    from repro.configs import bert_tower

    cfg, wl, model = r.config, r.workload, r.config["model"]
    args = program_args(cfg, wl, r.seed)
    check_widths(bert_tower(args.arch), model)

    from repro.launch import train

    built = train.build(args)
    key = weights.root_key(r.seed)
    params = weights.tower_params(key, model)
    state = built.state._replace(params=params)
    params0 = jax.tree_util.tree_map(lambda x: x.copy(), params)
    del params
    trainer = built.trainer
    loop = Loop(r, trainer, built.update, wl["checked_steps"], params0, optimizer(wl)["b1"])
    trainer.next_batch = loop.batch
    trainer.step_fn = loop.step
    del params0, built

    state, report = trainer.run(state)
    jax.block_until_ready(state)
    r.close_window()

    history = report.history
    layer = {
        "window_updates": loop.window_updates,
        "batch_s": loop.batch_s,
        "flops_per_update": counts.contaccum_update_flops(wl, model),
        "infonce_least_s_per_update": counts.infonce_least_s(
            wl, model, lambda f, b: r.least_time(f, b)),
        "memory_peak_bytes": r.memory_peak_bytes(),
    }
    if r.trace:
        batch = trainer.next_batch(len(history))
        compiled = loop.update.lower(state, batch).compile()
        ma = compiled.memory_analysis()
        layer["compiled_bytes"] = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                                   - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        del compiled, batch
    checked = history[: wl["checked_steps"]]
    program = {
        "losses": [h["loss"] for h in checked],
        "grad_norms": [[h["grad_norm"], h["grad_norm_query"], h["grad_norm_passage"]]
                       for h in checked],
        "grad1_norms": loop.grad1_norms,
        "grad1": loop.grad1,
        "change": loop.change,
    }
    restarts = report.restarts
    del state, trainer, loop.update
    gc.collect()

    r.note("longest update intervals (window offset s, interval s)",
           longest(loop.step_at), "longest next_batch s", [round(t, 4) for t in sorted(loop.batch_s)[-3:]])
    e2e = {"train_pairs_per_s": wl["total_batch"] * layer["window_updates"] / r.window_s()}

    ref = reference_readings(r, loop.batches, cast=reference.identity)
    checks = compare.with_limits(compare.train_readings(program, ref), wl["limits"])
    keep = compare.kept_leaves(ref["grad1"])
    gaps = compare.leaf_gaps(program["change"], ref["change"], keep)
    med = float(np.median([ref["grad1"][k] for k in keep]))
    worst = sorted(gaps, key=gaps.get)[-2:]
    r.note("worst leaves of change_gap (leaf, gap, reference gradient / median leaf's)",
           [(k, round(gaps[k], 4), round(ref["grad1"][k] / med, 4)) for k in worst],
           "gradient norms [global, query, passage] per update: program",
           np.round(program["grad_norms"], 4).tolist(), "reference",
           np.round(ref["grad_norms"], 4).tolist())
    return {
        "correct": compare.all_within(checks) and restarts == 0,
        "attempted": len(history),
        "failed": restarts,
        "end_to_end": e2e,
        "layer": layer,
        "checks": checks,
        "extra": {"program": program, "reference": ref, "batches": loop.batches},
    }


def reference_readings(r, batches, cast=reference.identity, **fault):
    """The float32 reference (or, with another ``cast`` or a fault of
    ``reference.contaccum_steps``, a control) over the checked updates'
    batches, from the seed's weights."""
    model = r.config["model"]
    params = weights.tower_params(weights.root_key(r.seed), model)
    out = reference.contaccum_steps(params, batches, model, optimizer(r.workload), r.workload,
                                    cast=cast, **fault)
    return dict(out, grad1=flatten(out["grad1"]), change=flatten(out["change"]))
