"""Training cells of an LM retriever tower: ContAccum updates of one
shared Moonlight-16B-A3B backbone (one chip's share) through
``repro.launch.train.build`` and ``Trainer.run``, closed loop.

As ``bench/drivers/train.py`` does for the two bert-base towers: set-up
builds the compiled update, its state and the Trainer with the weights the
benchmark made from the seed (``bench/harness/lm_reference.py``); the same
``Trainer.run`` drives the first ``checked_steps`` updates (compiling on
the first) and, without a pause, the measured window.

``correct`` compares the checked updates with the plain float32 reference
(``lm_reference.contaccum_steps``) run from the same seed over the same
batches, after the program's state is freed: each update's loss and the
per-leaf norms of the parameters' change (``bench/harness/compare.py``).

Per-layer data: the update's FLOPs (``bench/harness/lm_counts.py``), the
expert layer's device time by scope and the grouped products' time
(``bench/harness/moe_trace.py``, traced runs), and the expert counters
that each update returns in ``StepMetrics.tower``.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from bench.drivers import train as bert_train
from bench.harness import compare, lm_counts, lm_reference, moe_trace, weights

# the reference's names for the config's deployment
WIDTHS = {"d_model": "hidden_size", "n_heads": "num_attention_heads",
          "d_ff": "intermediate_size", "vocab_size": "vocab_size",
          "n_layers": "num_hidden_layers", "first_dense": "first_k_dense_replace",
          "norm_eps": "rms_norm_eps", "rope_theta": "rope_theta"}
MLA = ("kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")


def model_of(cfg: dict) -> dict:
    """The configuration's numbers with the deployment's: the router's
    width (every expert of the layer) and the first expert held here."""
    dep = cfg["deployment"]
    return dict({k: v for k, v in cfg.items() if isinstance(v, (int, float, str, bool))},
                router_experts=dep["experts_published"], experts_first=dep["experts_held"][0])


def check_widths(tower, model: dict):
    """The program's registered tower has the configuration's sizes."""
    m = tower.moe
    got = {v: getattr(tower, k) for k, v in WIDTHS.items()}
    got.update({k: getattr(tower.mla, k) for k in MLA})
    got.update(moe_intermediate_size=m.d_expert, num_experts_per_tok=m.top_k,
               routed_scaling_factor=m.routed_scale, norm_topk_prob=m.normalize_top_k,
               n_routed_experts=m.held_range[1], router_experts=m.n_experts,
               experts_first=m.held_range[0],
               n_shared_experts=m.d_shared // m.d_expert,
               scoring_func="sigmoid" if m.dropless else "softmax",
               topk_method="noaux_tc" if m.dropless else "greedy")
    bad = {k: (v, model[k]) for k, v in got.items() if v != model[k]}
    if bad:
        raise ValueError(f"program tower differs from the configuration: {bad}")


class Loop:
    """Wraps the Trainer's ``next_batch`` and update calls: keeps the
    checked updates' batches and the parameters' change over them (from
    ``params0``, on the host), opens the window after them and stops the
    Trainer once ``seconds`` passed."""

    def __init__(self, run, trainer, update, checked: int, params0):
        self.run, self.trainer, self.update = run, trainer, update
        self.checked, self.params0 = checked, params0
        self.calls = self.window_updates = 0
        self.batches, self.batch_s, self.step_at = [], [], []
        self.change = None
        self.next_batch = trainer.next_batch

    def batch(self, step):
        t = time.perf_counter()
        with self.run.span("bench.batch"):
            b = self.next_batch(step)
        if self.run.in_window:
            self.batch_s.append(time.perf_counter() - t)
        elif self.calls < self.checked:
            self.batches.append(tuple(np.asarray(x) for x in
                                      (b.query, b.passage_pos, b.passage_hard)))
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
        if i == self.checked - 1:
            self.change = bert_train.flatten(jax.tree_util.tree_map(
                lambda a, b: float(np.linalg.norm((np.asarray(a) - b).ravel().astype(np.float64))),
                jax.device_get(state.params), self.params0))
            self.params0 = None
            jax.block_until_ready(state)
            # what set-up made lives on; full collections in the window
            # need not walk it
            gc.collect()
            gc.freeze()
            self.run.open_window()
        return state, metrics


def run(r):
    """One run of an LM tower's training cell; ``r`` is the harness's run
    context."""
    import jax

    from repro.configs import get_arch
    from repro.launch import train

    cfg, wl = r.config, r.workload
    model = model_of(cfg)
    args = bert_train.program_args(cfg, wl, r.seed)
    check_widths(get_arch(args.arch).model_cfg, model)

    built = train.build(args)
    params = lm_reference.tower_params(weights.root_key(r.seed), model, cfg["select_bias_std"])
    shapes = jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), built.state.params)
    if shapes != jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), params):
        raise ValueError("the benchmark's weights do not have the program's layout")
    state = built.state._replace(params=params)
    # the starting point waits on the host: the update fills the chip
    params0 = jax.device_get(params)
    del params
    trainer = built.trainer
    loop = Loop(r, trainer, built.update, wl["checked_steps"], params0)
    trainer.next_batch = loop.batch
    trainer.step_fn = loop.step
    del params0, built

    state, report = trainer.run(state)
    jax.block_until_ready(state)
    r.close_window()
    gc.unfreeze()

    history = report.history
    window = history[wl["checked_steps"]:]
    routed = float(np.mean([h["rows_routed"] for h in window]))
    layer = {
        "window_updates": loop.window_updates,
        "batch_s": loop.batch_s,
        "flops_per_update": lm_counts.update_flops(wl, model),
        "expert_least_s_per_update": r.least_time(*lm_counts.expert_work(routed, model)),
        "expert_load_max_over_mean": float(np.mean(
            [h["expert_slots_max"] / h["expert_slots_mean"] for h in window])),
        "memory_peak_bytes": r.memory_peak_bytes(),
    }
    if r.trace:
        layer.update(moe_trace.seconds(str(r.trace_dir)))
        batch = trainer.next_batch(len(history))
        compiled = loop.update.lower(state, batch).compile()
        ma = compiled.memory_analysis()
        layer["compiled_bytes"] = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                                   - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        del compiled, batch
    checked = history[: wl["checked_steps"]]
    counters = {k: [h[k] for h in history[:1] + window[-1:]]
                for k in ("expert_slots_max", "expert_slots_mean", "absent_slots",
                          "rows_routed", "rows_buffer")}
    program = {
        "losses": [h["loss"] for h in checked],
        "grad_norms": [[h["grad_norm"], h["grad_norm_query"], h["grad_norm_passage"]]
                       for h in checked],
        "change": loop.change,
    }
    restarts = report.restarts
    del state, trainer, loop.update
    gc.collect()

    r.note("longest update intervals (window offset s, interval s)",
           bert_train.longest(loop.step_at), "longest next_batch s",
           [round(t, 4) for t in sorted(loop.batch_s)[-3:]])
    r.note("expert counters of the first and the last update", counters)
    e2e = {"train_pairs_per_s": wl["total_batch"] * layer["window_updates"] / r.window_s()}

    ref = reference_readings(r, loop.batches)
    checks = compare.with_limits(compare.train_readings(program, ref), wl["limits"])
    keep = compare.kept_leaves(ref["grad1"])
    gaps = compare.leaf_gaps(program["change"], ref["change"], keep)
    r.note("worst leaves of change_gap (leaf, gap)",
           [(k, round(gaps[k], 4)) for k in sorted(gaps, key=gaps.get)[-2:]],
           "losses: program", np.round(program["losses"], 5).tolist(), "reference",
           np.round(ref["losses"], 5).tolist(),
           "gradient norms [global, query side, passage side] per update: program",
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


def reference_readings(r, batches, cast=lm_reference.reference.identity, **fault):
    """The float32 reference (or, with another ``cast`` or a fault of
    ``lm_reference``, a control) over the checked updates' batches, from
    the seed's weights."""
    model = model_of(r.config)
    params = lm_reference.tower_params(weights.root_key(r.seed), model,
                                       r.config["select_bias_std"])
    out = lm_reference.contaccum_steps(params, batches, model, bert_train.optimizer(r.workload),
                                       r.workload, cast=cast, **fault)
    return dict(out, grad1=bert_train.flatten(out["grad1"]),
                change=bert_train.flatten(out["change"]))


def controls(r, res) -> dict:
    """The readings the limits are set from, beside a sound run's: the fp8
    control, the two planted faults of the expert layer (tokens past an
    expert's even share dropped; gates from the biased scores), and the
    bert cells' two of the accumulation (half of every batch left out;
    half the chunks' gradients dropped, losses and pushes kept)."""
    ex = res["extra"]
    ref = ex["reference"]
    k = r.workload["total_batch"] // r.workload["local_batch"]
    out = {}
    for name, kw in (("control_fp8", {"cast": lm_reference.reference.fp8}),
                     ("capacity_1.0", {"capacity": 1.0}),
                     ("biased_gates", {"biased_gates": True}),
                     ("half_batch", {"chunks_used": k // 2}),
                     ("grads_dropped", {"grad_chunks": k // 2})):
        out[name] = compare.train_readings(reference_readings(r, ex["batches"], **kw), ref)
    return out
