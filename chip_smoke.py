#!/usr/bin/env python3
"""Chip smoke test: the training and serving main paths, end to end, at
dpr-bert-base width (bert-base-uncased towers, random weights from a seed)
on a TPU, through the same entry points a user calls.

  python chip_smoke.py               # one chip
  python chip_smoke.py --four-chips  # the sharded paths on a four-chip host

One chip: ``repro.launch.train.main`` trains ContAccum at the paper's
geometry (N_total=128, N_local=8 so K=16, N_mem=2048, q_len 32, p_len 256,
bf16_banks) with the dense and then the fused Pallas loss, and
``repro.launch.serve.main`` serves single-query requests against a 32,768
passage index with the dense and then the fused Pallas search. Four chips:
``--dp 4 --shard-banks`` training under both ``--loss-comm`` modes against
the single-device replicated-bank run, and a 4-way sharded index against
the replicated one; nothing else.

Every phase checks its own output (finite losses, no restarts, loss and
top-k agreement with a reference) and prints what it found: compile
seconds, step times after warm-up, peak device bytes. The last stdout line
is one JSON object, ``{"ok": true, "device": {...}}``, printed only when
every phase passed. Without a TPU the script exits non-zero at once, and
it drives the chip from this one process only.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# the paper's ContAccum geometry (the `paper_batch` cell of
# configs/dpr_bert_base.py) at dpr-bert-base width
TRAIN_ARGV = [
    "--arch", "dpr-bert-base", "--method", "contaccum",
    "--total-batch", "128", "--local-batch", "8", "--bank", "2048",
    "--q-len", "32", "--p-len", "256", "--precision", "bf16_banks",
    "--steps", "6", "--corpus-size", "2048", "--seed", "0",
]
SERVE_ARGV = [
    "--arch", "dpr-bert-base", "--precision", "bf16_banks",
    "--n-passages", "32768", "--q-len", "32", "--p-len", "256",
    "--n-queries", "256", "--top-k", "100", "--max-batch", "32",
    "--seed", "0",
]
# sharded-bank parity: K=1 (one chunk = the global batch) so the 4-way
# device-major batch and the single-device batch form identical chunks;
# fp32 at full matmul precision so the comparison can use the fp32
# tolerance of tests/test_distributed.py
SHARDED_TRAIN_ARGV = [
    "--arch", "dpr-bert-base", "--method", "contaccum",
    "--total-batch", "128", "--bank", "2048", "--q-len", "32",
    "--p-len", "256", "--precision", "fp32", "--loss-impl", "fused",
    "--steps", "4", "--corpus-size", "2048", "--seed", "0",
]

BF16_RTOL = 5e-2      # tests/test_precision.py: bf16 loss vs reference
SCORE_RTOL, SCORE_ATOL = 2e-2, 1e-2   # tests/test_retrieval.py: bf16 scores
DIST_RTOL = 2e-4      # tests/test_distributed.py: sharded vs single-device


class CompileClock:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events (a cache hit is recorded as a short compile)."""

    def __init__(self, jax):
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.seconds, self.hits

    def since(self, mark):
        return {"compile_s": self.seconds - mark[0], "cache_hits": self.hits - mark[1]}


def peak_bytes(devices):
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def report(name, **fields):
    print(f"[{name}] " + json.dumps(fields, default=float), flush=True)


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def train_phase(name, argv, clock, devices):
    """One ``train.main`` run; returns its per-step losses."""
    import numpy as np

    from repro.launch import train

    mark = clock.mark()
    _, rep = train.main(argv)
    losses = [h["loss"] for h in rep.history]
    steps = train.parse_args(argv).steps
    check(rep.steps_run == steps, f"{name}: ran {rep.steps_run} of {steps} steps")
    check(rep.restarts == 0, f"{name}: {rep.restarts} restarts")
    check(bool(np.all(np.isfinite(losses))), f"{name}: non-finite loss {losses}")
    times = [h["step_time_s"] for h in rep.history]
    report(
        name, losses=losses, first_step_s=times[0],
        step_s_after_warmup=times[2:], peak_bytes_in_use=peak_bytes(devices),
        **clock.since(mark),
    )
    return losses


def fused_step_has_kernel(argv):
    """Whether the compiled fused-loss train step contains the Pallas
    kernel (``tpu_custom_call``), not an XLA fallback."""
    from repro.launch import train

    run = train.build(train.parse_args(argv))
    batch = run.trainer.next_batch(0)
    return "tpu_custom_call" in run.update.lower(run.state, batch).compile().as_text()


def separated(scores, rtol, atol):
    """Ranks whose reference score differs from both neighbours by more than
    the tolerance: there the served id is determined, ties aside."""
    import numpy as np

    tol = atol + rtol * np.abs(scores)
    gap = np.diff(scores, axis=1)
    left = np.concatenate([np.full_like(scores[:, :1], np.inf), -gap], axis=1)
    right = np.concatenate([-gap, np.full_like(scores[:, :1], np.inf)], axis=1)
    return (left > tol) & (right > tol)


def reference_topk(retriever, queries, k):
    """Plain float32 ``jnp`` top-k over the retriever's index rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    store = retriever.index
    q = jax.jit(retriever.encoder.encode_query)(retriever.params, jnp.asarray(queries))
    q = retriever.policy.cast_compute(q).astype(jnp.float32)
    reps = jnp.asarray(np.asarray(store.reps).astype(np.float32))
    s = jnp.dot(q, reps.T, precision=jax.lax.Precision.HIGHEST)
    s = jnp.where(jnp.asarray(store.row_valid)[None, :], s, -jnp.inf)
    scores, ids = jax.lax.top_k(s, k)
    return np.asarray(scores), np.asarray(ids)


def serve_phase(name, argv, clock, devices, n_check=32):
    """One ``serve.main`` run, checked against the float32 reference; returns
    the served (ids, scores)."""
    import numpy as np

    from repro.launch import serve

    mark = clock.mark()
    retriever, stats = serve.main(argv)
    ids, scores = stats["ids"], stats["scores"]
    ref_s, ref_i = reference_topk(retriever, stats["queries"][:n_check], ids.shape[1])
    got_s, got_i = scores[:n_check], ids[:n_check]
    sep = separated(ref_s, SCORE_RTOL, SCORE_ATOL)
    ids_ok = bool(np.all(got_i[sep] == ref_i[sep]))
    scores_ok = bool(np.allclose(got_s, ref_s, rtol=SCORE_RTOL, atol=SCORE_ATOL))
    report(
        name, qps=stats["qps"], batch_mean=stats["batch_mean"],
        checked_queries=n_check, separated_ranks=int(sep.sum()),
        ids_match=ids_ok, scores_match=scores_ok,
        max_score_err=float(np.max(np.abs(got_s - ref_s))),
        peak_bytes_in_use=peak_bytes(devices), **clock.since(mark),
    )
    check(ids_ok, f"{name}: ids differ from the float32 reference")
    check(scores_ok, f"{name}: scores differ from the float32 reference")
    return ids, scores


def one_chip(clock, devices, train_argv=TRAIN_ARGV, serve_argv=SERVE_ARGV):
    dense = train_phase("train/dense", train_argv + ["--loss-impl", "dense"], clock, devices)
    fused_argv = train_argv + ["--loss-impl", "fused"]
    fused = train_phase("train/fused", fused_argv, clock, devices)
    rel = abs(fused[0] - dense[0]) / abs(dense[0])
    has_kernel = fused_step_has_kernel(fused_argv)
    report("train/agree", first_loss_dense=dense[0], first_loss_fused=fused[0],
           rel_diff=rel, fused_step_has_tpu_custom_call=has_kernel)
    check(rel <= BF16_RTOL, f"first-step losses disagree: {dense[0]} vs {fused[0]}")
    check(has_kernel, "fused train step has no tpu_custom_call")

    for impl in ("dense", "fused"):
        serve_phase(f"serve/{impl}", serve_argv + ["--search-impl", impl], clock, devices)


def four_chips(clock, devices, train_argv=SHARDED_TRAIN_ARGV, serve_argv=SERVE_ARGV):
    import numpy as np

    single = train_phase("train/single", train_argv + ["--local-batch", "128"], clock, devices)
    for comm in ("all_gather", "ring"):
        losses = train_phase(
            f"train/dp4/{comm}",
            train_argv + ["--local-batch", "32", "--dp", "4", "--shard-banks",
                          "--loss-comm", comm],
            clock, devices,
        )
        ok = bool(np.allclose(losses, single, rtol=DIST_RTOL))
        report(f"train/dp4/{comm}/agree", single=single, sharded=losses, match=ok)
        check(ok, f"dp4 {comm} losses {losses} differ from single-device {single}")

    rep_ids, _ = serve_phase("serve/replicated", serve_argv, clock, devices)
    shard_ids, _ = serve_phase("serve/sharded4", serve_argv + ["--dp", "4"], clock, devices)
    same = bool(np.array_equal(rep_ids, shard_ids))
    report("serve/sharded4/agree", ids_equal_replicated=same)
    check(same, "4-way sharded index ids differ from the replicated index")
    report("memory", peak_bytes_in_use=peak_bytes(devices),
           bytes_in_use=[(d.memory_stats() or {}).get("bytes_in_use") for d in devices])


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-bank and sharded-index paths "
                         "on a four-chip host")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    want = 4 if args.four_chips else 1
    if platform != "tpu" or len(devices) < want:
        print(f"chip_smoke: needs {want} TPU device(s), JAX found "
              f"{len(devices)} {platform} device(s)", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import setup_compile_cache

    print(f"compile cache: {setup_compile_cache()}", flush=True)
    clock = CompileClock(jax)
    if args.four_chips:
        jax.config.update("jax_default_matmul_precision", "highest")
        four_chips(clock, devices[:4])
    else:
        one_chip(clock, devices[:1])
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
