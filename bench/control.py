#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell, in
one process on the chips the cell asks for.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds 5

For every seed it runs the cell as ``bench/run.py`` does (a short window)
and records the numbers compared, the program's readings. For the control
seeds it also records:

* the control: the plain reference put in the program's place, computed
  one precision below what the configuration states (fp8 matrix-product
  inputs where the program computes in bf16), compared with the float32
  reference as the program is;
* the planted faults the cell can have. Training: half of every batch left
  out, the mean taken over the rest (the reference over the first K/2
  chunks); the gradients of half the chunks dropped from the accumulation,
  with every chunk's loss and bank push kept (the reference accumulating
  the first K/2 chunks' gradients, divided by K); a state left unchanged
  reads 1 on ``change_gap`` by definition and needs no run. Serving: one
  answer altered where it is produced (the reference's own top-k with its
  first id moved to the next row); the top-1 id served at every rank.

One JSON line per seed goes to standard output and to
``chiprun_out/control_<cell>.jsonl``.
"""

import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
# the gradient norms each training record keeps, as read
NORMS = ("grad_norms", "grad1_norms", "grad1")


def train_extra(r, res):
    from bench.drivers import train
    from bench.harness import compare, reference

    ex = res["extra"]
    ref = ex["reference"]
    k = r.workload["total_batch"] // r.workload["local_batch"]
    ctl = train.reference_readings(r, ex["batches"], cast=reference.fp8)
    half = train.reference_readings(r, ex["batches"], chunks_used=k // 2)
    dropped = train.reference_readings(r, ex["batches"], grad_chunks=k // 2)
    return {"control": compare.train_readings(ctl, ref),
            "faults": {"half_batch": compare.train_readings(half, ref),
                       "grads_dropped": compare.train_readings(dropped, ref),
                       "state_unchanged": {"change_gap": 1.0}},
            "norms": {name: {k: x[k] for k in NORMS}
                      for name, x in (("control", ctl), ("half_batch", half),
                                      ("grads_dropped", dropped))}}


def train_norms(res):
    """The gradient norms of the program and the reference, as read."""
    ex = res["extra"]
    return {name: {k: x[k] for k in NORMS}
            for name, x in (("program", ex["program"]), ("reference", ex["reference"]))}


def serve_extra(r, res):
    import numpy as np

    from bench.drivers import serve
    from bench.harness import compare, reference

    ex = res["extra"]
    ref, tokens, rows, block = ex["reference"], ex["tokens"], ex["rows"], ex["block"]
    n_valid = r.workload["index_rows"]

    def rescored(ids):
        return dict(ref, served_ref_s=serve.reference_search(r, tokens, ids, rows, block)["served_ref_s"])

    low = serve.reference_search(r, tokens, ex["served_ids"], rows, block, cast=reference.fp8)
    out = {"control": compare.serve_readings(low["top_i"], low["top_s"], rescored(low["top_i"]))}
    altered = ref["top_i"].copy()
    altered[:, 0] = (altered[:, 0] + 1) % n_valid
    repeated = np.repeat(ref["top_i"][:, :1], ref["top_i"].shape[1], axis=1)
    out["faults"] = {
        "answer_altered": compare.serve_readings(altered, ref["top_s"], rescored(altered)),
        "top1_repeated": compare.serve_readings(
            repeated, np.repeat(ref["top_s"][:, :1], repeated.shape[1], axis=1),
            rescored(repeated))}
    return out


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from bench.harness.spec import Cell

    cell = Cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"control: cell {cell.name} needs {cell.chips} TPU chip(s)", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", os.environ["JAX_COMPILATION_CACHE_DIR"])
    # the reference's program is over JAX's default 192 MiB entry limit
    jax.config.update("jax_compilation_cache_max_size", 4 << 30)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.harness.session import Run

    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    train_cell = cell.config["driver"] == "train"
    extra = train_extra if train_cell else serve_extra
    driver = cell.driver()
    with open(out_dir / f"control_{cell.name}.jsonl", "a") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            t = time.perf_counter()
            r = Run(cell, seed, args.seconds, False, devices[: cell.chips], t)
            res = driver.run(r)
            line = {"seed": seed, "correct": res["correct"],
                    "program": {k: c["value"] for k, c in res["checks"].items()},
                    "end_to_end": res["end_to_end"], "setup_s": r.setup_s()}
            if train_cell:
                line["norms"] = train_norms(res)
            if seed in controls:
                extra_ = extra(r, res)
                if train_cell:
                    line["norms"].update(extra_.pop("norms"))
                line.update(extra_)
            line["seconds"] = time.perf_counter() - t
            text = json.dumps(line, default=float)
            print(text, flush=True)
            log.write(text + "\n")
            log.flush()
            del res
    return 0


if __name__ == "__main__":
    sys.exit(main())
