#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for an LM tower's
training cell (bench/drivers/train_lm.py), in one process on one chip.

    python3 bench/control_lm.py --workload train-moonlight-ep8 --seeds 1,2,... \
        --control-seeds 1 --seconds 5

For every seed it runs the cell as ``bench/run.py`` does (a short window)
and records the numbers compared, the program's readings. For the control
seeds it also records, against the same float32 reference
(``train_lm.controls``): the reference in fp8 matrix-product inputs (one
precision below the bf16 the configuration states), and the reference with
one fault planted: two of the expert layer, tokens past each expert's even
share dropped (dispatch by capacity factor 1.0) and gates taken from the
biased scores that chose the experts; two of the accumulation, as
``bench/control.py`` plants them for the bert cells, half of every batch
left out and half the chunks' gradients dropped. A state left unchanged
reads 1 on ``change_gap`` by definition and needs no run.

One JSON line per seed goes to standard output and to
``chiprun_out/control_<cell>.jsonl``.
"""

import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


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
    jax.config.update("jax_compilation_cache_max_size", 4 << 30)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.harness.session import Run

    out_dir = ROOT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    driver = cell.driver()
    with open(out_dir / f"control_{cell.name}.jsonl", "a") as log:
        for seed in (int(s) for s in args.seeds.split(",")):
            t = time.perf_counter()
            r = Run(cell, seed, args.seconds, False, devices[: cell.chips], t)
            res = driver.run(r)
            ex = res["extra"]
            line = {"seed": seed, "correct": res["correct"],
                    "program": {k: c["value"] for k, c in res["checks"].items()},
                    "end_to_end": res["end_to_end"], "setup_s": r.setup_s(),
                    "memory_peak_bytes": res["layer"]["memory_peak_bytes"],
                    "losses": {"program": ex["program"]["losses"],
                               "reference": ex["reference"]["losses"]},
                    "grad_norms": {"program": ex["program"]["grad_norms"],
                                   "reference": ex["reference"]["grad_norms"]}}
            if seed in controls:
                line["controls"] = driver.controls(r, res)
            line["seconds"] = time.perf_counter() - t
            text = json.dumps(line, default=float)
            print(text, flush=True)
            log.write(text + "\n")
            log.flush()
            del res, ex
    return 0


if __name__ == "__main__":
    sys.exit(main())
