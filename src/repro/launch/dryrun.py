import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# The lines above MUST run before any other import (including repro.*):
# jax locks the device count at first initialization, and the dry-run needs
# 512 placeholder host devices to build the production meshes, on the CPU
# even where a chip is attached.

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the single-pod (16,16) and multi-pod (2,16,16) production meshes, and record
memory_analysis / cost_analysis / collective traffic for the roofline report.

Usage:
  python -m repro.launch.dryrun --list
  python -m repro.launch.dryrun --arch internlm2-1.8b --shape train_4k
  python -m repro.launch.dryrun --all [--mesh single|multi|both]
  python -m repro.launch.dryrun --all --out experiments/dryrun

Each cell writes experiments/dryrun/<mesh>/<arch>__<shape>.json; failures are
recorded with the exception text (a failure here is a sharding bug in the
framework, not an environment problem).
"""

import argparse
import json
import time
import traceback

import jax

from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_cell, list_cells


def run_cell(arch_id: str, shape_name: str, mesh, mesh_name: str, out_dir: str,
             *, verbose: bool = True, extra_tag: str = "") -> dict:
    n_devices = mesh.devices.size
    rec = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": mesh_name,
        "n_devices": int(n_devices),
        "ok": False,
    }
    t0 = time.time()
    try:
        prog = build_cell(arch_id, shape_name, mesh)
        rec["kind"] = prog.kind
        rec["static_info"] = {
            k: (float(v) if isinstance(v, (int, float)) else v)
            for k, v in prog.static_info.items()
        }
        jitted = jax.jit(prog.fn, donate_argnums=prog.donate_argnums)
        lowered = jitted.lower(*prog.args)
        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()

        raw_flops, raw_bytes = H.cost_numbers(compiled)
        mem = H.memory_numbers(compiled)
        stats = H.analyze_hlo(compiled.as_text(), n_devices)
        roof = H.roofline(stats, raw_flops=raw_flops, raw_bytes=raw_bytes)
        flops = stats.flops

        model_flops = float(prog.static_info.get("model_flops", 0.0))
        global_flops = flops * n_devices
        rec.update(
            ok=True,
            lower_s=round(t_lower - t0, 2),
            compile_s=round(t_compile - t_lower, 2),
            memory=mem,
            roofline=roof.as_dict(),
            top_collectives=stats.top_collectives,
            top_hbm=stats.top_hbm,
            model_flops=model_flops,
            useful_flops_ratio=(
                model_flops / global_flops if global_flops else None
            ),
        )
    except Exception as e:  # a failed cell is a bug; record and continue
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["wall_s"] = round(time.time() - t0, 2)

    if out_dir:
        os.makedirs(os.path.join(out_dir, mesh_name), exist_ok=True)
        tag = f"__{extra_tag}" if extra_tag else ""
        path = os.path.join(
            out_dir, mesh_name, f"{arch_id}__{shape_name}{tag}.json"
        )
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    if verbose:
        if rec["ok"]:
            r = rec["roofline"]
            mem_gb = rec["memory"].get("total_bytes", 0) / 2**30
            print(
                f"[{mesh_name}] {arch_id}/{shape_name}: OK "
                f"compile={rec['compile_s']}s mem/dev={mem_gb:.2f}GiB "
                f"t_comp={r['t_compute']:.3e}s t_mem={r['t_memory']:.3e}s "
                f"t_coll={r['t_collective']:.3e}s dom={r['dominant']}",
                flush=True,
            )
        else:
            print(
                f"[{mesh_name}] {arch_id}/{shape_name}: FAIL {rec['error']}",
                flush=True,
            )
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--only-family", default=None,
                    help="lm|bert|gnn|recsys filter for --all")
    args = ap.parse_args()

    if args.list:
        for a, s in list_cells():
            print(f"{a:24s} {s}")
        return

    cells = (
        list_cells()
        if args.all
        else [(args.arch, args.shape)]
    )
    if args.only_family:
        from repro.configs import get_arch

        cells = [
            (a, s) for a, s in cells if get_arch(a).family == args.only_family
        ]
    if not cells or cells[0][0] is None:
        ap.error("pass --all or --arch/--shape")

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh(multi_pod=False)))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16", make_production_mesh(multi_pod=True)))

    n_ok = n_fail = 0
    for mesh_name, mesh in meshes:
        for arch_id, shape_name in cells:
            if args.skip_existing:
                path = os.path.join(
                    args.out, mesh_name, f"{arch_id}__{shape_name}.json"
                )
                if os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("ok"):
                            continue
            rec = run_cell(arch_id, shape_name, mesh, mesh_name, args.out)
            n_ok += rec["ok"]
            n_fail += not rec["ok"]
    print(f"\ndry-run: {n_ok} ok, {n_fail} failed", flush=True)
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
