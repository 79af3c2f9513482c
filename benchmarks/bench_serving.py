"""Serving sweep on the Retriever API (suite ``serving``).

For every (precision x index layout x search backend) point the harness
builds a Retriever on 8 forced host-platform devices, serves a fixed query
stream through the dynamic-batching server, and reports:

  * qps and p50/p99 request latency (submit -> result, measured at the
    future);
  * the coalesced-batch histogram (mean/max — the _collect fix means a
    backed-up queue fills batches instead of degrading to size 1);
  * persistent index bytes per device — the serving memory axis: bf16 index
    rows halve it, row-block sharding divides by D, composed: /(2·D).

Acceptance (ISSUE 5): sharded bf16 index bytes/device <= 12.5% of the
replicated fp32 baseline on 8 devices — the measured value is 6.25%
(bf16 halves, 8-way sharding divides by 8). Emitted as
``serving/<precision>/<layout>/index_reduction_vs_fp32_pct`` rows.

Runs in a subprocess because the 8-device host platform must be forced via
XLA_FLAGS before jax is first imported (same isolation pattern as
benchmarks/bench_precision.py).
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from typing import List, Tuple

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    import time
    import jax
    import numpy as np

    from repro.data.retrieval import SyntheticRetrievalCorpus
    from repro.models.bert import tiny_bert
    from repro.models.towers import make_bert_dual_encoder
    from repro.retrieval import (
        Retriever, RetrieverConfig, make_dp_mesh, make_server,
    )

    quick = "--quick" in sys.argv
    D = 8
    assert jax.device_count() == D, jax.device_count()
    mesh = make_dp_mesh(D)

    n_passages = 1024 if quick else 4096
    n_queries = 32 if quick else 96
    corpus = SyntheticRetrievalCorpus(n_passages=n_passages, q_len=16, p_len=32)

    def bench(precision, layout, impl):
        enc = make_bert_dual_encoder(tiny_bert(), precision=precision)
        params = enc.init(jax.random.PRNGKey(0))
        rcfg = RetrieverConfig(
            top_k=20, search_impl=impl, index_layout=layout,
            precision=precision, encode_batch=256,
            score_block=1024, block_n=256,
        )
        r = Retriever(enc, params, rcfg,
                      mesh=mesh if layout == "sharded" else None)
        store = r.build_index(corpus.passages)
        server = make_server(r, max_batch=16, max_wait_s=0.01).start()
        try:
            r.search(corpus.queries[:16])   # warm the compile cache
            lat = []
            t0 = time.perf_counter()
            futs = [
                (time.perf_counter(), server.submit(corpus.queries[i]))
                for i in range(n_queries)
            ]
            for t_sub, fut in futs:
                fut.get(timeout=120)
                lat.append(time.perf_counter() - t_sub)
            dt = time.perf_counter() - t0
        finally:
            server.stop()
        sizes = np.asarray(server.batch_sizes)
        cell = f"serving/{precision}/{layout}/{impl}"
        for metric, val in (
            ("qps", n_queries / dt),
            ("p50_ms", float(np.percentile(lat, 50)) * 1e3),
            ("p99_ms", float(np.percentile(lat, 99)) * 1e3),
            ("batch_mean", float(sizes.mean())),
            ("batch_max", float(sizes.max())),
            ("index_kib_per_dev", store.bytes_per_device() / 1024.0),
        ):
            print(f"ROW {cell}/{metric} {val:.6g}", flush=True)
        return store.bytes_per_device()

    baseline = None
    for precision in ("fp32", "bf16_banks"):
        for layout in ("replicated", "sharded"):
            for impl in ("dense", "fused"):
                idx_dev = bench(precision, layout, impl)
            # index bytes are impl-independent; report reduction per layout
            if precision == "fp32" and layout == "replicated":
                baseline = idx_dev
            else:
                red = 100.0 * (1.0 - idx_dev / baseline)
                print(f"ROW serving/{precision}/{layout}/"
                      f"index_reduction_vs_fp32_pct {red:.6g}", flush=True)
    print("BENCH-DONE")
    """
)


def run(quick: bool = False) -> List[Tuple[str, float]]:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env.pop("XLA_FLAGS", None)
    # a CPU-harness child: on a chip host the parent already holds the chip
    env["JAX_PLATFORMS"] = "cpu"
    argv = [sys.executable, "-c", SCRIPT] + (["--quick"] if quick else [])
    proc = subprocess.run(
        argv,
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=2400,
    )
    if proc.returncode != 0 or "BENCH-DONE" not in proc.stdout:
        raise RuntimeError(
            f"bench_serving subprocess failed:\n{proc.stdout}\n{proc.stderr}"
        )
    rows: List[Tuple[str, float]] = []
    print(f"{'cell':<58} {'value':>12}")
    for line in proc.stdout.splitlines():
        if not line.startswith("ROW "):
            continue
        _, name, value = line.split()
        rows.append((name, float(value)))
        print(f"{name:<58} {float(value):>12.4g}")
    return rows


if __name__ == "__main__":
    run(quick="--quick" in sys.argv)
