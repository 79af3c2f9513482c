"""PrecisionPolicy memory/speed sweep: fp32 vs bf16 vs bf16_banks,
replicated and sharded banks (suite ``precision``).

For each (policy, bank layout) the harness trains the paper's method
(contaccum) for a few steps on 8 forced host-platform devices under the
shard_map StepProgram path and reports:

  * per-device persistent bank bytes — the axis the policy exists to cut:
    fp32 replicated = (N_q+N_p)·d·4 on every chip; bf16_banks halves it,
    sharding divides by D, and the two compose to /(2·D);
  * per-evaluation representation bytes (compute-dtype activations: the
    local chunk's q/p/hard reps plus the gathered bank column block — the
    rep_cache store and the loss inputs scale with this);
  * mean step wall time (host-platform CPU: a sanity signal, not a TPU
    number — bf16 matmuls on CPU are emulated and often *slower*).

Also emits ``precision/bank_reduction_vs_fp32_pct`` rows: the acceptance
criterion is >= 40% per-device bank-byte reduction for bf16_banks vs the
fp32 replicated baseline (the measured value is 50%, and 93.75% combined
with 8-way sharding).

Runs in a subprocess because the 8-device host platform must be forced via
XLA_FLAGS before jax is first imported (same isolation pattern as
benchmarks/bench_distributed.py).
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
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import (
        ContrastiveConfig, RetrievalBatch, bank_bytes_per_device,
        resolve_precision,
    )
    from repro.core.methods import build_step_program, init_state
    from repro.distribution.sharding import contrastive_state_spec
    from repro.models.bert import BertConfig
    from repro.models.towers import make_bert_dual_encoder
    from repro.optim import chain, clip_by_global_norm, sgd

    quick = "--quick" in sys.argv
    D = 8
    assert jax.device_count() == D, jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("data",))

    B, K, QL, PL = 64, 2, 16, 32
    steps, warmup = (3, 1) if quick else (6, 2)
    bank = 1024 if quick else 4096

    bcfg = BertConfig(
        name="bench-bert", n_layers=2, d_model=64, n_heads=4, d_ff=128,
        vocab_size=2000, max_position=64, dtype=jnp.float32,
    )

    def make_batch(i):
        rng = np.random.default_rng(i)
        return RetrievalBatch(
            query=jnp.asarray(rng.integers(0, 2000, (B, QL), dtype=np.int32)),
            passage_pos=jnp.asarray(rng.integers(0, 2000, (B, PL), dtype=np.int32)),
            passage_hard=None,
        )

    def bench(precision, shard_banks):
        policy = resolve_precision(precision)
        cfg = ContrastiveConfig(
            method="contaccum", accumulation_steps=K, bank_size=bank,
            precision=policy, dp_axis=("data",), shard_banks=shard_banks,
        )
        enc = make_bert_dual_encoder(bcfg, precision=policy)
        tx = chain(clip_by_global_norm(2.0), sgd(0.05))
        state = init_state(jax.random.PRNGKey(0), enc, tx, cfg)
        spec = contrastive_state_spec(("data",), shard_banks)
        bspec = RetrievalBatch(query=P("data"), passage_pos=P("data"),
                               passage_hard=None)
        update = jax.jit(jax.shard_map(
            build_step_program(enc, tx, cfg).update, mesh=mesh,
            in_specs=(spec, bspec), out_specs=(spec, P()), check_vma=False,
        ))
        for i in range(warmup):
            state, m = update(state, make_batch(i))
        jax.block_until_ready(m.loss)
        t0 = time.perf_counter()
        for i in range(warmup, warmup + steps):
            state, m = update(state, make_batch(i))
        jax.block_until_ready(m.loss)
        dt_ms = (time.perf_counter() - t0) / steps * 1e3
        assert np.isfinite(float(m.loss)), (precision, shard_banks)

        # persistent bank bytes: from the actual state (dtype included)
        assert state.bank_p.buf.dtype == policy.bank_dtype
        nq = state.bank_q.buf.shape[0]
        np_rows = state.bank_p.buf.shape[0]
        shards = D if shard_banks else 1
        bank_dev = bank_bytes_per_device(
            nq, np_rows, enc.rep_dim, policy, shards=shards
        )
        # compute-dtype representation bytes per loss evaluation: the local
        # chunk's rows + the assembled column block (gathered bank columns)
        c_item = jnp.dtype(policy.compute_dtype).itemsize
        rows = B // D // K + (nq // shards)
        cols = B // K + np_rows
        rep_dev = (rows + cols) * enc.rep_dim * c_item

        mode = "sharded" if shard_banks else "replicated"
        for metric, val in (
            ("bank_kib_per_dev", bank_dev / 1024.0),
            ("rep_kib_per_eval", rep_dev / 1024.0),
            ("step_ms", dt_ms),
        ):
            print(f"ROW precision/{precision}/{mode}/{metric} {val:.6g}",
                  flush=True)
        return bank_dev

    baseline = None
    for precision in ("fp32", "bf16", "bf16_banks"):
        for shard_banks in (False, True):
            bank_dev = bench(precision, shard_banks)
            if precision == "fp32" and not shard_banks:
                baseline = bank_dev
            else:
                red = 100.0 * (1.0 - bank_dev / baseline)
                mode = "sharded" if shard_banks else "replicated"
                print(f"ROW precision/{precision}/{mode}/"
                      f"bank_reduction_vs_fp32_pct {red:.6g}", flush=True)
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
        timeout=1800,
    )
    if proc.returncode != 0 or "BENCH-DONE" not in proc.stdout:
        raise RuntimeError(
            f"bench_precision subprocess failed:\n{proc.stdout}\n{proc.stderr}"
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
