"""Replicated vs sharded memory banks, and all-gather vs ring loss comm,
under the shard_map StepProgram path.

Two measurements, both on forced host-platform devices:

  * **step sweep** (8 devices): per (method, bank, mode) — per-device bank
    bytes (replicated banks cost (N_q + N_p) * d * itemsize on EVERY chip, a
    sharded one 1/D of that) and mean step wall time. ``mode`` is
    ``replicated``, ``sharded`` (all-gather loss comm) or ``ring``
    (``loss_comm='ring'``: shards streamed around the DP ring).

  * **transient bytes** (D in {2, 4, 8}): compiled temp buffer bytes of one
    fused-backend loss evaluation (value_and_grad), via XLA's
    ``compile().memory_analysis()`` — the same inspection
    tests/test_hlo_analysis.py uses. This is the number the ring path
    exists to shrink: the all-gather path materializes the full
    (N_mem, d) passage-column block per eval (flat in D), the ring path
    peaks at one N_mem/D shard (~1/D scaling).

Runs in subprocesses because the forced device count must be set via
XLA_FLAGS before jax is first imported (benchmarks.run imports jax early),
mirroring the tests/test_distributed.py isolation pattern; the transient
sweep needs one subprocess per D.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap
from typing import List, Tuple

STEP_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    import time
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core import ContrastiveConfig, RetrievalBatch
    from repro.core.methods import build_step_program, init_state
    from repro.distribution.sharding import contrastive_state_spec
    from repro.models.bert import BertConfig
    from repro.models.towers import make_bert_dual_encoder
    from repro.optim import chain, clip_by_global_norm, sgd

    quick = "--quick" in sys.argv
    D = 8
    assert jax.device_count() == D, jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("data",))

    enc = make_bert_dual_encoder(BertConfig(
        name="bench-bert", n_layers=2, d_model=64, n_heads=4, d_ff=128,
        vocab_size=2000, max_position=64, dtype=jnp.float32,
    ))
    B, K, QL, PL = 64, 2, 16, 32
    # same timing window in quick mode: one warmup step still pays host
    # thread-pool/autotune amortization, inflating step_ms 2-8x vs the
    # committed baselines (quick saves by shrinking the method x bank
    # matrix instead, which is where the wall time actually goes)
    steps, warmup = 6, 2
    banks = [2048] if quick else [2048, 8192]

    def make_batch(i):
        rng = np.random.default_rng(i)
        return RetrievalBatch(
            query=jnp.asarray(rng.integers(0, 2000, (B, QL), dtype=np.int32)),
            passage_pos=jnp.asarray(rng.integers(0, 2000, (B, PL), dtype=np.int32)),
            passage_hard=None,
        )

    # mode -> (shard_banks, loss_comm)
    MODES = {
        "replicated": (False, "all_gather"),
        "sharded": (True, "all_gather"),
        "ring": (True, "ring"),
    }

    def bench(method, bank, mode):
        shard_banks, loss_comm = MODES[mode]
        cfg = ContrastiveConfig(
            method=method, accumulation_steps=K, bank_size=bank,
            dp_axis=("data",), shard_banks=shard_banks, loss_comm=loss_comm,
        )
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

        nq = state.bank_q.buf.shape[0]
        np_rows = state.bank_p.buf.shape[0]
        itemsize = jnp.dtype(cfg.resolved_bank_dtype()).itemsize
        per_dev = (nq + np_rows) * enc.rep_dim * itemsize
        if shard_banks:
            per_dev //= D
        print(f"ROW dist/{method}/bank{bank}/{mode}/bank_kib_per_dev "
              f"{per_dev / 1024.0:.6g}", flush=True)
        print(f"ROW dist/{method}/bank{bank}/{mode}/step_ms {dt_ms:.6g}",
              flush=True)

    for method in ("contaccum",) if quick else ("contaccum", "contcache"):
        for bank in banks:
            for mode in MODES:
                bench(method, bank, mode)
    print("BENCH-DONE")
    """
)

# One loss evaluation (fused backend, passage-bank columns only — isolating
# the column-communication path the two loss_comm modes differ in) lowered +
# compiled per mode: the per-device temp buffer bytes are read straight off
# XLA's memory analysis, no execution. ``base`` (no bank at all) bounds the
# bank-independent footprint so the bank-attributable transient is the
# difference. ``loss_fwd`` is the forward eval; ``loss_grad`` adds the VJP
# (whose ring bwd re-streams the shards instead of saving them).
TRANSIENT_SCRIPT = textwrap.dedent(
    """
    import os
    import sys
    D = int(sys.argv[1])
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={D}"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    from repro.core.dist import DistCtx
    from repro.core.loss import (
        FusedLossBackend, contrastive_loss, sharded_bank_extra_columns,
    )
    from repro.core.memory_bank import BankState

    N_MEM, REP_D, B_LOCAL = 2048, 64, 8
    assert jax.device_count() == D, jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("data",))
    ctx = DistCtx(("data",))
    backend = FusedLossBackend(interpret=True)

    B = B_LOCAL * D
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, REP_D)), jnp.float32)
    pp = jnp.asarray(rng.standard_normal((B, REP_D)), jnp.float32)
    pbuf = jnp.asarray(rng.standard_normal((N_MEM, REP_D)), jnp.float32)
    valid = jnp.ones((N_MEM,), bool)
    age = jnp.zeros((N_MEM,), jnp.int32)
    head = jnp.zeros((), jnp.int32)

    def make_eval(comm, grad):
        def eval_loss(q, pp, pbuf, valid, age, head):
            extra = None
            if comm is not None:
                bank_p = BankState(buf=pbuf, valid=valid, head=head, age=age)
                extra = sharded_bank_extra_columns(bank_p, ctx, comm)

            def f(q):
                loss, _ = contrastive_loss(
                    q, pp, extra_cols=extra,
                    temperature=0.5, ctx=ctx, backend=backend,
                )
                return loss

            if grad:
                return jax.value_and_grad(f)(q)
            return f(q), q

        row = P("data")
        return jax.jit(jax.shard_map(
            eval_loss, mesh=mesh,
            in_specs=(row, row, row, row, row, P()),
            out_specs=(P(), row), check_vma=False,
        ))

    for grad in (False, True):
        stage = "loss_grad" if grad else "loss_fwd"
        for comm in (None, "all_gather", "ring"):
            compiled = make_eval(comm, grad).lower(
                q, pp, pbuf, valid, age, head
            ).compile()
            mem = compiled.memory_analysis()
            temp = getattr(mem, "temp_size_in_bytes", 0)
            name = comm or "base"
            print(f"ROW dist/transient/D{D}/{name}/{stage}_temp_kib "
                  f"{temp / 1024.0:.6g}", flush=True)
    print("BENCH-DONE")
    """
)

TRANSIENT_DS = (2, 4, 8)


def _subprocess_rows(argv, timeout=1200) -> List[Tuple[str, float]]:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    env.pop("XLA_FLAGS", None)
    # a CPU-harness child: on a chip host the parent already holds the chip
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        argv,
        capture_output=True,
        text=True,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=timeout,
    )
    if proc.returncode != 0 or "BENCH-DONE" not in proc.stdout:
        raise RuntimeError(
            f"bench_distributed subprocess failed:\n{proc.stdout}\n{proc.stderr}"
        )
    rows: List[Tuple[str, float]] = []
    for line in proc.stdout.splitlines():
        if not line.startswith("ROW "):
            continue
        _, name, value = line.split()
        rows.append((name, float(value)))
    return rows


def run(quick: bool = False) -> List[Tuple[str, float]]:
    rows = _subprocess_rows(
        [sys.executable, "-c", STEP_SCRIPT] + (["--quick"] if quick else [])
    )
    # the transient sweep is compile-only (cheap) and its 1/D scaling is the
    # headline number of the ring path, so it always covers every D
    for d in TRANSIENT_DS:
        rows += _subprocess_rows([sys.executable, "-c", TRANSIENT_SCRIPT, str(d)])
    print(f"{'cell':<48} {'value':>12}")
    for name, value in rows:
        print(f"{name:<48} {value:>12.4g}")
    return rows


if __name__ == "__main__":
    run(quick="--quick" in sys.argv)
