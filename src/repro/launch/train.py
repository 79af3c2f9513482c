"""Training driver: the paper's dense-retriever training (any of the four
methods) on synthetic or DPR-format data, wired through the fault-tolerant
Trainer. CPU-runnable end to end at reduced scale (the default ``--arch
bert-tiny`` tower); the same step functions lower for the production meshes
via launch/dryrun.py.

  PYTHONPATH=src python -m repro.launch.train \
      --method contaccum --total-batch 128 --local-batch 8 --bank 512 \
      --steps 200 --checkpoint-dir /tmp/ckpt

The paper's model and geometry (bert-base-uncased towers from the config
registry, N_total=128, N_local=8, N_mem=2048, q_len 32, p_len 256) on a TPU:

  PYTHONPATH=src python -m repro.launch.train --arch dpr-bert-base \
      --method contaccum --total-batch 128 --local-batch 8 --bank 2048 \
      --q-len 32 --p-len 256 --precision bf16_banks --loss-impl fused

An LM retriever tower through the same path: one shared Moonlight-16B-A3B
backbone (one chip's share: 8 of its 64 experts, a fifth of its depth, an
eighth of its vocabulary), mean-pooled:

  PYTHONPATH=src python -m repro.launch.train --arch moonlight-16b-a3b-ep8 \
      --method contaccum --total-batch 128 --local-batch 32 --bank 2048 \
      --q-len 32 --p-len 256 --precision bf16_banks --lr 2e-5

Data-parallel shard_map path (requires >= N devices, e.g.
XLA_FLAGS=--xla_force_host_platform_device_count=8 on CPU): ``--dp N``
shards the batch over an N-way mesh with cross-device in-batch negatives;
``--shard-banks`` additionally gives each device a bank/N shard of the
memory banks instead of replicating them (core/step_program.py);
``--loss-comm ring`` then streams those shards around the DP ring at loss
time instead of all-gathering them (core/loss.py).

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train \
      --method contaccum --dp 8 --shard-banks --total-batch 64 --bank 256

Asynchronous hard-negative mining (repro/mining): ``--negatives mined``
spins up a ``HardNegativeMiner`` that periodically re-encodes the corpus
with a snapshot of the training params on a background thread and publishes
per-query hard negatives; the loader joins them into every batch as extra
``passage_hard`` columns. Composes with any --method — with a bank method
(e.g. contaccum) the banks keep extending the matrix *and* every batch
carries mined columns:

  PYTHONPATH=src python -m repro.launch.train \
      --method contaccum --negatives mined --mine-every 50 --mine-topk 32
"""

from __future__ import annotations

import argparse
import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import TINY_BERT, bert_tower, get_arch, lm_tower_archs, tower_archs
from repro.core.methods import (
    available_methods,
    build_step_program,
    init_state,
    method_composition,
    method_needs_mesh,
    method_uses_banks,
)
from repro.core.precision import PRECISION_PRESETS
from repro.core.types import ContrastiveConfig, RetrievalBatch
from repro.data.loader import ShardedLoader
from repro.data.retrieval import SyntheticRetrievalCorpus
from repro.launch.compile_cache import setup_compile_cache
from repro.models.towers import make_bert_dual_encoder, make_lm_dual_encoder
from repro.optim.adamw import adamw, chain, clip_by_global_norm
from repro.optim.schedules import linear_warmup_linear_decay
from repro.runtime.trainer import Trainer, TrainerConfig


@dataclasses.dataclass
class TrainStep:
    """The jitted update (state donated) and what its state is built from."""

    tower: Any
    enc: Any
    tx: Any
    cfg: ContrastiveConfig
    update: Any


@dataclasses.dataclass
class TrainRun:
    """Everything ``main`` runs: the jitted update, its initial state, and
    the Trainer that drives it (plus the miner, when mining)."""

    update: Any
    state: Any
    trainer: Trainer
    miner: Optional[Any] = None


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=TINY_BERT, choices=tower_archs(),
                    help="tower config from the registry (repro/configs): "
                         "bert-tiny for CPU runs, dpr-bert-base for the "
                         "paper's bert-base-uncased towers, or an LM "
                         "backbone shared by both sides and mean-pooled "
                         "(moonlight-16b-a3b-ep8; moonlight-tiny on CPU)")
    # mesh-requiring compositions can't build in this single-program driver;
    # only offer methods it can actually run
    methods = [m for m in available_methods() if not method_needs_mesh(m)]
    ap.add_argument("--method", default="contaccum", choices=methods)
    ap.add_argument("--loss-impl", default="dense", choices=["dense", "fused"],
                    help="loss backend (core/loss.py): dense einsum or the "
                         "blocked Pallas online-softmax kernel")
    ap.add_argument("--precision", default="fp32",
                    choices=sorted(PRECISION_PRESETS),
                    help="PrecisionPolicy preset (core/precision.py): fp32 "
                         "(reference), bf16 (bf16 compute, fp32 masters/"
                         "banks), bf16_banks (bf16 compute AND bf16 bank "
                         "buffers — halves persistent bank bytes)")
    ap.add_argument("--total-batch", type=int, default=64)
    ap.add_argument("--local-batch", type=int, default=8)
    ap.add_argument("--bank", type=int, default=256)
    ap.add_argument("--dp", type=int, default=0,
                    help="shard_map the update over N data-parallel devices "
                         "(0 = single-program; needs jax.device_count() >= N)")
    ap.add_argument("--shard-banks", action="store_true",
                    help="shard the memory banks over the DP mesh "
                         "(bank/N rows per device) instead of replicating")
    ap.add_argument("--loss-comm", default="all_gather",
                    choices=["all_gather", "ring"],
                    help="how sharded bank columns reach the loss (needs "
                         "--shard-banks): all_gather materializes the full "
                         "(bank, d) block per eval; ring streams one bank/N "
                         "shard at a time around the DP ring via ppermute "
                         "with an online-softmax merge — exact, peak "
                         "transient O(bank*d/N) instead of O(bank*d)")
    ap.add_argument("--negatives", default=None, choices=["mined"],
                    help="override the method's negative source: 'mined' "
                         "runs the asynchronous hard-negative miner "
                         "(repro/mining) and injects its table into every "
                         "batch; bank methods keep their banks on top")
    ap.add_argument("--mine-every", type=int, default=50,
                    help="trainer steps between mining refreshes")
    ap.add_argument("--mine-topk", type=int, default=32,
                    help="mining search depth per query (>= band upper edge)")
    ap.add_argument("--mine-negatives", type=int, default=4,
                    help="mined negatives injected per query per batch")
    ap.add_argument("--mine-band", type=int, nargs=2, default=None,
                    metavar=("LO", "HI"),
                    help="teleportation band [LO, HI) of gold-excluded ranks "
                         "(default [1, mine-topk))")
    ap.add_argument("--mine-margin", type=float, default=0.0,
                    help="drop mined candidates scoring within this margin "
                         "of the gold passage (false-negative guard)")
    ap.add_argument("--mine-sync", action="store_true",
                    help="refresh synchronously on the training thread "
                         "(deterministic; default is the async background "
                         "pipeline)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--lr", type=float, default=2e-4)
    ap.add_argument("--corpus-size", type=int, default=2048)
    ap.add_argument("--q-len", type=int, default=16,
                    help="query tokens (<= the tower's max_position)")
    ap.add_argument("--p-len", type=int, default=32,
                    help="passage tokens (<= the tower's max_position)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def _mines(args) -> bool:
    source, _ = method_composition(args.method)
    return args.negatives == "mined" or source == "mined"


def build_step(args) -> TrainStep:
    """Validate the flags and build the jitted update; allocates nothing."""
    dp = args.dp
    if args.shard_banks and not dp:
        raise SystemExit("--shard-banks needs --dp N (banks shard over the DP mesh)")
    if args.shard_banks and not method_uses_banks(args.method):
        raise SystemExit(f"--shard-banks: method {args.method!r} has no memory banks")
    if args.loss_comm == "ring" and not args.shard_banks:
        raise SystemExit("--loss-comm ring needs --shard-banks (it streams "
                         "the per-device bank shards around the DP ring)")
    if dp:
        if jax.device_count() < dp:
            raise SystemExit(
                f"--dp {dp} needs >= {dp} devices (have {jax.device_count()}; "
                f"on CPU set XLA_FLAGS=--xla_force_host_platform_device_count={dp})"
            )
        if args.total_batch % dp:
            raise SystemExit(f"--total-batch {args.total_batch} not divisible by --dp {dp}")
        if args.shard_banks and args.bank % dp:
            raise SystemExit(f"--bank {args.bank} not divisible by --dp {dp}")

    mine = _mines(args)
    # with a bank method the banks stay the source and mined columns ride
    # the batch (contaccum x mined); otherwise the source becomes 'mined'
    negatives = (
        "mined" if mine and not method_uses_banks(args.method) else None
    )

    bank = args.bank if method_uses_banks(args.method) else 0
    # with --dp the per-device batch is total/dp; accumulation chunks split
    # the *local* batch so K still targets --local-batch rows per chunk
    k = max(args.total_batch // max(dp, 1) // args.local_batch, 1)
    _, backprop = method_composition(args.method)
    cfg = ContrastiveConfig(
        method=args.method,
        negatives=negatives,
        accumulation_steps=k if backprop != "direct" else 1,
        bank_size=bank,
        loss_impl=args.loss_impl,
        precision=args.precision,
        temperature=1.0,
        grad_clip_norm=2.0,
        dp_axis="data" if dp else None,
        shard_banks=bool(args.shard_banks and dp and bank),
        loss_comm=args.loss_comm,
    )
    if args.arch in lm_tower_archs():
        tower = get_arch(args.arch).model_cfg
        enc = make_lm_dual_encoder(tower, precision=args.precision)
    else:
        tower = bert_tower(args.arch)
        if max(args.q_len, args.p_len) > tower.max_position:
            raise SystemExit(
                f"--q-len/--p-len exceed {args.arch}'s max_position "
                f"{tower.max_position}"
            )
        enc = make_bert_dual_encoder(tower, precision=args.precision)
    tx = chain(
        clip_by_global_norm(cfg.grad_clip_norm),
        adamw(linear_warmup_linear_decay(args.lr, args.steps // 10, args.steps)),
    )
    program = build_step_program(enc, tx, cfg)
    update = program.update
    if dp:
        from jax.sharding import Mesh, PartitionSpec as P

        from repro.core.types import RetrievalBatch as RB
        from repro.distribution.sharding import contrastive_state_spec

        mesh = Mesh(np.array(jax.devices()[:dp]), ("data",))
        state_spec = contrastive_state_spec(("data",), cfg.shard_banks)
        batch_spec = RB(query=P("data"), passage_pos=P("data"), passage_hard=P("data"))
        update = jax.shard_map(
            update,
            mesh=mesh,
            in_specs=(state_spec, batch_spec),
            out_specs=(state_spec, P()),
            check_vma=False,
        )
    update = jax.jit(update, donate_argnums=(0,))
    return TrainStep(tower=tower, enc=enc, tx=tx, cfg=cfg, update=update)


def build(args) -> TrainRun:
    """Assemble the update, its initial state, the data and the Trainer."""
    ts = build_step(args)
    mine = _mines(args)
    state = init_state(jax.random.PRNGKey(args.seed), ts.enc, ts.tx, ts.cfg)

    corpus = SyntheticRetrievalCorpus(
        n_passages=args.corpus_size, vocab_size=ts.tower.vocab_size,
        q_len=args.q_len, p_len=args.p_len, seed=args.seed,
    )
    loader = ShardedLoader(args.corpus_size, args.total_batch, seed=args.seed)

    miner = None
    injector = None
    hooks = []
    if mine:
        from repro.data.loader import MinedNegativeInjector
        from repro.mining import HardNegativeMiner, MinerConfig
        from repro.runtime.trainer import PeriodicHook

        band = args.mine_band or (1, args.mine_topk)
        mcfg = MinerConfig(
            refresh_every=args.mine_every,
            top_k=args.mine_topk,
            n_negatives=args.mine_negatives,
            depth_lo=band[0],
            depth_hi=band[1],
            margin=args.mine_margin,
            sync=args.mine_sync,
            precision=args.precision,
        )
        # corpus alignment: query i's gold passage IS passage i
        miner = HardNegativeMiner(
            ts.enc, mcfg, queries=corpus.queries, passages=corpus.passages
        )
        injector = MinedNegativeInjector(
            miner.buffer.read,
            corpus.n_passages,
            seed=args.seed,
            state=loader.state,
            on_step=miner.note_step,
        )
        # not advisory: a failed refresh fails the run instead of training
        # on silently stale negatives
        hooks.append(
            PeriodicHook(
                every=mcfg.refresh_every,
                fn=miner.refresh_hook,
                prefix="mine/",
                name="mine",
                advisory=False,
            )
        )

    def next_batch(step):
        idx = loader.next_indices()
        b = corpus.batch(idx)
        hard = b["passage_hard"]
        if injector is not None:
            mined_ids = injector.mined_ids(idx, gold=idx, step=step)
            hard = np.concatenate([hard, corpus.passages[mined_ids]], axis=1)
        return RetrievalBatch(
            query=jnp.asarray(b["query"]),
            passage_pos=jnp.asarray(b["passage_pos"]),
            passage_hard=jnp.asarray(hard),
        )

    trainer = Trainer(
        TrainerConfig(
            total_steps=args.steps,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_every=args.checkpoint_every,
        ),
        ts.update,
        next_batch,
        loader_state=loader.state,
        hooks=hooks,
        aux_state=miner,
    )
    return TrainRun(update=ts.update, state=state, trainer=trainer, miner=miner)


def main(argv=None):
    args = parse_args(argv)
    setup_compile_cache()
    run = build(args)
    state, report = run.trainer.run(run.state)
    miner = run.miner
    if miner is not None:
        miner.close()
        print(
            f"mining: {miner.refreshes} refreshes, {miner.skipped} skipped, "
            f"last refresh overlapped {miner.last_overlap} steps"
        )
    print(
        f"done: {report.steps_run} steps, {report.restarts} restarts, "
        f"final loss {report.final_metrics.get('loss', float('nan')):.4f}, "
        f"final grad-norm ratio "
        f"{report.final_metrics.get('grad_norm_ratio', float('nan')):.3f}"
    )
    return state, report


if __name__ == "__main__":
    main()
