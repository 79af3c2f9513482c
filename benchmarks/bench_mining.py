"""Hard-negative mining benchmark (suite ``mining``).

Three questions about the repro/mining subsystem, answered with numbers:

  1. **Refresh cost** — wall time of one full refresh (corpus re-encode +
     top-k mining + teleportation filtering + table publish) as the corpus
     grows. The warm number is the steady-state cadence cost; the first
     refresh (compile included) is reported as an info row because compile
     time is environment noise.
  2. **Async vs blocking** — what the background pipeline buys: the median
     time the refresh hook holds the *training thread* in async mode
     (``hook_ms`` — a param snapshot + thread start, or a skip while one is
     in flight) vs the full blocking refresh a sync miner pays there
     (``refresh_block_ms`` — which includes draining the dispatched step
     queue before the snapshot, the honest cost of stopping training to
     mine), and how many training steps ran concurrently with the last
     async refresh (``steps_overlapped`` — the acceptance row: >= 1 means
     training really does overlap mining).
  3. **Does mining help?** — identical training budgets with in-batch
     negatives only vs with mined columns joined into every batch (sync
     refreshes, deterministic), then one exact recall@{1,10,100} eval per
     run. ``recall10_delta`` > 0 is the paper-facing claim: fresher, harder
     negatives beat in-batch sampling at equal step count.

The mined run follows the ANCE recipe this subsystem exists for — and the
teleportation knobs are load-bearing, not decorative: on this corpus
(256 passages, ~8 passages per topic) mining with ``depth_lo=1`` or with
``margin=0`` from a cold encoder *collapses* training (recall@10 drops to
~0.03 — every mined "negative" is a topic-mate the noisy query genuinely
matches, so the loss pushes queries out of their own topic cluster). A
warm-up before the first refresh, a band past the topic-mates
(``[8, 24)``) and a score margin make the same pipeline strictly beat the
in-batch baseline. Both failure and fix are the bench's point.

Time rows (``*_ms``) are regression-checked at the standard 15% tolerance;
recall and overlap rows are info rows (quality trends, not perf gates).
"""

from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import fmt_table
from repro.core.methods import build_step_program, init_state
from repro.core.types import ContrastiveConfig, RetrievalBatch
from repro.data.loader import MinedNegativeInjector, ShardedLoader
from repro.data.retrieval import SyntheticRetrievalCorpus
from repro.evaluation import evaluate_topk
from repro.models.bert import tiny_bert
from repro.mining import HardNegativeMiner, MinerConfig
from repro.models.towers import make_bert_dual_encoder
from repro.optim import adamw, chain, clip_by_global_norm

BATCH = 32


def _miner_cfg(sync: bool, refresh_every: int = 16,
               margin: float = 2.0) -> MinerConfig:
    # band [8, 24): past the corpus's ~8 topic-mates per passage; margin 2.0
    # additionally drops candidates the model can't yet separate from gold
    # (false-negative guard — see the module docstring for what happens
    # without these)
    return MinerConfig(
        refresh_every=refresh_every, top_k=24, n_negatives=4,
        depth_lo=8, depth_hi=24, margin=margin, sync=sync, query_batch=256,
    )


def _refresh_latency(enc, params, quick: bool):
    """Warm refresh wall time vs corpus size (one compiled shape each)."""
    out, table = [], []
    for n in ((256, 1024) if quick else (1024, 4096)):
        corpus = SyntheticRetrievalCorpus(n_passages=n, q_len=16, p_len=32)
        miner = HardNegativeMiner(
            enc, _miner_cfg(sync=True),
            queries=corpus.queries, passages=corpus.passages,
        )
        t0 = time.perf_counter()
        miner.refresh(params, 0)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        miner.refresh(params, 1)
        warm = time.perf_counter() - t0
        out += [
            (f"mining/refresh/np{n}/warm_ms", warm * 1e3),
            # compile-inclusive first refresh: info row (environment noise)
            (f"mining/refresh/np{n}/cold_over_warm", cold / warm),
        ]
        table.append((n, f"{warm * 1e3:.1f}", f"{cold * 1e3:.1f}"))
    print("\n== mining: refresh latency vs corpus size ==")
    print(fmt_table(table, ("n_passages", "warm_ms", "cold_ms")))
    return out


def _train(enc, corpus, steps: int, *, mined: bool, sync: bool,
           refresh_every: int, warmup: int = 0, seed: int = 0):
    """One fixed-budget training run; returns (final params, miner, ms spent
    inside each refresh-hook call on the training thread). ``warmup`` delays
    the first refresh (ANCE warm-up: mine only once the encoder is past its
    random phase); refreshes then fire every ``refresh_every`` steps."""
    cfg = ContrastiveConfig(
        method="dpr", negatives="mined" if mined else None, temperature=1.0
    )
    tx = chain(clip_by_global_norm(2.0), adamw(2e-3))
    update = jax.jit(build_step_program(enc, tx, cfg).update)
    state = init_state(jax.random.PRNGKey(seed), enc, tx, cfg)
    loader = ShardedLoader(corpus.n_passages, BATCH, seed=seed)

    miner = injector = None
    if mined:
        miner = HardNegativeMiner(
            enc, _miner_cfg(sync=sync, refresh_every=refresh_every),
            queries=corpus.queries, passages=corpus.passages,
        )
        injector = MinedNegativeInjector(
            miner.buffer.read, corpus.n_passages, seed=seed,
            state=loader.state, on_step=miner.note_step,
        )

    first = max(warmup, refresh_every)
    hook_ms = []
    for step in range(steps):
        idx = loader.next_indices()
        b = corpus.batch(idx)
        hard = b["passage_hard"]
        if injector is not None:
            ids = injector.mined_ids(idx, gold=idx, step=step)
            hard = np.concatenate([hard, corpus.passages[ids]], axis=1)
        state, _ = update(state, RetrievalBatch(
            query=jnp.asarray(b["query"]),
            passage_pos=jnp.asarray(b["passage_pos"]),
            passage_hard=jnp.asarray(hard),
        ))
        if (miner is not None and step + 1 >= first
                and (step + 1 - first) % refresh_every == 0):
            t0 = time.perf_counter()
            miner.refresh_hook(state, step)
            hook_ms.append((time.perf_counter() - t0) * 1e3)
    if miner is not None:
        miner.wait()  # drain (and surface) any in-flight refresh
    params = jax.device_get(state.params)
    return params, miner, hook_ms


def run(quick: bool = False) -> List[Tuple[str, float]]:
    enc = make_bert_dual_encoder(tiny_bert())
    params = enc.init(jax.random.PRNGKey(0))
    out = _refresh_latency(enc, params, quick)

    corpus = SyntheticRetrievalCorpus(n_passages=256, q_len=16, p_len=32)

    # async vs blocking: same budget, same cadence, opposite execution mode.
    # The median hook time keeps the first refresh's compile out of the
    # regression-gated number (it dominates the mean on a cold cache).
    _, m_async, kicks = _train(
        enc, corpus, 32, mined=True, sync=False, refresh_every=8
    )
    _, m_sync, blocks = _train(
        enc, corpus, 32, mined=True, sync=True, refresh_every=8
    )
    hook_ms = float(np.median(kicks))
    block_ms = float(np.median(blocks))
    out += [
        ("mining/async/hook_ms", hook_ms),
        ("mining/sync/refresh_block_ms", block_ms),
        # acceptance row: the last async refresh overlapped >= 1 train step
        ("mining/async/steps_overlapped", float(m_async.last_overlap)),
        ("mining/async/refreshes", float(m_async.refreshes)),
        ("mining/async/skipped", float(m_async.skipped)),
    ]
    print("\n== mining: async vs blocking refresh ==")
    print(fmt_table(
        [("async", f"{hook_ms:.1f}", str(m_async.last_overlap),
          str(m_async.refreshes)),
         ("sync", f"{block_ms:.1f}", "0", str(m_sync.refreshes))],
        ("mode", "train-thread ms/refresh (median)", "steps overlapped",
         "refreshes"),
    ))

    # mined vs in-batch at an identical step budget (sync = deterministic).
    # 96 steps regardless of --quick: the comparison is only meaningful once
    # the in-batch baseline itself has learned something to beat.
    steps, warmup, every = 96, 32, 16
    p_mined, _, _ = _train(
        enc, corpus, steps, mined=True, sync=True,
        refresh_every=every, warmup=warmup,
    )
    p_base, _, _ = _train(
        enc, corpus, steps, mined=False, sync=True, refresh_every=every
    )
    ks = (1, 10, 100)
    r_mined = evaluate_topk(enc, p_mined, corpus, ks=ks)
    r_base = evaluate_topk(enc, p_base, corpus, ks=ks)
    for k in ks:
        out += [
            (f"mining/recall{k}/in_batch", r_base[f"recall@{k}"]),
            (f"mining/recall{k}/mined", r_mined[f"recall@{k}"]),
        ]
    out.append((
        "mining/recall10_delta", r_mined["recall@10"] - r_base["recall@10"]
    ))
    print("\n== mining: mined vs in-batch negatives "
          f"({steps} steps, warm-up {warmup}, refresh every {every}) ==")
    print(fmt_table(
        [(f"recall@{k}", f"{r_base[f'recall@{k}']:.4f}",
          f"{r_mined[f'recall@{k}']:.4f}") for k in ks],
        ("cutoff", "in_batch", "mined"),
    ))
    return out


if __name__ == "__main__":
    import sys

    run(quick="--quick" in sys.argv)
