"""Serving driver on the Retriever API: load a trainer checkpoint (or init
fresh), build the passage index, start the dynamic-batching server, and run
a load test with single-query requests. CPU-runnable end to end.

  PYTHONPATH=src python -m repro.launch.serve --n-passages 1024 --n-queries 64

Serve a model trained by launch/train.py (pass both the same ``--arch``):

  PYTHONPATH=src python -m repro.launch.train --steps 100 --checkpoint-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.serve --ckpt /tmp/ckpt

The paper's bert-base-uncased towers over a bf16 index on a TPU:

  PYTHONPATH=src python -m repro.launch.serve --arch dpr-bert-base \
      --precision bf16_banks --n-passages 32768 --q-len 32 --p-len 256

Sharded bf16 index over an 8-way DP mesh with the fused Pallas search
kernel (on CPU force the host devices first):

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.serve \\
      --dp 8 --precision bf16_banks --search-impl fused
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import TINY_BERT, bert_archs, bert_tower
from repro.core.precision import PRECISION_PRESETS
from repro.data.retrieval import SyntheticRetrievalCorpus
from repro.launch.compile_cache import setup_compile_cache
from repro.models.towers import make_bert_dual_encoder
from repro.retrieval import (
    Retriever,
    RetrieverConfig,
    load_trained_params,
    make_dp_mesh,
    make_server,
)


def main(argv=None):
    """Returns (retriever, stats); next to the load-test numbers, stats
    carries the served queries and their (ids, scores), in submission
    order."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=TINY_BERT, choices=bert_archs(),
                    help="tower config from the registry (repro/configs); "
                         "must match the checkpoint's")
    ap.add_argument("--ckpt", default=None,
                    help="runtime/trainer.py checkpoint dir: serve the "
                         "trained params instead of a fresh init")
    ap.add_argument("--dp", type=int, default=0,
                    help="shard the index over an N-way DP mesh (0 = "
                         "replicated; needs jax.device_count() >= N)")
    ap.add_argument("--precision", default="fp32",
                    choices=sorted(PRECISION_PRESETS),
                    help="PrecisionPolicy preset: queries encoded/scored in "
                         "compute dtype, index stored in bank dtype "
                         "(bf16_banks halves index bytes), scores fp32")
    ap.add_argument("--search-impl", default="dense",
                    choices=["dense", "fused"],
                    help="per-device scoring: blocked-scan top-k vs the "
                         "fused Pallas QK^T + running-top-k kernel")
    ap.add_argument("--n-passages", type=int, default=1024)
    ap.add_argument("--n-queries", type=int, default=64)
    ap.add_argument("--top-k", type=int, default=20)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--q-len", type=int, default=16)
    ap.add_argument("--p-len", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    setup_compile_cache()
    cfg = bert_tower(args.arch)
    enc = make_bert_dual_encoder(cfg, precision=args.precision)
    if args.ckpt:
        params, step = load_trained_params(args.ckpt)
        print(f"restored trained params from {args.ckpt} (step {step})")
    else:
        params = enc.init(jax.random.PRNGKey(args.seed))
    corpus = SyntheticRetrievalCorpus(
        n_passages=args.n_passages, vocab_size=cfg.vocab_size,
        q_len=args.q_len, p_len=args.p_len, seed=args.seed,
    )

    rcfg = RetrieverConfig(
        top_k=args.top_k,
        search_impl=args.search_impl,
        index_layout="sharded" if args.dp else "replicated",
        precision=args.precision,
        encode_batch=128,
    )
    mesh = make_dp_mesh(args.dp) if args.dp else None
    retriever = Retriever(enc, params, rcfg, mesh=mesh)

    t0 = time.time()
    store = retriever.build_index(corpus.passages)
    print(
        f"index: {store.reps.shape} ({str(store.reps.dtype)}, "
        f"{store.bytes_per_device()/1024:.0f} KiB/device over "
        f"{store.shards} shard(s)) built in {time.time()-t0:.2f}s"
    )

    # compile the one padded batch shape the server runs before the clock
    # starts, so the load test times serving, not compilation
    t0 = time.time()
    retriever.search(corpus.queries[: args.max_batch])
    print(f"search warm-up (compile + first batch): {time.time()-t0:.2f}s")

    server = make_server(
        retriever, max_batch=args.max_batch
    ).start()
    try:
        t0 = time.time()
        futures = [
            server.submit(corpus.queries[i]) for i in range(args.n_queries)
        ]
        results = [fut.get(timeout=60) for fut in futures]
        dt = time.time() - t0
        for res in results:
            if isinstance(res, Exception):
                raise res
        hits = sum(int(i in ids) for i, (ids, _) in enumerate(results))
        sizes = server.batch_sizes
        stats = {
            "qps": args.n_queries / dt,
            "recall": hits / args.n_queries,
            "batch_mean": float(np.mean(sizes)),
            "batch_max": int(max(sizes)),
            "index_bytes_per_device": store.bytes_per_device(),
            "queries": corpus.queries[: args.n_queries],
            "ids": np.stack([ids for ids, _ in results]),
            "scores": np.stack([scores for _, scores in results]),
        }
        print(
            f"served {args.n_queries} queries in {dt:.2f}s "
            f"({stats['qps']:.1f} qps), top-{args.top_k} recall "
            f"{stats['recall']:.3f}, mean coalesced batch "
            f"{stats['batch_mean']:.1f} (max {stats['batch_max']})"
        )
        return retriever, stats
    finally:
        server.stop()


if __name__ == "__main__":
    main()
