"""Serving cells: single-query requests through ``make_server`` (the
program's ``BatchingServer``) over ``Retriever.search`` on an index the
benchmark generated from the seed, under open-loop arrivals.

Set-up makes the query tower's weights and the index on the device, builds
the Retriever and its server as a user would, and runs the one padded
batch shape the server uses until it no longer compiles. The window then
sends ``rate * seconds`` requests at due times fixed by the seed, whether or
not earlier ones have finished, and waits for each, at most ``drain_s``
past the window's close. A request is timed from when it was due until its
result was delivered.

``correct``: once the window has closed and the index is freed, a sample of
the delivered requests, drawn from the seed, is run through the plain
float32 reference (query encode, then exact scores over every index row,
regenerated block by block). Two numbers are compared, both in units of
the query's reference norm (the spread of a score, since index rows are
unit normal): the widest gap by which a served id's reference score lies
below the reference's score at that rank, and the widest gap between a
served score and the reference score of that id.
"""

from __future__ import annotations

import gc
import queue
import threading
import time
from functools import partial

import numpy as np

from bench.drivers.train import longest
from bench.harness import compare, counts, reference, traffic, weights


def layout(wl: dict, block: int) -> tuple:
    """(rows, shards): the index padded to whole scan blocks on every shard,
    so the search never copies it to pad (padding rows are masked by
    ``row_valid``). ``shards`` above 1 is the sharded index of the
    four-chip cell that PERF.md lists for a later PR;
    tests/bench/test_bench_sharded.py drives it on four CPU devices."""
    shards = wl["shards"]
    per = -(-wl["index_rows"] // (block * shards)) * block
    return per * shards, shards


def build(r):
    """The Retriever and its server over the seed's weights and index."""
    import jax
    import jax.numpy as jnp

    from repro.configs import bert_tower
    from repro.models.towers import make_bert_dual_encoder
    from repro.retrieval import IndexStore, Retriever, RetrieverConfig, make_dp_mesh, make_server

    from bench.drivers.train import check_widths

    cfg, wl, model = r.config, r.workload, r.config["model"]
    p = cfg["program"]
    tower = bert_tower(p["arch"])
    check_widths(tower, model)
    enc = make_bert_dual_encoder(tower, precision=p["precision"])
    sharded = wl["shards"] > 1
    rcfg = RetrieverConfig(top_k=wl["top_k"], search_impl=wl["search_impl"],
                           index_layout="sharded" if sharded else "replicated",
                           precision=p["precision"])
    mesh = make_dp_mesh(wl["shards"]) if sharded else None
    block = rcfg.resolve_backend().block
    rows, shards = layout(wl, block)
    key = weights.root_key(r.seed)
    params = weights.tower_params(key, model, towers=("query",))
    dtype = jnp.dtype(rcfg.resolved_index_dtype())
    reps = weights.index_rows(key, rows // block, block, model["hidden_size"], dtype, mesh=mesh)
    valid = jnp.arange(rows) < wl["index_rows"]
    if sharded:
        from jax.sharding import NamedSharding, PartitionSpec as P

        params = jax.device_put(params, NamedSharding(mesh, P()))
        valid = jax.device_put(valid, NamedSharding(mesh, P(rcfg.dp_axis)))
    store = IndexStore(reps=reps, row_valid=valid, n_total=wl["index_rows"], shards=shards)
    retriever = Retriever(enc, params, rcfg, mesh=mesh, index=store)
    server = make_server(retriever, max_batch=wl["max_batch"])
    return retriever, server, block, rows


def wrap_spans(r, server):
    """Wrap the server's two calls in host spans: collecting a batch, and
    the serve function that searches it. Returns the times at which each
    search returned."""
    collect, serve_fn = server._collect, server.serve_fn
    returned = []

    def collect_w():
        with r.span("bench.collect"):
            return collect()

    def serve_w(payloads):
        with r.span("bench.serve_fn"):
            out = serve_fn(payloads)
        returned.append(time.perf_counter())
        return out

    server._collect = collect_w
    server.serve_fn = serve_w
    return returned


def open_loop(r, server, tokens, offsets, drain_s):
    """Send request i at ``t0 + offsets[i]`` from a generator thread; wait
    for each result on this thread. Returns (sent, done, results); ``done``
    is NaN for a request that failed or was not delivered by the deadline."""
    n = len(offsets)
    t0 = r.t0
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    results = [None] * n
    handoff: "queue.Queue" = queue.Queue()

    def generate():
        for i in range(n):
            delay = t0 + offsets[i] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            sent[i] = time.perf_counter()
            handoff.put(server.submit(tokens[i]))

    gen = threading.Thread(target=generate, name="bench-generator", daemon=True)
    gen.start()
    deadline = t0 + r.seconds + drain_s
    for i in range(n):
        fut = handoff.get()
        try:
            res = fut.get(timeout=max(deadline - time.perf_counter(), 0.0))
        except queue.Empty:
            break
        now = time.perf_counter()
        if not isinstance(res, Exception):
            done[i], results[i] = now, res
        r.maybe_stop_trace(now)
    gen.join()
    return sent, done, results


def run(r):
    """One run of a serving cell; ``r`` is the harness's run context."""
    wl, model = r.workload, r.config["model"]
    retriever, server, block, rows = build(r)
    offsets = traffic.poisson_offsets(wl["rate_qps"], r.seconds, r.seed)
    tokens = traffic.query_tokens(len(offsets), wl["q_len"], model["vocab_size"], r.seed)
    for _ in range(wl["warmup_batches"]):
        retriever.search(tokens[:wl["max_batch"]])
    returned = wrap_spans(r, server)
    server.start()
    r.open_window()
    sent, done, results = open_loop(r, server, tokens, offsets, wl["drain_s"])
    r.close_window(at=np.nanmax(done) if np.isfinite(done).any() else None)
    server.stop()

    due = r.t0 + offsets
    delivered = np.isfinite(done)
    deadline = r.t0 + r.seconds + wl["drain_s"]
    latency = np.where(delivered, done, deadline) - due
    cut = r.host_window_end()
    read = due < cut
    in_window = [(t, n) for t, n in zip(returned, server.batch_sizes) if t < cut]
    flops, bytes_ = counts.search_work(wl["max_batch"], wl["q_len"], rows // wl["shards"], model)
    layer = {
        "generator_lag_s": (sent - due)[read & np.isfinite(sent)],
        "batch_sizes": [n for _, n in in_window],
        "max_batch": wl["max_batch"],
        "search_least_s": r.least_time(flops, bytes_),
        "window_s_host": cut - r.t0,
        "memory_peak_bytes": r.memory_peak_bytes(),
    }
    e2e = {"serve_p95_ms": float(np.percentile(latency, 95) * 1e3)}
    lag = sent - due
    worst = np.argsort(np.nan_to_num(lag, nan=np.inf))[-3:]
    r.note("longest intervals between searches (window offset s, interval s)",
           longest([t - r.t0 for t in returned if t >= r.t0]),
           "latest sends (due offset s, lag s)",
           [(round(float(offsets[i]), 3), round(float(lag[i]), 3)) for i in worst])

    del retriever, server
    gc.collect()
    pick = sample(r.seed, np.flatnonzero(delivered), wl["check_requests"])
    served_ids = np.stack([results[i][0] for i in pick])
    served_s = np.stack([results[i][1] for i in pick])
    ref = reference_search(r, tokens[pick], served_ids, rows, block)
    checks = compare.with_limits(compare.serve_readings(served_ids, served_s, ref), wl["limits"])
    missing = int((~delivered).sum())
    return {
        "correct": compare.all_within(checks) and missing == 0,
        "attempted": len(offsets),
        "failed": missing,
        "end_to_end": e2e,
        "layer": layer,
        "checks": checks,
        "extra": {"tokens": tokens[pick], "served_ids": served_ids, "served_s": served_s,
                  "reference": ref, "rows": rows, "block": block},
    }


def sample(seed: int, ids: np.ndarray, n: int) -> np.ndarray:
    return np.sort(traffic.rng(seed, 3).choice(ids, size=min(n, len(ids)), replace=False))


def reference_search(r, tokens, served_ids, rows, block, cast=reference.identity):
    """Reference (or, with another ``cast``, control) answers for the
    sampled requests: the query norms, the exact top-k scores and ids, and
    the fp32 score of each served id. Also the control's own served scores
    when ``cast`` is not the identity."""
    import jax.numpy as jnp

    model, wl = r.config["model"], r.workload
    key = weights.root_key(r.seed)
    params = weights.tower_params(key, model, towers=("query",))["query"]
    q = reference.encode_queries(params, jnp.asarray(tokens), model=reference.frozen(model),
                                 cast=cast)
    dtype = jnp.dtype(r.config["program"]["index_dtype"])
    make_block = partial(_index_block, block=block, dim=model["hidden_size"], dtype=dtype)
    top_s, top_i, served = reference.topk(q, key, make_block, rows // block, block,
                                          wl["index_rows"], wl["top_k"], served_ids, cast=cast)
    return {"q_norm": np.linalg.norm(np.asarray(q), axis=1), "top_s": top_s, "top_i": top_i,
            "served_ref_s": served}


def _index_block(key, b, *, block, dim, dtype):
    return weights.index_block(key, b, block, dim, dtype)
