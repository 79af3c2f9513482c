"""Retriever API tests: search-backend parity (dense vs fused vs reference,
ties, k > n, masking), BatchingServer coalescing/padding/flush semantics
(including the backlog regression), eval-path equivalence + bounded memory,
sharded-vs-replicated index parity on 8 host devices, and the end-to-end
trained-checkpoint -> serve -> recall smoke."""

import subprocess
import sys
import textwrap
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused_topk.ops import fused_topk_scores
from repro.kernels.fused_topk.ref import topk_scores_ref
from repro.retrieval import (
    DenseSearchBackend,
    FusedSearchBackend,
    Retriever,
    RetrieverConfig,
    build_index_store,
    load_trained_params,
    make_server,
    resolve_search_backend,
)
from repro.runtime.server import BatchingServer


# ------------------------------------------------------- backend parity
def _rand(q, n, d, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    return (
        rng.normal(size=(q, d)).astype(dtype),
        rng.normal(size=(n, d)).astype(dtype),
    )


@pytest.mark.parametrize("impl,kw", [
    ("dense", {"block": 64}),
    ("fused", {"block_q": 16, "block_n": 64}),
])
def test_backend_matches_reference(impl, kw):
    q, p = _rand(13, 517, 24)
    be = resolve_search_backend(impl, **kw)
    scores, ids = jax.jit(
        lambda a, b: be.topk(a, b, 10)
    )(jnp.asarray(q), jnp.asarray(p))
    ref_s, ref_i = topk_scores_ref(jnp.asarray(q), jnp.asarray(p), 10)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref_i), impl)
    np.testing.assert_allclose(np.asarray(scores), np.asarray(ref_s),
                               rtol=0, atol=1e-5)
    assert np.asarray(scores).dtype == np.float32


def test_dense_fused_parity_with_ties():
    """Quantized reps force exact score ties across blocks; both backends
    must break them toward the lowest column id (lax.top_k semantics)."""
    rng = np.random.default_rng(1)
    q = rng.integers(-2, 3, size=(7, 8)).astype(np.float32)
    p = rng.integers(-2, 3, size=(200, 8)).astype(np.float32)
    p[50] = p[10]           # identical rows in different blocks -> tied scores
    p[130] = p[10]
    dense = DenseSearchBackend(block=32)
    fused = FusedSearchBackend(block_q=8, block_n=32)
    s_d, i_d = dense.topk(jnp.asarray(q), jnp.asarray(p), 12)
    s_f, i_f = fused.topk(jnp.asarray(q), jnp.asarray(p), 12)
    s_r, i_r = topk_scores_ref(jnp.asarray(q), jnp.asarray(p), 12)
    np.testing.assert_array_equal(np.asarray(i_d), np.asarray(i_r))
    np.testing.assert_array_equal(np.asarray(i_f), np.asarray(i_r))
    np.testing.assert_array_equal(np.asarray(s_d), np.asarray(s_f))


@pytest.mark.parametrize("impl,kw", [
    ("dense", {"block": 4}),
    ("fused", {"block_q": 8, "block_n": 4}),
])
def test_backend_k_exceeds_valid_columns(impl, kw):
    """k > n (and k > n_valid): the tail slots must come back with id -1,
    not garbage, and valid slots must still be exact."""
    q, p = _rand(3, 6, 8, seed=2)
    valid = np.array([True, False, True, True, False, True])
    be = resolve_search_backend(impl, **kw)
    scores, ids = be.topk(jnp.asarray(q), jnp.asarray(p), 9,
                          col_valid=jnp.asarray(valid))
    ref_s, ref_i = topk_scores_ref(jnp.asarray(q), jnp.asarray(p), 9,
                                   col_valid=jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(ref_i))
    assert np.all(np.asarray(ids)[:, 4:] == -1)          # only 4 valid columns
    np.testing.assert_allclose(np.asarray(scores)[:, :4],
                               np.asarray(ref_s)[:, :4], atol=1e-5)


def _merge_case(case):
    """(q, p, col_valid or None, k, block) for the two-stage merge cases:
    9,000 rows, so the last 4,096-row block ends in a partly masked group."""
    rng = np.random.default_rng(4)
    n, valid = 9000, None
    q = rng.integers(-2, 3, size=(7, 8)).astype(np.float32)
    p = rng.integers(-2, 3, size=(n, 8)).astype(np.float32)
    k, block = 16, 4096
    if case == "random":
        q, p = _rand(13, n, 24, seed=5)
        k = 10
    elif case == "duplicate_rows":       # ties inside groups, across groups and blocks
        p[[50, 60, 300, 4200, 8300]] = p[10]
    elif case == "ties_past_k_groups":   # every group of every block holds the k-th value
        q, p = np.ones((3, 8), np.float32), np.ones((n, 8), np.float32)
        p[5000] = 2.0
    elif case == "masked_groups":        # scattered masks, whole groups, a whole block's head
        valid = rng.random(n) > 0.3
        valid[256:1024] = False
        valid[4096:4096 + 1000] = False
        k = 10
    elif case == "k_past_valid":
        valid = np.zeros(n, bool)
        valid[[5, 700, 4100, 8999]] = True
    elif case == "fallback":             # 4·k·32 > 3·block: one top_k a block
        k, block = 60, 2048
    return q, p, valid, k, block


@pytest.mark.parametrize("case", [
    "random", "duplicate_rows", "ties_past_k_groups", "masked_groups",
    "k_past_valid", "fallback",
])
def test_dense_two_stage_merge_is_exact(case, monkeypatch):
    """The two-stage block merge (group maxima, k groups, top_k over their
    columns) selects what one top_k over each block does, scores to the
    last bit, and what the full (Q, N) reference does: ids exactly,
    lowest-id ties, -1 for empty slots."""
    from repro.retrieval import search

    q, p, valid, k, block = (jnp.asarray(x) if isinstance(x, np.ndarray) else x
                             for x in _merge_case(case))
    be = DenseSearchBackend(block=block)
    assert (search.merge_group(block, k) is None) == (case == "fallback")

    def run():
        return [np.asarray(x) for x in
                jax.jit(lambda a, b: be.topk(a, b, k, col_valid=valid))(q, p)]

    s, i = run()
    ref_s, ref_i = topk_scores_ref(q, p, k, col_valid=valid)
    monkeypatch.setattr(search, "merge_group", lambda block, k: None)
    one_s, one_i = run()
    np.testing.assert_array_equal(i, np.asarray(ref_i))
    if case == "random":   # the reference's one (Q, N) matmul rounds apart
        np.testing.assert_allclose(s, np.asarray(ref_s), rtol=0, atol=1e-5)
    else:                  # integer scores: exact in any summation order
        np.testing.assert_array_equal(s, np.asarray(ref_s))
    np.testing.assert_array_equal(i, one_i)
    np.testing.assert_array_equal(s, one_s)
    if case == "ties_past_k_groups":
        np.testing.assert_array_equal(i[:, 0], 5000)
        np.testing.assert_array_equal(i[:, 1:], np.tile(np.arange(k - 1), (3, 1)))
    if case == "k_past_valid":
        np.testing.assert_array_equal(np.sort(i[:, :4], axis=1),
                                      np.tile([5, 700, 4100, 8999], (7, 1)))
        assert np.all(i[:, 4:] == -1)


def _sort_widths(hlo_text: str) -> list:
    """The widest dimension of each operand of the sorts and TopK custom
    calls in a compiled program's HLO text."""
    import re

    shapes = dict(re.findall(r"%([\w.-]+) = \w+\[([\d,]*)\]", hlo_text))
    widths = []
    for line in hlo_text.splitlines():
        m = re.search(r"= .*? (?:sort|custom-call)\(([^)]*)\)", line)
        if m and (" sort(" in line or '"TopK"' in line):
            for name in re.findall(r"%([\w.-]+)", m.group(1)):
                widths.append(max(int(x) for x in shapes[name].split(",")))
    return widths


@pytest.mark.parametrize("k,block,width,engaged", [
    (100, 65536, 2048 + 100 + 100 * 32, True),    # the serving cells' shape
    (16, 4096, 128 + 16 + 16 * 32, True),
    (48, 2048, 64 + 48 + 48 * 32, True),         # 4·k·32 = 3·block: the last k in
    (49, 2048, 2048 + 49, False),                 # and the first k out
    (9, 16, 16 + 9, False),
])
def test_dense_merge_width_and_engagement(k, block, width, engaged):
    """``merge_width`` counts the columns a block's merge sorts; where the
    two-stage merge engages, no sort in the compiled search is as wide as
    the block, and its three steps are named inside ``block_topk``."""
    from helpers import op_name_scopes

    be = DenseSearchBackend(block=block)
    assert be.merge_width(k) == width
    if block > 4096:
        return
    q, p = _rand(4, 2 * block + 7, 8)
    text = jax.jit(lambda a, b: be.topk(a, b, k)).lower(
        jnp.asarray(q), jnp.asarray(p)).compile().as_text()
    widths = _sort_widths(text)
    assert widths and (max(widths) < block) == engaged, widths
    nested = ("group_max", "group_select", "candidate_topk")
    found = op_name_scopes(text, ("block_topk",) + nested)
    named = {s for v in found.values() for s in v}
    assert named == ({"block_topk", *nested} if engaged else {"block_topk"}), named
    # one tracked scope an op; the merge's parts only inside block_topk
    nest = [["block_topk", m] for m in nested]
    assert all(len(v) <= 1 and not set(v) & set(nested) or v in nest
               for v in found.values()), found


@pytest.mark.parametrize("k,bn", [
    (5, 16),      # k inside one tile
    (40, 16),     # k spans several tiles
    (130, 64),    # k past one 128-lane running block and past n_valid
    (160, 32),    # k > n: every slot past the valid columns is empty
])
def test_fused_topk_selection_ties_and_empty_slots(k, bn):
    """The kernel's selection (k rounds of row max, lowest id holding it,
    mask it out) on all-equal scores: ids come back 0, 1, 2, ... in order
    whatever the tiling, and slots past the valid columns are NEG_INF, -1."""
    n, n_valid = 150, 100
    q, p = jnp.ones((4, 8)), jnp.ones((n, 8))
    valid = jnp.arange(n) < n_valid          # a masked tail of whole tiles
    s, i = fused_topk_scores(q, p, k, col_valid=valid, block_q=8,
                             block_n=bn, interpret=True)
    s_r, i_r = topk_scores_ref(q, p, k, col_valid=valid)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_r))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_r))
    want = np.where(np.arange(k) < n_valid, np.arange(k), -1)
    np.testing.assert_array_equal(np.asarray(i), np.broadcast_to(want, (4, k)))


def test_fused_bf16_index_well_separated_ids_exact():
    """bf16 queries/index (the bf16_banks serving path): ids stay exact when
    scores are separated beyond bf16 rounding; scores match the bf16
    reference matmul to documented tolerance (inputs rounded, accumulation
    fp32)."""
    rng = np.random.default_rng(3)
    d = 16
    p = rng.normal(size=(64, d)).astype(np.float32)
    p *= (1.0 + np.arange(64))[:, None]          # well-separated norms
    q = rng.normal(size=(5, d)).astype(np.float32)
    qb, pb = jnp.asarray(q, jnp.bfloat16), jnp.asarray(p, jnp.bfloat16)
    s_f, i_f = fused_topk_scores(qb, pb, 8, block_q=8, block_n=16,
                                 interpret=True)
    s_r, i_r = topk_scores_ref(qb, pb, 8)
    np.testing.assert_array_equal(np.asarray(i_f), np.asarray(i_r))
    np.testing.assert_allclose(np.asarray(s_f), np.asarray(s_r),
                               rtol=2e-2, atol=1e-2)
    assert np.asarray(s_f).dtype == np.float32   # fp32-scores contract


def test_resolve_search_backend_rejects_unknown():
    with pytest.raises(ValueError, match="unknown search_impl"):
        resolve_search_backend("faiss")
    with pytest.raises(ValueError, match="index_layout"):
        Retriever(None, None, RetrieverConfig(index_layout="interleaved"))
    with pytest.raises(ValueError, match="mesh"):
        Retriever(None, None, RetrieverConfig(index_layout="sharded"))


# ------------------------------------------------------------ index store
def test_index_store_pads_and_masks():
    reps = np.arange(10 * 4, dtype=np.float32).reshape(10, 4)
    store = build_index_store(
        lambda toks: jnp.asarray(toks, jnp.float32), reps,
        batch=4, dtype=jnp.bfloat16, shards=4,
    )
    assert store.reps.shape == (12, 4) and store.reps.dtype == jnp.bfloat16
    # the store stays on the host (the full matrix must never land on one
    # device; the Retriever device_puts straight into the target layout)
    assert isinstance(store.reps, np.ndarray)
    assert isinstance(store.row_valid, np.ndarray)
    assert store.n_total == 10 and store.rows_per_shard == 3
    assert np.asarray(store.row_valid).sum() == 10
    # bf16 + 4 shards: 12*4*2/4 bytes
    assert store.bytes_per_device() == 12 * 4 * 2 // 4


# ------------------------------------------------------------- batching
def test_batching_server_coalesces_backlog():
    """Regression for the _collect coalescing-under-backlog bug: the flush
    deadline was computed from the first request's *submit* time, so a
    backed-up queue degraded every batch to size 1. Pre-fill the queue
    before starting the worker: every batch must come out full."""
    done = threading.Event()

    def serve(batch):
        done.wait()          # hold the first batch until the queue backs up
        return np.arange(len(batch))[:, None], batch.sum(axis=1, keepdims=True)

    srv = BatchingServer(serve, max_batch=8, max_wait_s=0.001)
    futs = [srv.submit(np.full((4,), float(i))) for i in range(32)]
    time.sleep(0.05)         # all 32 requests sit in the queue (backlog)
    srv.start()
    done.set()
    try:
        for f in futs:
            f.get(timeout=10)
        assert list(srv.batch_sizes) == [8, 8, 8, 8], srv.batch_sizes
    finally:
        srv.stop()


def test_batching_server_pads_to_compiled_shape_and_flushes():
    """A lone request must flush after ~max_wait_s padded to max_batch (one
    compiled shape), and each caller gets only its own row back."""
    seen = []

    def serve(batch):
        seen.append(batch.shape)
        return np.tile(batch[:, :1], (1, 3)), batch.sum(axis=1, keepdims=True)

    srv = BatchingServer(serve, max_batch=4, max_wait_s=0.02).start()
    try:
        t0 = time.monotonic()
        ids, scores = srv.query(np.full((2,), 7.0), timeout=10)
        assert time.monotonic() - t0 < 5.0
        assert seen[0] == (4, 2)             # padded to the compiled shape
        assert ids.shape == (3,) and np.all(ids == 7.0)
        assert scores.shape == (1,)
    finally:
        srv.stop()


# ---------------------------------------------------------- eval rewire
def _mlp_encoder(d_in=12, d=8):
    """Tiny deterministic linear dual encoder over float 'token' vectors."""
    from repro.core.types import DualEncoder

    def init(rng):
        kq, kp = jax.random.split(rng)
        return {
            "query": jax.random.normal(kq, (d_in, d)) * 0.5,
            "passage": jax.random.normal(kp, (d_in, d)) * 0.5,
        }

    return DualEncoder(
        init=init,
        encode_query=lambda p, x: x @ p["query"],
        encode_passage=lambda p, x: x @ p["passage"],
        rep_dim=d,
    )


class _VecCorpus:
    """eval_split-compatible corpus over raw float vectors."""

    def __init__(self, n=96, d_in=12, seed=0):
        rng = np.random.default_rng(seed)
        self.n_passages = n
        self.passages = rng.normal(size=(n, d_in)).astype(np.float32)
        self.queries = (
            self.passages + 0.05 * rng.normal(size=(n, d_in))
        ).astype(np.float32)

    def eval_split(self, n=16):
        idx = np.arange(self.n_passages - n, self.n_passages)
        return self.queries[idx], self.passages, idx


def test_evaluate_topk_matches_legacy_full_argsort():
    """The Retriever-backed eval must reproduce the old full (Q, N) score
    matrix + argsort path exactly, for both backends."""
    from repro.evaluation import evaluate_topk

    enc = _mlp_encoder()
    params = enc.init(jax.random.PRNGKey(0))
    corpus = _VecCorpus()
    queries, passages, gold = corpus.eval_split(
        n=min(256, corpus.n_passages // 4)
    )
    q = np.asarray(enc.encode_query(params, jnp.asarray(queries)))
    p = np.asarray(enc.encode_passage(params, jnp.asarray(passages)))
    order = np.argsort(-(q @ p.T), axis=1)
    legacy = {
        f"top@{k}": float(np.mean([
            gold[i] in order[i, :k] for i in range(len(gold))
        ]))
        for k in (1, 5, 20)
    }
    for impl in ("dense", "fused"):
        got = evaluate_topk(
            enc, params, corpus,
            cfg=RetrieverConfig(search_impl=impl, score_block=16,
                                block_q=8, block_n=16),
        )
        # legacy top@k fields are preserved exactly; each cutoff is also
        # reported under its canonical recall@k alias (same value, one search)
        assert {k: v for k, v in got.items() if k.startswith("top@")} == legacy, (
            impl, got, legacy
        )
        for k in (1, 5, 20):
            assert got[f"recall@{k}"] == got[f"top@{k}"]


def test_eval_search_memory_bounded_by_block():
    """The blocked search must never materialize the (Q, N) score matrix:
    compiled temp bytes stay well under Q*N*4 when block << N."""
    from repro.launch.hlo_analysis import memory_numbers

    qn, n, d, k, block = 64, 8192, 16, 10, 128
    be = DenseSearchBackend(block=block)
    q, p = _rand(qn, n, d)
    compiled = (
        jax.jit(lambda a, b: be.topk(a, b, k))
        .lower(jnp.asarray(q), jnp.asarray(p))
        .compile()
    )
    temp = memory_numbers(compiled).get("temp_size_in_bytes", None)
    if temp is None:
        pytest.skip("memory_analysis unavailable on this backend")
    full = qn * n * 4
    assert temp < full // 2, (temp, full)


def test_evaluate_topk_persistent_retriever_tracks_params():
    """The trainer-hook path: a reused Retriever must re-encode the corpus
    with the *current* params each call (ANCE), never serve a stale index,
    and keep its jitted programs across calls."""
    from repro.evaluation import evaluate_topk

    enc = _mlp_encoder()
    corpus = _VecCorpus()
    p_a = enc.init(jax.random.PRNGKey(0))
    p_b = enc.init(jax.random.PRNGKey(7))
    r = Retriever(enc, p_a, RetrieverConfig(score_block=16))
    got_a = evaluate_topk(enc, p_a, corpus, retriever=r)
    reps_a = np.asarray(r.index.reps)
    jit_tokens = r._search_tokens
    got_b = evaluate_topk(enc, p_b, corpus, retriever=r)
    assert not np.allclose(reps_a, np.asarray(r.index.reps))  # re-encoded
    assert r._search_tokens is jit_tokens                     # no re-trace
    assert got_a == evaluate_topk(enc, p_a, corpus)           # == one-off path
    assert got_b == evaluate_topk(enc, p_b, corpus)


def test_trainer_periodic_eval_hook():
    """TrainerConfig.eval_every wires eval_fn results into the history."""
    from repro.runtime.trainer import Trainer, TrainerConfig

    calls = []

    def eval_fn(state, step):
        calls.append(step)
        return {"top@1": 0.5}

    tr = Trainer(
        TrainerConfig(total_steps=6, eval_every=2, log_every=100),
        lambda s, b: (s + b, {"loss": 1.0}),
        next_batch=lambda i: jnp.asarray(1.0),
        eval_fn=eval_fn,
    )
    _, report = tr.run(jnp.asarray(0.0))
    assert calls == [1, 3, 5]
    evald = [h for h in report.history if "eval/top@1" in h]
    assert len(evald) == 3 and evald[0]["eval/top@1"] == 0.5


def test_trainer_eval_failure_does_not_consume_restart_budget():
    """eval is advisory: a deterministically failing eval_fn must not
    trigger restore-and-replay (which would replay the same healthy step
    into the same eval until max_restarts kills the run)."""
    from repro.runtime.trainer import Trainer, TrainerConfig

    def eval_fn(state, step):
        raise RuntimeError("corpus re-encode OOM")

    tr = Trainer(
        TrainerConfig(total_steps=6, eval_every=2, max_restarts=1,
                      log_every=100),
        lambda s, b: (s + b, {"loss": 1.0}),
        next_batch=lambda i: jnp.asarray(1.0),
        eval_fn=eval_fn,
    )
    state, report = tr.run(jnp.asarray(0.0))
    assert report.restarts == 0 and float(state) == 6.0


def test_evaluate_topk_rejects_retriever_plus_cfg():
    from repro.evaluation import evaluate_topk

    enc = _mlp_encoder()
    params = enc.init(jax.random.PRNGKey(0))
    r = Retriever(enc, params, RetrieverConfig())
    with pytest.raises(ValueError, match="not both"):
        evaluate_topk(enc, params, _VecCorpus(), retriever=r,
                      cfg=RetrieverConfig(search_impl="fused"))


# --------------------------------------------- sharded vs replicated (8 dev)
SHARDED_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np

    import sys
    sys.path.insert(0, "tests")
    from helpers import op_name_scopes
    from test_retrieval import _VecCorpus, _mlp_encoder
    from repro.retrieval import Retriever, RetrieverConfig, make_dp_mesh

    assert jax.device_count() == 8
    enc = _mlp_encoder()
    params = enc.init(jax.random.PRNGKey(0))
    mesh = make_dp_mesh(8)
    merge = ("group_max", "group_select", "candidate_topk")

    # 93 and 4,805 rows (% 8 != 0) exercise row padding; blocks of 576
    # rows a device (9·32 <= 3/4 of 576) engage the two-stage block merge
    for precision, impl, block, n in (("fp32", "dense", 16, 93),
                                      ("bf16_banks", "fused", 16, 93),
                                      ("fp32", "dense", 576, 4805)):
        corpus = _VecCorpus(n=n)
        rcfg = dict(top_k=9, precision=precision, score_block=block,
                    block_q=8, block_n=16, search_impl=impl)
        rep = Retriever(enc, params, RetrieverConfig(**rcfg))
        sh = Retriever(
            enc, params,
            RetrieverConfig(index_layout="sharded", **rcfg), mesh=mesh,
        )
        rep.build_index(corpus.passages)
        sh.build_index(corpus.passages)
        assert sh.index.shards == 8
        assert sh.index.bytes_per_device() * 8 == (
            sh.index.reps.shape[0] * sh.index.reps.shape[1]
            * jnp.dtype(sh.index.reps.dtype).itemsize
        )
        # the store is PLACED sharded: each device persistently holds only
        # its rows/8 block (the 1/D HBM claim), not a full replica that
        # gets resharded per search call
        rows, d = sh.index.reps.shape
        shard_shapes = {s.data.shape for s in sh.index.reps.addressable_shards}
        assert shard_shapes == {(rows // 8, d)}, shard_shapes
        ids_r, s_r = rep.search(corpus.queries[:17])
        ids_s, s_s = sh.search(corpus.queries[:17])
        # sharded must match replicated bit-for-bit: ids AND scores
        np.testing.assert_array_equal(ids_r, ids_s, err_msg=impl)
        np.testing.assert_array_equal(s_r, s_s, err_msg=impl)
        # the merge across shards is its own named scope in the program
        text = sh._search_tokens.lower(
            sh.params, sh.index.reps, sh.index.row_valid,
            jnp.asarray(corpus.queries[:17]),
        ).compile().as_text()
        found = op_name_scopes(text, ("block_topk", "shard_merge") + merge)
        want = {"shard_merge", "block_topk"} if impl == "dense" else {"shard_merge"}
        if block > 16:
            want |= set(merge)
        assert {s for v in found.values() for s in v} == want, found
        # one tracked scope an op; the merge's parts only inside block_topk
        nest = [["block_topk", m] for m in merge]
        assert all(len(v) <= 1 and not set(v) & set(merge) or v in nest
                   for v in found.values()), found
        print(f"{precision}/{impl}/{block}: OK")
    print("SHARDED-PARITY-OK")
    """
)


@pytest.mark.slow
def test_sharded_index_matches_replicated_8dev():
    res = subprocess.run(
        [sys.executable, "-c", SHARDED_SCRIPT],
        capture_output=True,
        text=True,
        timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin"},
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "SHARDED-PARITY-OK" in res.stdout


# ----------------------------------------------------- end-to-end smoke
def test_trained_checkpoint_serves_end_to_end(tmp_path):
    """launch/train.py checkpoint -> load_trained_params -> Retriever ->
    BatchingServer -> recall: the full trainer-to-serving round trip at
    tiny scale, including the launch/serve.py --ckpt driver."""
    from repro.launch import serve as serve_mod
    from repro.launch import train as train_mod

    ckpt = str(tmp_path / "ckpt")
    train_mod.main([
        "--steps", "4", "--total-batch", "8", "--local-batch", "4",
        "--bank", "16", "--corpus-size", "64",
        "--checkpoint-dir", ckpt, "--checkpoint-every", "2",
    ])
    params, step = load_trained_params(ckpt)
    assert step == 3
    assert "query" in params and "passage" in params

    _, stats = serve_mod.main([
        "--ckpt", ckpt, "--n-passages", "64", "--n-queries", "8",
        "--top-k", "8", "--max-batch", "4",
    ])
    assert stats["qps"] > 0
    assert 0.0 <= stats["recall"] <= 1.0
    assert stats["batch_mean"] >= 1.0

    # the loaded params really are the trained ones, not a fresh init
    from repro.models.bert import init_bert, tiny_bert

    enc = tiny_bert()
    fresh = init_bert(jax.random.PRNGKey(0), enc)
    assert not np.allclose(
        np.asarray(params["query"]["embed"]["word"]),
        np.asarray(fresh["embed"]["word"]),
    )


def test_load_trained_params_rejects_foreign_checkpoint(tmp_path):
    from repro.checkpoint.checkpoint import save_checkpoint

    save_checkpoint(str(tmp_path), 0, {"weights": np.zeros((2,))})
    with pytest.raises(ValueError, match="no 'state/params/'"):
        load_trained_params(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        load_trained_params(str(tmp_path / "nope"))


def test_retriever_requires_index_before_search():
    enc = _mlp_encoder()
    params = enc.init(jax.random.PRNGKey(0))
    r = Retriever(enc, params, RetrieverConfig(top_k=3))
    with pytest.raises(ValueError, match="no index"):
        r.search(np.zeros((2, 12), np.float32))
    with pytest.raises(ValueError, match="no index"):
        make_server(r)


def test_make_server_round_trips_retriever_results():
    enc = _mlp_encoder()
    params = enc.init(jax.random.PRNGKey(0))
    corpus = _VecCorpus(n=40)
    r = Retriever(enc, params, RetrieverConfig(top_k=5, score_block=8))
    r.build_index(corpus.passages)
    direct_ids, direct_scores = r.search(corpus.queries[:6])
    srv = make_server(r, max_batch=6, max_wait_s=0.02).start()
    try:
        futs = [srv.submit(corpus.queries[i]) for i in range(6)]
        for i, f in enumerate(futs):
            ids, scores = f.get(timeout=30)
            np.testing.assert_array_equal(ids, direct_ids[i])
            np.testing.assert_allclose(scores, direct_scores[i], atol=1e-6)
    finally:
        srv.stop()


def test_retrieval_cells_build_and_trace():
    """launch/steps.py serve/eval cells build and trace with sharded index
    SDS inputs (compile cost is covered at MLP scale above)."""
    from jax.sharding import Mesh

    from repro.launch.steps import build_cell

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    for shape, impl in (("serve_topk", "dense"), ("eval_topk", "fused")):
        prog = build_cell("dpr-bert-base", shape, mesh)
        assert prog.static_info["search_impl"] == impl
        assert prog.static_info["index_bytes_per_device"] > 0
        ids, scores = jax.eval_shape(prog.fn, *prog.args)
        assert ids.shape == (prog.static_info["top_k"],) or ids.shape[1] == (
            prog.static_info["top_k"]
        )
        assert scores.dtype == jnp.float32
