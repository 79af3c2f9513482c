"""The program's layer map for a profiler: named scopes in the compiled
update and search (op_name metadata, one scope per op at most), the
Trainer's and the server's host spans, and the server's bounded counters
(``batch_sizes``, ``queue_wait_s``)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import op_name_scopes
from repro.runtime.server import BatchingServer, Ring

# the update's named scopes (core/step_program.py)
SCOPES = ("towers", "loss", "grad_accum", "bank_push", "optimizer")

TRAIN_ARGS = ["--arch", "bert-tiny", "--precision", "bf16_banks", "--total-batch", "16",
              "--local-batch", "8", "--bank", "32", "--q-len", "8", "--p-len", "16",
              "--steps", "100", "--corpus-size", "64", "--seed", "3"]


def update_hlo(method: str, loss_impl: str) -> str:
    from repro.launch import train

    built = train.build(train.parse_args(
        TRAIN_ARGS + ["--method", method, "--loss-impl", loss_impl]))
    batch = built.trainer.next_batch(0)
    return built.update.lower(built.state, batch).compile().as_text()


@pytest.mark.parametrize("method, loss_impl, scopes", [
    ("contaccum", "dense", SCOPES),
    ("contcache", "dense", SCOPES),
    ("dpr", "dense", ("towers", "loss", "optimizer")),
])
def test_update_names_each_layer_once(method, loss_impl, scopes):
    found = op_name_scopes(update_hlo(method, loss_impl), SCOPES)
    assert {s for v in found.values() for s in v} == set(scopes)
    assert not {n: v for n, v in found.items() if len(v) > 1}
    # differentiation keeps the names: the towers' backward is named too
    assert any("transpose(jvp(towers))" in n for n in found)


def test_search_names_block_topk():
    from repro.retrieval import Retriever, RetrieverConfig
    from test_retrieval import _VecCorpus, _mlp_encoder

    enc = _mlp_encoder()
    merge = ("group_max", "group_select", "candidate_topk")
    # one top_k a block; then blocks of 576 columns (9·32 <= 3/4 of 576),
    # where the two-stage merge's steps are named inside block_topk
    for block, n, want in ((16, 93, {"block_topk"}),
                           (576, 600, {"block_topk", *merge})):
        corpus = _VecCorpus(n=n)
        r = Retriever(enc, enc.init(jax.random.PRNGKey(0)),
                      RetrieverConfig(top_k=9, score_block=block, search_impl="dense"))
        r.build_index(corpus.passages)
        r.search(corpus.queries[:5])
        text = r._search_tokens.lower(r.params, r.index.reps, r.index.row_valid,
                                      jnp.asarray(corpus.queries[:5])).compile().as_text()
        found = op_name_scopes(text, ("block_topk", "shard_merge") + merge)
        assert {s for v in found.values() for s in v} == want
        # one tracked scope an op; the merge's parts only inside block_topk
        nest = [["block_topk", m] for m in merge]
        assert all(len(v) <= 1 and not set(v) & set(merge) or v in nest
                   for v in found.values()), found


def test_ring_keeps_the_newest_entries():
    r = Ring(maxlen=3)
    r.extend(range(5))
    assert list(r) == [2, 3, 4] and r[0] == 2 and r[-1] == 4
    assert r[1:] == [3, 4] and r[5:] == []
    srv = BatchingServer(lambda x: x, max_batch=2)
    for name in ("batch_sizes", "queue_wait_s"):
        ring = getattr(srv, name)
        assert isinstance(ring, Ring) and ring.maxlen == BatchingServer.RECORD
        ring.extend(range(BatchingServer.RECORD + 10))
        assert len(ring) == BatchingServer.RECORD and ring[0] == 10


def test_queue_wait_counts_each_request_and_the_search_ahead():
    """Requests that arrive while a search runs wait at least its length."""
    search_s = 0.2
    started = threading.Event()

    def serve(batch):
        started.set()
        time.sleep(search_s)
        return np.zeros((len(batch), 1), np.int32), np.zeros((len(batch), 1), np.float32)

    srv = BatchingServer(serve, max_batch=4, max_wait_s=0.001).start()
    try:
        first = srv.submit(np.zeros(2))
        started.wait(5)
        behind = [srv.submit(np.zeros(2)) for _ in range(3)]
        for f in [first] + behind:
            f.get(timeout=10)
    finally:
        srv.stop()
    assert list(srv.batch_sizes) == [1, 3]
    # one tuple a batch, one wait a request: the two rings drop together
    assert [len(b) for b in srv.queue_wait_s] == [1, 3]
    waits = [w for b in srv.queue_wait_s for w in b]
    assert waits[0] < search_s / 2
    assert all(w >= search_s * 0.9 for w in waits[1:]), waits
