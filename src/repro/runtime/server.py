"""Dynamic batching for retrieval serving.

``BatchingServer`` coalesces single-query requests up to ``max_batch``
(padding to the compiled batch shape) or flushes after ``max_wait_s`` —
classic dynamic batching. The model-side machinery (index build, sharded
scoring, precision) lives in the Retriever API (``repro/retrieval``);
``retrieval.serving.make_server`` wires a Retriever to this server, and the
legacy helpers below (``blocked_topk_scores``, ``build_index``,
``make_retrieval_server``) are thin wrappers kept for existing callers.

Fault-tolerance notes: the server is stateless between batches — a restart
replays only in-flight requests (callers time out and retry); the index is a
checkpointed artifact.
"""

from __future__ import annotations

import collections
import dataclasses
import queue
import threading
import time
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------- exact top-k
def blocked_topk_scores(
    query_reps: jnp.ndarray,      # (Q, d)
    index: jnp.ndarray,           # (N, d)
    k: int,
    *,
    block: int = 65536,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact top-k by blocked matmul + running merge — never materializes the
    full (Q, N) score matrix. Returns (scores (Q, k), ids (Q, k)); ids are
    -1 (scores NEG_INF) for slots beyond the index size when k > N.

    Legacy entry point: the implementation is the 'dense' SearchBackend in
    repro/retrieval/search.py (lazy import breaks the runtime <-> retrieval
    cycle: retrieval.serving builds on BatchingServer below)."""
    from repro.retrieval.search import DenseSearchBackend

    return DenseSearchBackend(block=block).topk(query_reps, index, k)


def build_index(
    encode_passage: Callable[[Any], jnp.ndarray],
    passages: np.ndarray,
    *,
    batch: int = 256,
) -> np.ndarray:
    """Legacy fixed-batch corpus encode (see repro/retrieval/index.py)."""
    from repro.retrieval.index import encode_corpus

    return encode_corpus(encode_passage, passages, batch=batch)


# ----------------------------------------------------------- dynamic batching
@dataclasses.dataclass
class Request:
    payload: np.ndarray
    future: "queue.Queue"        # 1-slot: receives (ids, scores) or Exception
    t_enqueue: float = dataclasses.field(default_factory=time.monotonic)


class Ring(collections.deque):
    """A bounded record of the newest ``maxlen`` entries that also takes
    slices, as the lists the counters were before it did."""

    def __getitem__(self, i):
        if isinstance(i, slice):
            return list(self)[i]
        return super().__getitem__(i)


class BatchingServer:
    """Dynamic batcher: coalesce requests to ``max_batch`` (padding to the
    compiled batch size) or flush after ``max_wait_s``.

    Counters, each a ``Ring`` with one entry for each of the newest
    ``RECORD`` batches, so the two drop together: ``batch_sizes``, the
    requests in the batch; ``queue_wait_s``, a tuple with, for each of its
    requests, the seconds from ``submit`` until the batch was collected
    (the coalescing window included), that is until its search began.
    Each batch runs under the host spans ``repro.server.collect``,
    ``repro.server.search`` and ``repro.server.deliver`` of a JAX profiler
    trace."""

    RECORD = 65536

    def __init__(
        self,
        serve_fn: Callable[[np.ndarray], Tuple[np.ndarray, np.ndarray]],
        *,
        max_batch: int = 32,
        max_wait_s: float = 0.01,
    ):
        self.serve_fn = serve_fn
        self.max_batch = max_batch
        self.max_wait_s = max_wait_s
        self._q: "queue.Queue[Request]" = queue.Queue()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.batch_sizes: Ring = Ring(maxlen=self.RECORD)
        self.queue_wait_s: Ring = Ring(maxlen=self.RECORD)

    def start(self):
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=5)

    def submit(self, payload: np.ndarray) -> "queue.Queue":
        fut: "queue.Queue" = queue.Queue(maxsize=1)
        self._q.put(Request(payload=payload, future=fut))
        return fut

    def query(self, payload: np.ndarray, timeout: float = 30.0):
        res = self.submit(payload).get(timeout=timeout)
        if isinstance(res, Exception):
            raise res
        return res

    # -- internals ---------------------------------------------------------
    def _collect(self) -> List[Request]:
        try:
            first = self._q.get(timeout=0.05)
        except queue.Empty:
            return []
        batch = [first]
        # Drain whatever is already queued without waiting: under backlog the
        # batch fills instantly. (The old deadline was first.t_enqueue +
        # max_wait_s — submit time, not collect time — so a backed-up queue
        # made remaining <= 0 on the first iteration and every batch
        # degraded to size 1, exactly when coalescing matters most.)
        while len(batch) < self.max_batch:
            try:
                batch.append(self._q.get_nowait())
            except queue.Empty:
                break
        # then wait out the remainder of the coalescing window, measured
        # from collect time, for stragglers
        deadline = time.monotonic() + self.max_wait_s
        while len(batch) < self.max_batch:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                break
            try:
                batch.append(self._q.get(timeout=remaining))
            except queue.Empty:
                break
        return batch

    def _loop(self):
        while not self._stop.is_set():
            with jax.profiler.TraceAnnotation("repro.server.collect"):
                batch = self._collect()
            if not batch:
                continue
            t_collected = time.monotonic()
            self.batch_sizes.append(len(batch))
            self.queue_wait_s.append(tuple(t_collected - r.t_enqueue for r in batch))
            payloads = np.stack([r.payload for r in batch])
            n = len(batch)
            if n < self.max_batch:  # pad to the compiled shape
                payloads = np.concatenate(
                    [payloads, np.repeat(payloads[-1:], self.max_batch - n, axis=0)]
                )
            try:
                with jax.profiler.TraceAnnotation("repro.server.search"):
                    ids, scores = self.serve_fn(payloads)
                    ids, scores = np.asarray(ids), np.asarray(scores)
                with jax.profiler.TraceAnnotation("repro.server.deliver"):
                    for i, r in enumerate(batch):
                        r.future.put((ids[i], scores[i]))
            except Exception as e:  # pragma: no cover - surfaced to callers
                for r in batch:
                    r.future.put(e)


def make_retrieval_server(
    encode_query: Callable[[np.ndarray], jnp.ndarray],
    index: np.ndarray,
    *,
    k: int = 20,
    max_batch: int = 32,
    max_wait_s: float = 0.01,
) -> BatchingServer:
    """Legacy raw-matrix server; prefer retrieval.serving.make_server (the
    Retriever-backed path: checkpoint load, sharding, precision, backends)."""
    index_dev = jnp.asarray(index)

    @jax.jit
    def _serve(tokens):
        reps = encode_query(tokens)
        scores, ids = blocked_topk_scores(reps, index_dev, k)
        return ids, scores

    return BatchingServer(_serve, max_batch=max_batch, max_wait_s=max_wait_s)
