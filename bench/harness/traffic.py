"""Open-loop arrival schedules and request contents, from the seed.

Every seed gets the same multiset of inter-arrival gaps, in its own order:
the gaps are the quantiles of an exponential distribution at the cell's
rate, shuffled by the seed and scaled so that exactly ``rate * seconds``
requests fall due inside the window. So a seed changes when requests come,
not how many or how long the gaps are, and runs of different seeds do the
same work.
"""

from __future__ import annotations

import numpy as np


def rng(seed: int, *tag) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 63), *[int(t) for t in tag]])


def poisson_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times, in seconds from the window's start, of ``round(rate *
    seconds)`` requests; all lie in ``[0, seconds)``."""
    n = int(round(rate * seconds))
    if n < 1:
        raise ValueError(f"rate {rate}/s over {seconds} s gives no request")
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = rng(seed, 1).permutation(gaps)
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def query_tokens(n: int, q_len: int, vocab: int, seed: int, cls_id: int = 101) -> np.ndarray:
    """``n`` tokenized queries: [CLS] and ``q_len - 1`` word ids, uniform
    over the vocabulary past its first ids (BERT's reserved and unused
    tokens)."""
    toks = rng(seed, 2).integers(min(1000, vocab // 10), vocab, size=(n, q_len), dtype=np.int32)
    toks[:, 0] = cls_id
    return toks
