"""The open-loop schedule and the request contents, from the seed."""

import numpy as np
import pytest

import bench_tiny  # noqa: F401
from bench.harness import traffic

SEEDS = [0, 7, 2**31 + 5, 3_000_000_000]


@pytest.mark.parametrize("seed", SEEDS)
def test_schedule_repeats_by_seed(seed):
    a = traffic.poisson_offsets(200.0, 20.0, seed)
    b = traffic.poisson_offsets(200.0, 20.0, seed)
    assert np.array_equal(a, b)


@pytest.mark.parametrize("seed", SEEDS)
def test_every_request_falls_due_in_the_window(seed):
    off = traffic.poisson_offsets(200.0, 20.0, seed)
    assert len(off) == 4000 and off[0] == 0.0
    assert np.all(np.diff(off) > 0) and off[-1] < 20.0


def test_seeds_share_the_gaps_in_another_order():
    def gaps(seed):  # the last gap runs to the window's end
        return np.diff(np.append(traffic.poisson_offsets(150.0, 10.0, seed), 10.0))

    base = np.sort(gaps(SEEDS[0]))
    for seed in SEEDS[1:]:
        assert np.allclose(np.sort(gaps(seed)), base)
        assert not np.allclose(gaps(seed), gaps(SEEDS[0]))


def test_gaps_are_exponential_at_the_rate():
    gaps = np.diff(traffic.poisson_offsets(250.0, 40.0, 1))
    assert np.mean(gaps) == pytest.approx(1 / 250.0, rel=0.01)
    # exponential: the coefficient of variation is 1
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.05)


@pytest.mark.parametrize("seed", SEEDS)
def test_queries_repeat_by_seed(seed):
    a = traffic.query_tokens(64, 32, 30522, seed)
    assert np.array_equal(a, traffic.query_tokens(64, 32, 30522, seed))
    assert a.shape == (64, 32) and np.all(a[:, 0] == 101)
    assert a[:, 1:].min() >= 1000 and a.max() < 30522
    assert len({row.tobytes() for row in a}) == 64
