"""serve.generator_lag_ms: 95th percentile over the window's requests of
how late the load generator sent each one after it was due (host clock).
A starved generator shows here, not as a fast server."""

from bench.harness import readers


def read(d):
    lag = d.get("generator_lag_s")
    return readers.p95(lag) * 1e3 if lag is not None and len(lag) else None
