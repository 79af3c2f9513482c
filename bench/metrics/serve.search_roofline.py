"""serve.search_roofline: the least time of one search call on one chip
(its index rows and tower weights read once at the HBM bandwidth, or its
FLOPs at the bf16 peak, whichever is larger; bench/harness/counts.py) over
the device time of one execution of the search program."""

from bench.harness import readers


def read(d):
    calls = readers.program_calls(d)
    if calls is None or calls[1] <= 0:
        return None
    return 100.0 * d["search_least_s"] / calls[1]
