"""serve.search_device_ms: device time of one execution of the search
program (query encode, scan and top-k, and the shard merge), mean over the
executions wholly inside the traced window, averaged over the chips."""

from bench.harness import readers


def read(d):
    calls = readers.program_calls(d)
    return calls[1] * 1e3 if calls is not None else None
