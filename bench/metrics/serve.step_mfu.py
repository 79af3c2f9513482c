"""serve.step_mfu: the whole serving step's share of the chip's peak over
the window: the least time of one search call at the chip's peaks times
the batches the server ran in the window, over the window's length."""


def read(d):
    n = len(d.get("batch_sizes") or ())
    return 100.0 * d["search_least_s"] * n / d["window_s_host"] if n else None
