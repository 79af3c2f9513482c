"""train.infonce_roofline: the fused InfoNCE kernel's share of its
roofline: the least time its calls' own mathematics needs (every chunk's
forward, dQ and dP products at their shapes, each bounded by the bf16 peak
or by reading its operands once; bench/harness/counts.py), over the device
time of the kernel's events in the traced window."""

from bench.harness import readers


def read(d):
    t = readers.infonce_seconds(d)
    calls = readers.program_calls(d)
    if not t or calls is None:
        return None
    return 100.0 * d["infonce_least_s_per_update"] * calls[0] / t
