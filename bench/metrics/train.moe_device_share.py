"""train.moe_device_share: device time of the dropless expert layer (its
``moe_*`` named scopes and the grouped-product kernels;
bench/harness/moe_trace.py) over the device's busy time in the traced
window."""

from bench.harness import readers


def read(d):
    s, b = d.get("moe_scope_s"), readers.busy(d)
    if not s or not sum(s.values()) or b is None or b[0] <= 0:
        return None
    return 100.0 * sum(s.values()) / b[0]
