"""train.infonce_device_share: device time of the fused InfoNCE kernel's
events over the device's busy time in the traced window."""

from bench.harness import readers


def read(d):
    t = readers.infonce_seconds(d)
    b = readers.busy(d)
    if not t or b is None or b[0] <= 0:
        return None
    return 100.0 * t / b[0]
