"""serve.device_idle_share: share of the traced window in which no
operation ran on the device (1 - union of op intervals / window), averaged
over the chips."""

from bench.harness import readers


def read(d):
    return readers.idle_share_pct(d)
