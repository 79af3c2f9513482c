"""train.input_ms: host time of the Trainer's ``next_batch`` (loader and
batch assembly, host-to-device copy), mean per update in the window, from
the harness's host clock around the call."""

import numpy as np


def read(d):
    return float(np.mean(d["batch_s"]) * 1e3) if d.get("batch_s") else None
