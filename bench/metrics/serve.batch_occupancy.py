"""serve.batch_occupancy: mean size of the batches the BatchingServer ran
in the window (its ``batch_sizes`` counter), over ``max_batch``."""

import numpy as np


def read(d):
    sizes = d.get("batch_sizes")
    return 100.0 * float(np.mean(sizes)) / d["max_batch"] if sizes else None
