"""train.hbm_peak_gb: ``peak_bytes_in_use`` of the fullest chip after the
window, in GB (1e9 bytes), as the TPU runtime reports it."""


def read(d):
    v = d.get("memory_peak_bytes")
    return v / 1e9 if v else None
