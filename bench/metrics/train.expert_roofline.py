"""train.expert_roofline: the grouped expert products' share of their
roofline: the least time of the products the update's routed slots need
(three products a slot to a held expert forward, each with its two
backward products, bounded by the bf16 peak or by reading the held
experts' bf16 weights once; bench/harness/lm_counts.py), over the device
time of the chip's ragged-dot kernels, in the whole updates of the traced
window. Recomputed forward products count in the time, not in the least
time."""

from bench.harness import readers


def read(d):
    t, calls = d.get("grouped_program_s"), readers.program_calls(d)
    if not t or calls is None or "expert_least_s_per_update" not in d:
        return None
    return 100.0 * d["expert_least_s_per_update"] * calls[0] / t
