"""train.step_mfu: the whole update's share of the chip's bf16 peak over
the traced window: matrix-product FLOPs per update (bench/harness/counts.py:
towers at their own lengths, attention products, every chunk's similarity
matrix; no embedding gathers, no recomputation) times the updates that
ran wholly inside the traced window, over the window, over the peak."""

from bench.harness import readers


def read(d):
    calls, b = readers.program_calls(d), readers.busy(d)
    if calls is None or b is None:
        return None
    return 100.0 * d["flops_per_update"] * calls[0] / b[1] / d["peaks"]["bf16_flops_per_s"]
