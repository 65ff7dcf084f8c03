"""Model step, prefill: prompt FLOPs (cold chunks and resume batches,
attention over each token's context) in the traced span over the device
time of ``prefill_step`` and ``resume_step`` times the chip's peak."""
from bench.record import PREFILL_EXECUTABLES


def read(rec):
    if rec.trace is None:
        return None
    t = rec.device_s(PREFILL_EXECUTABLES)
    flops = rec.prefill_flops_in_trace()
    if t <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (t * rec.peaks["flops_per_s"])
