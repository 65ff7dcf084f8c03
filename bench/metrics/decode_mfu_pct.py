"""Model step, decode: model FLOPs of the decode steps in the traced
span (``cost.decode_token_flops`` at each lane's live length) over the
device time of the ``fused_step`` and ``mega_step`` executables times
the chip's peak."""
from bench import cost
from bench.record import DECODE_EXECUTABLES


def read(rec):
    if rec.trace is None:
        return None
    t = rec.device_s(DECODE_EXECUTABLES)
    steps = rec.decode_steps()
    if t <= 0 or not steps:
        return None
    flops = sum(cost.decode_token_flops(rec.cfg, k)
                for lanes in steps for k in lanes)
    return 100.0 * flops / (t * rec.peaks["flops_per_s"])
