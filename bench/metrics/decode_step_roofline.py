"""Kernels on the decode path (the whole decode step until the model
names its attention ops): per step, the larger of FLOPs over peak and
bytes over bandwidth (weights once, each lane's live K/V rows read, its
new row written), summed over the traced span's steps, over the device
time of ``fused_step`` and ``mega_step``."""
from bench import cost
from bench.record import DECODE_EXECUTABLES


def read(rec):
    if rec.trace is None:
        return None
    t = rec.device_s(DECODE_EXECUTABLES)
    steps = rec.decode_steps()
    if t <= 0 or not steps:
        return None
    pk = rec.peaks
    bound = sum(max(sum(cost.decode_token_flops(rec.cfg, k) for k in lanes)
                    / pk["flops_per_s"],
                    cost.decode_step_bytes(rec.cfg, lanes)
                    / pk["hbm_bytes_per_s"])
                for lanes in steps)
    return 100.0 * bound / t
