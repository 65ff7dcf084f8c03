"""Engine dispatch: the engine's ``dispatch_gap_s`` samples observed in
the window (host time between decode submissions, less device waits);
p95."""


def read(rec):
    return rec.p95_ms(rec.obs.dispatch_gaps)
