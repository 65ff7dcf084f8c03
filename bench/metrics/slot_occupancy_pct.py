"""KV pool: the time-weighted mean over the window of ``slots_in_use``
over the pool's slots.  The gauge is sampled after each engine cycle
and holds until the next sample; cycles are short while no lane
decodes and long while one does, so a mean over cycles would weigh the
idle ones most."""


def read(rec):
    pts = [(t1, s) for _, t1, s in rec.obs.cycles]
    held = span = 0.0
    for (t, s), (t_next, _) in zip(pts, pts[1:]):
        a, b = max(t, rec.obs.open_s), min(t_next, rec.obs.close_s)
        if b > a:
            held += s * (b - a)
            span += b - a
    if span <= 0:
        return None
    return 100.0 * held / span / rec.num_slots
