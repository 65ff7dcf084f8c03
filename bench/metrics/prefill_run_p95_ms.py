"""Engine: per request due in the window, from the first device call
that carries its prompt (``Session.dispatched_s``) to the host reading
its first token (``Session.first_token_s``), decode work queued ahead
of it on the device included; p95."""
from bench.stages import stage_gaps


def read(rec):
    return rec.p95_ms(stage_gaps(rec, "dispatched_s", "first_token_s"))
