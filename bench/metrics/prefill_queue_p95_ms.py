"""Planner: per request due in the window, from admission
(``Session.request_arrivals``) to the first device call that carries
its prompt (``Session.dispatched_s``); p95."""
from bench.stages import stage_gaps


def read(rec):
    return rec.p95_ms(stage_gaps(rec, "request_arrivals", "dispatched_s"))
