"""Gateway: per request due in the window, from the gateway taking it
(``Session.staged_s``: entry to ``submit`` for a cold request, the
tool's return for a resume) to its op reaching the engine
(``Session.readied_s``); p95."""
from bench.stages import stage_gaps


def read(rec):
    return rec.p95_ms(stage_gaps(rec, "staged_s", "readied_s"))
