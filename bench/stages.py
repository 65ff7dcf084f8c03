"""Per-request stage stamps that the program keeps on each ``Session``,
aligned by turn with ``request_arrivals`` (engine clock):

``staged_s``          the gateway took the request (cold: on entering
                      ``submit``; resume: when the tool returned)
``readied_s``         its op reached the engine
``request_arrivals``  the engine admitted it
``dispatched_s``      the first device call carrying its prompt
``first_token_s``     the host read its first token

A program that keeps no such stamp (an older one) gives no value."""
from __future__ import annotations

from typing import List, Optional

STAGES = ("staged_s", "readied_s", "request_arrivals", "dispatched_s",
          "first_token_s")


def stamp(rec, req, name: str) -> Optional[float]:
    """The request's stamp ``name``, or ``None`` where there is none."""
    sess = rec.obs.sessions.get(rec.obs.streams[req.stream].session_id)
    xs = getattr(sess, name, None)
    return xs[req.turn] if xs is not None and req.turn < len(xs) else None


def stage_gaps(rec, start: str, end: str) -> List[float]:
    """``end - start`` for each request due in the window that has
    both stamps."""
    out = []
    for r in rec.reqs:
        a, b = stamp(rec, r, start), stamp(rec, r, end)
        if a is not None and b is not None:
            out.append(b - a)
    return out
