"""HTTP/SSE front: client receipt of a token minus the engine's stamp on
it (``TokenEvent.t``), on one clock; p95 over the window's tokens."""


def read(rec):
    off = rec.obs.engine_offset
    lags = [r - (ev["t"] + off)
            for st in rec.obs.streams
            for ev, r in zip(st.events, st.received_s)
            if not ev.get("error") and rec.in_window(r)]
    return rec.p95_ms(lags)
