"""HTTP/SSE front: per token received in the window, client receipt
minus the host's read of it (``TokenEvent.t_emit``), on one clock;
p95.  Events without ``t_emit`` (an older program) give nothing."""


def read(rec):
    off = rec.obs.engine_offset
    lags = [r - (ev["t_emit"] + off)
            for st in rec.obs.streams
            for ev, r in zip(st.events, st.received_s)
            if not ev.get("error") and ev.get("t_emit") is not None
            and rec.in_window(r)]
    return rec.p95_ms(lags)
