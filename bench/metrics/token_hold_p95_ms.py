"""Engine: per token received in the window, from the engine's stamp on
it (``TokenEvent.t``, interpolated across a decode flush window) to the
host reading it (``TokenEvent.t_emit``, the flush); p95.  Events
without ``t_emit`` (an older program) give nothing."""


def read(rec):
    holds = [ev["t_emit"] - ev["t"]
             for st in rec.obs.streams
             for ev, r in zip(st.events, st.received_s)
             if not ev.get("error") and ev.get("t_emit") is not None
             and rec.in_window(r)]
    return rec.p95_ms(holds)
