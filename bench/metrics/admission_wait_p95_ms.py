"""Gateway admission: ``Session.queue_delays_s`` (ready -> admitted) of
the requests due in the window; p95."""


def read(rec):
    waits = []
    for r in rec.reqs:
        sess = rec.obs.sessions.get(rec.obs.streams[r.stream].session_id)
        if sess is not None and r.turn < len(sess.queue_delays_s):
            waits.append(sess.queue_delays_s[r.turn])
    return rec.p95_ms(waits)
