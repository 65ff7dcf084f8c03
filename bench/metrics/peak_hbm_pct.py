"""Device memory: ``peak_bytes_in_use`` over ``bytes_limit`` from
``memory_stats()`` after the window, before the reference runs."""


def read(rec):
    if not rec.memory.get("limit"):
        return None
    return 100.0 * rec.memory["peak"] / rec.memory["limit"]
