"""Server execute: mean host-clock time of one wave's execute (slot
gather, guarded device call until the results are on the host, state
commit, emit), the serving metrics sink's ``compute_s``, over the waves of
the measured window (the sink keeps the newest 4096), in ms."""


def read(rec):
    s = rec.sink
    return s["compute_ms_mean"] if s.get("waves") else None
