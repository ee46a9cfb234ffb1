"""Device: share of the device's idle time in the traced window during
which the server's compute thread was inside ``serve.execute`` (host work
the device waits on) rather than waiting for a wave or outside both spans,
in % (``serve_trace.py``, which re-reads the traced run's trace)."""

import serve_trace


def read(rec):
    if rec.trace is None:
        return None
    r = serve_trace.reduce_dir()
    return None if r is None else r["idle_exec_share"]
