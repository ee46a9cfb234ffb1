"""Per-window latency, shared by the latency readers: from the moment a
window was due on the schedule to the moment the client's ``poll``
received its answer, for every window due inside the measured window.  A
window without an answer, or answered with an error, counts as still
waiting when the run stopped waiting for answers."""

import numpy as np


def latencies_ms(rec):
    lo, hi = rec.due_range()
    idx = np.arange(lo, hi)
    due = rec.due_abs(idx)
    ok = rec.status[lo:hi] == 1
    end = np.where(ok, rec.t_recv[lo:hi], rec.t_final)
    return (end - due) * 1e3


def percentile(rec, q):
    lat = latencies_ms(rec)
    return float(np.percentile(lat, q)) if len(lat) else None
