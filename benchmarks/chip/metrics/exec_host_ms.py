"""Server execute: mean per wave of the host work the device waits on, the
wall time of every execute stage but the wait for the results (slot
gather, upload, jitted call, commit, emit), over the waves of the measured
window (the serving metrics sink's stage times), in ms."""

from metrics._stages import stages


def read(rec):
    s = stages(rec)
    return None if s is None else 1e3 * s["host_wall_s"] / s["waves"]
