"""Wave assembly: per-window median of the wait in the scheduler's pending
list, from ``submit`` to the window's wave being built, over the waves of
the measured window (the serving metrics sink's stage times), in ms."""

from metrics._stages import stages


def read(rec):
    s = stages(rec)
    return None if s is None else s["pending_wait_ms"]["p50"]
