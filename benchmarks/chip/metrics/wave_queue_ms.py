"""Server execute: per-window median of the wait in the queue of assembled
waves, from the window's wave being built to the start of its execute on
the compute thread, over the waves of the measured window (the serving
metrics sink's stage times), in ms."""

from metrics._stages import stages


def read(rec):
    s = stages(rec)
    return None if s is None else s["queue_wait_ms"]["p50"]
