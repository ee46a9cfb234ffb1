"""Device: share of the traced window in which no operation ran on the
device (the union of the "XLA Ops" intervals), mean over the chips used,
in %."""


def read(rec):
    if rec.trace is None or rec.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - rec.trace["busy_s"] / rec.trace["window_s"])
