"""Whole step: answered windows per second of the measured window times
the operations of a window, over the chips' peak int8 rate, in %."""

from metrics.windows_per_s import read as windows_per_s


def read(rec):
    if "peaks" not in rec.work:
        return None
    rate = windows_per_s(rec) * rec.work["ops_per_window"]
    return 100.0 * rate / (rec.chips * rec.work["peaks"]["int8_ops_per_s"])
