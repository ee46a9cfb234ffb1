"""Load generator: how late the ``submit`` call of a window due inside
the measured window started, against its due time; 99th percentile, ms."""

import numpy as np


def read(rec):
    lo, hi = rec.due_range()
    t = rec.t_sub[lo:hi]
    lag = (t - rec.due_abs(np.arange(lo, hi)))[~np.isnan(t)]
    return float(np.percentile(lag, 99) * 1e3) if len(lag) else None
