"""Windows answered without error during the measured window, per second
of the window (host clock)."""


def read(rec):
    n = rec.n_sub
    ok = (rec.status[:n] == 1) & rec.in_window(rec.t_recv[:n])
    return float(ok.sum()) / rec.seconds
