"""Process start to window open: imports, weights, build, quantise,
compilation (from the persistent cache after a cell's first run) and the
warm-up traffic."""


def read(rec):
    return float(rec.setup_s)
