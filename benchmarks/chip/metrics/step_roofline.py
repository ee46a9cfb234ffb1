"""Wave program: the least time a wave's work needs over the device time
of the jitted wave program per wave, in %.

The least time is the larger of the wave's operations over the chip's
peak int8 rate and its bytes over the HBM bandwidth (``work.py``;
``peaks.json``); a wave computes every row of its batch, padding too.  The
device time is the "XLA Modules" time of the traced window on every
device, and the waves are the runs of the most-run program there."""


def read(rec):
    if rec.trace is None or "peaks" not in rec.work:
        return None
    mods = rec.trace["modules"]
    if not mods:
        return None
    runs = max(m["runs"] for m in mods.values())
    seconds = sum(m["seconds"] for m in mods.values())
    pk = rec.work["peaks"]
    least = max(rec.work["ops_per_wave"] / pk["int8_ops_per_s"],
                rec.work["bytes_per_wave"] / pk["hbm_bytes_per_s"])
    return 100.0 * runs * least / seconds if seconds > 0 else None
