"""Wave assembly: real windows over wave slots (waves x batch), over every
wave computed in the measured window (the serving metrics sink's counts),
in %."""


def read(rec):
    s = rec.sink
    if not s.get("waves"):
        return None
    return 100.0 * s["samples"] / (s["waves"] * s["batch"])
