"""Wave assembly: the waves cut part-full because the compute thread was
waiting for work (the serving metrics sink's ``idle_cuts``), over every
wave computed in the measured window, in %.  None with no waves, or where
the program keeps no such counter."""


def read(rec):
    s = rec.sink
    if not s.get("waves") or "idle_cuts" not in s:
        return None
    return 100.0 * s["idle_cuts"] / s["waves"]
