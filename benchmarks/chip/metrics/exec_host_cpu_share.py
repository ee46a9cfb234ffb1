"""Server execute: the compute thread's CPU time over the wall time of the
host stages that ``exec_host_ms`` counts, in %.  A low share means the
thread was waiting there (for the interpreter lock, a lock or a transfer),
not working."""

from metrics._stages import stages


def read(rec):
    s = stages(rec)
    if s is None or s["host_wall_s"] <= 0:
        return None
    return 100.0 * s["host_cpu_s"] / s["host_wall_s"]
