"""The serving path's own spans in a traced run: what the server's compute
thread was doing while the device idled.

    python3 benchmarks/chip/serve_trace.py [trace_dir]

prints :func:`reduce` of the trace a ``--trace 1`` run of ``run.py`` leaves
under ``.out/trace`` (or under ``trace_dir``) as one JSON line.

The program marks each wave's steps with ``serve.*`` spans on the
profiler's host threads (``repro.serving.scheduler`` and ``server``): the
compute thread alternates ``serve.wait`` (waiting for the next assembled
wave) and ``serve.execute``, whose children ``serve.slots``, ``serve.h2d``,
``serve.call``, ``serve.ready``, ``serve.commit`` and ``serve.emit`` run one
after another.  :func:`load` keeps those spans per host thread, with the
benchmark's ``bench.traced`` span that bounds the window and each device's
"XLA Ops" line; it shares the trace's clock with ``trace_reduce``.  A
program that marks no ``serve.execute`` span gives None.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
#: Where ``run.py`` writes a traced run's profiler trace.
TRACE_DIR = os.path.join(HERE, ".out", "trace")
WAIT, EXECUTE = "serve.wait", "serve.execute"
CHILDREN = ("serve.slots", "serve.h2d", "serve.call", "serve.ready",
            "serve.commit", "serve.emit")
#: Idle time inside ``serve.execute`` that none of its children covers.
EXECUTE_OTHER = "serve.execute (other)"
#: Idle time in which the compute thread was in neither span.
OUTSIDE = "none"


def load(path: str) -> Dict:
    """``devices``: per device plane, its ``ops``; ``host``: the
    ``bench.traced`` span; ``threads``: per host thread that marked any,
    its ``serve.*`` spans.  Events are ``[name, start_ns, duration_ns]``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict] = {}
    host: List = []
    threads: List[List] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
            devices[plane.name] = {"ops": [[e.name, e.start_ns, e.duration_ns]
                                           for ln in ops for e in ln.events]}
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                spans = []
                for e in ln.events:
                    if e.name.startswith("serve."):
                        spans.append([e.name, e.start_ns, e.duration_ns])
                    elif e.name == trace_reduce.WINDOW_SPAN:
                        host.append([e.name, e.start_ns, e.duration_ns])
                if spans:
                    threads.append(spans)
    return {"devices": devices, "host": host, "threads": threads}


def _compute_thread(threads: List[List]) -> Optional[List]:
    """The thread that spent most time in ``serve.execute``, else None."""
    busiest, most = None, 0.0
    for spans in threads:
        t = sum(d for n, _, d in spans if n == EXECUTE)
        if t > most:
            busiest, most = spans, t
    return busiest


def reduce(trace: Dict) -> Optional[Dict]:
    """The device's idle time in the traced window, split by what the
    compute thread was doing, mean over the devices.

    ``idle_s_by_stage`` gives the idle seconds under ``serve.wait``, under
    each child of ``serve.execute``, under ``serve.execute`` outside its
    children (``EXECUTE_OTHER``) and outside both spans (``OUTSIDE``);
    they sum to ``idle_s``, the window less the union of the device's
    ops.  ``idle_exec_share`` is the part under ``serve.execute``, in %
    of ``idle_s``; ``compute_cover`` the share of the window inside
    ``serve.wait`` or ``serve.execute``, in %; ``waves`` the
    ``serve.execute`` spans that start in the window.  None where the
    trace has no device or no ``serve.execute`` span."""
    spans = _compute_thread(trace["threads"])
    if spans is None or not trace["devices"]:
        return None
    t0, t1 = trace_reduce.window_of(trace)
    window_s = (t1 - t0) * 1e-9
    names = (WAIT, EXECUTE) + CHILDREN
    cover = {n: trace_reduce.Coverage(trace_reduce.clip(
        [e for e in spans if e[0] == n], t0, t1)) for n in names}
    idle = {n: 0.0 for n in names}
    idle_s = busy_s = 0.0
    for lines in trace["devices"].values():
        merged = trace_reduce.union(trace_reduce.clip(lines["ops"], t0, t1))
        busy_s += sum(e - s for s, e in merged) * 1e-9
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            idle_s += (ge - gs) * 1e-9
            for n in names:
                idle[n] += cover[n].overlap(gs, ge) * 1e-9
    n_dev = len(trace["devices"])
    idle_s, busy_s = idle_s / n_dev, busy_s / n_dev
    idle = {n: v / n_dev for n, v in idle.items()}
    by_stage = {WAIT: idle[WAIT], **{n: idle[n] for n in CHILDREN},
                EXECUTE_OTHER: idle[EXECUTE] - sum(idle[n]
                                                   for n in CHILDREN),
                OUTSIDE: idle_s - idle[WAIT] - idle[EXECUTE]}
    inside = trace_reduce.Coverage(
        cover[WAIT].merged + cover[EXECUTE].merged).overlap(t0, t1)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_s": idle_s,
        "idle_s_by_stage": by_stage,
        "idle_exec_share": (100.0 * idle[EXECUTE] / idle_s if idle_s > 0
                            else None),
        "compute_cover": 100.0 * inside * 1e-9 / window_s,
        "waves": sum(1 for n, s, _ in spans if n == EXECUTE and t0 <= s < t1),
    }


def reduce_dir(trace_dir: str = TRACE_DIR) -> Optional[Dict]:
    """:func:`reduce` of the one trace under ``trace_dir``."""
    return reduce(load(trace_reduce.find_xplane(trace_dir)))


if __name__ == "__main__":
    print(json.dumps(reduce_dir(*sys.argv[1:2])))
