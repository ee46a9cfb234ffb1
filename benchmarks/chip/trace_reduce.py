"""Reduction of a profiler trace to device busy time, program time, the
top device operations and the device's idle gaps.

:func:`load` reads an ``.xplane.pb`` with ``jax.profiler.ProfileData``
into plain lists, which is also the form of the recorded trace the
self-check reads (``tests/trace_small.json``):

* ``devices``: per device plane (``/device:TPU:<n>``), its ``ops`` (the
  "XLA Ops" line) and ``modules`` (the "XLA Modules" line, one event per
  run of a jitted program), each ``[name, start_ns, duration_ns]``;
* ``host``: the host spans ``[name, start_ns, duration_ns]`` of the
  benchmark's own ``TraceAnnotation``s (names starting with ``bench.``)
  and of JAX's dispatch on the host threads.

Host and device events share the trace's clock.  :func:`reduce` takes the
traced window from the ``bench.traced`` span.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns

WINDOW_SPAN = "bench.traced"
#: JAX's own host spans of a wave's dispatch: what the serving thread does
#: around the device call.
DISPATCH_SPANS = ("PjitFunction(", "np.asarray(jax.Array)", "shard_args")
#: The load generator's spans.
GENERATOR_SPANS = ("bench.submit", "bench.sleep")
HOST_SPANS = ("bench.",) + DISPATCH_SPANS


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def load(path: str) -> Dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            devices[plane.name] = {
                key: [[e.name, e.start_ns, e.duration_ns]
                      for e in lines[name].events] if name in lines else []
                for key, name in (("ops", "XLA Ops"),
                                  ("modules", "XLA Modules"))}
        elif plane.name.startswith("/host:CPU"):
            for ln in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in ln.events
                            if e.name.startswith(HOST_SPANS))
    return {"devices": devices, "host": host}


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Merged, sorted ``[start, end]`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


class Coverage:
    """The union of a set of intervals, with the length of its overlap
    with any query interval in O(log n)."""

    def __init__(self, intervals: Sequence[Tuple[float, float]]):
        self.merged = union(intervals)
        self.starts = [s for s, _ in self.merged]
        self.ends = [e for _, e in self.merged]
        self.prefix = [0.0]
        for s, e in self.merged:
            self.prefix.append(self.prefix[-1] + e - s)

    def overlap(self, t0: float, t1: float) -> float:
        i0 = bisect.bisect_right(self.ends, t0)
        i1 = bisect.bisect_left(self.starts, t1)
        if i0 >= i1:
            return 0.0
        total = self.prefix[i1] - self.prefix[i0]
        total -= max(0.0, t0 - self.starts[i0])
        total -= max(0.0, self.ends[i1 - 1] - t1)
        return total


def clip(events: Sequence[Event], t0: float, t1: float):
    """``[start, end]`` of each event, cut to the window [t0, t1]."""
    return [(max(s, t0), min(s + d, t1)) for _, s, d in events
            if s + d > t0 and s < t1]


def op_label(name: str) -> str:
    """``%copy.9 = s32[...] copy(...)`` -> ``copy.9 (copy)``."""
    m = re.match(r"%?([^\s=]+)\s*=.*?\s([\w.-]+)\(", name)
    return f"{m.group(1)} ({m.group(2)})" if m else name


def module_label(name: str) -> str:
    """``jit_slot_path(577008952385304121)`` -> ``jit_slot_path``."""
    return re.sub(r"\(\d+\)$", "", name)


def _most(cover: Dict[str, "Coverage"], t0: float, t1: float,
          default: str) -> str:
    """The name whose spans overlap [t0, t1] most, else ``default``."""
    best, label = 0.0, default
    for name, cov in cover.items():
        ov = cov.overlap(t0, t1)
        if ov > best:
            best, label = ov, name
    return label


def window_of(trace: Dict) -> Tuple[float, float]:
    spans = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN!r} span, found "
                           f"{len(spans)}")
    return spans[0]


def reduce(trace: Dict, top: int = 10) -> Dict:
    """Busy and idle time of every device over the traced window, the
    time and run count of each jitted program, the operations that took
    most time, and the idle time by what the host was doing.

    ``busy_s`` is the mean over devices of the union of op intervals.
    ``idle_gaps`` sums the gaps between busy intervals by a two-part
    label: the JAX dispatch span that overlaps the gap most (``python``
    where none does: the serving threads' untraced Python, such as wave
    assembly, slot gather and emit), then the load generator's span
    that overlaps it most (``bench.submit`` or ``bench.sleep``).  The
    consumer's ``bench.poll`` spans cover nearly every gap and label
    none."""
    t0, t1 = window_of(trace)
    window_s = (t1 - t0) * 1e-9
    by_name: Dict[str, List[Tuple[float, float]]] = {}
    for n, s, d in trace["host"]:
        if n != WINDOW_SPAN:
            by_name.setdefault(n, []).append((s, s + d))
    cover = {n: Coverage(iv) for n, iv in sorted(by_name.items())}
    dispatch = {n: c for n, c in cover.items() if n.startswith(DISPATCH_SPANS)}
    generator = {n: c for n, c in cover.items() if n in GENERATOR_SPANS}
    busy, modules, ops, gaps = [], {}, {}, {}
    for lines in trace["devices"].values():
        merged = union(clip(lines["ops"], t0, t1))
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for name, s, d in lines["modules"]:
            if s >= t0 and s + d <= t1:
                m = modules.setdefault(module_label(name), [0, 0.0])
                m[0] += 1
                m[1] += d * 1e-9
        for name, s, d in lines["ops"]:
            if s >= t0 and s + d <= t1:
                key = op_label(name)
                ops[key] = ops.get(key, 0.0) + d * 1e-9
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            label = (f"{_most(dispatch, gs, ge, 'python')} | "
                     f"{_most(generator, gs, ge, 'none')}")
            gaps[label] = gaps.get(label, 0.0) + (ge - gs) * 1e-9
    n_dev = max(1, len(busy))
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev,
        "modules": {k: {"runs": v[0], "seconds": v[1]}
                    for k, v in modules.items()},
        "device_ops": sorted(([k, v / n_dev] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v / n_dev] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }
