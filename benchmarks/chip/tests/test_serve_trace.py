"""Self-check of ``serve_trace.reduce`` on a hand-made trace: the device's
idle time is split exactly by what the compute thread's ``serve.*`` spans
say it was doing."""

import pytest

import serve_trace

# Window [0, 100] ns; the device busy in [0, 10], [30, 40] and [70, 80],
# so idle in [10, 30], [40, 70] and [80, 100]: 70 ns.
DEVICES = {"/device:TPU:0": {"ops": [["%a = s32[] copy(x)", 0, 10],
                                     ["%b = s32[] add(y)", 30, 10],
                                     ["%a = s32[] copy(x)", 70, 10]]}}
COMPUTE = [
    ["serve.wait", 0, 12],
    ["serve.execute", 12, 48],          # [12, 60]
    ["serve.slots", 12, 3], ["serve.h2d", 15, 10], ["serve.call", 25, 3],
    ["serve.ready", 28, 17], ["serve.commit", 45, 5], ["serve.emit", 50, 8],
    ["serve.wait", 60, 15],
    ["serve.execute", 75, 20],          # [75, 95], children up to 90 only
    ["serve.slots", 75, 5], ["serve.h2d", 80, 10],
]
# An assembler thread: its spans never count for the compute thread.
ASSEMBLER = [["serve.assemble", 0, 100], ["serve.put", 20, 50]]


def _trace(threads=(ASSEMBLER, COMPUTE), devices=DEVICES):
    return {"devices": devices, "host": [["bench.traced", 0, 100]],
            "threads": [list(t) for t in threads]}


def test_reduce_hand_made_serve_trace():
    r = serve_trace.reduce(_trace())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)
    assert r["idle_s"] == pytest.approx(70e-9)
    want_ns = {
        "serve.wait": 2 + 10,            # [10, 12] and [60, 70]
        "serve.slots": 3,                # [12, 15]; [75, 80] is busy
        "serve.h2d": 10 + 10,            # [15, 25] and [80, 90]
        "serve.call": 3,                 # [25, 28]
        "serve.ready": 2 + 5,            # [28, 30] and [40, 45]
        "serve.commit": 5,               # [45, 50]
        "serve.emit": 8,                 # [50, 58]
        serve_trace.EXECUTE_OTHER: 2 + 5,   # [58, 60] and [90, 95]
        serve_trace.OUTSIDE: 5,          # [95, 100]
    }
    got = r["idle_s_by_stage"]
    assert set(got) == set(want_ns)
    for name, ns in want_ns.items():
        assert got[name] == pytest.approx(ns * 1e-9), name
    assert sum(got.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["idle_exec_share"] == pytest.approx(100.0 * 53 / 70)
    assert r["compute_cover"] == pytest.approx(95.0)
    assert r["waves"] == 2


@pytest.mark.parametrize("trace", [
    _trace(threads=(ASSEMBLER,)),       # a program without the spans
    _trace(devices={}),                 # no device plane (the CPU)
], ids=["no-serve-spans", "no-device"])
def test_reduce_without_spans_or_device(trace):
    assert serve_trace.reduce(trace) is None
