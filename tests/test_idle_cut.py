"""The wave scheduler's idle cut: with a deadline set, a part-full wave is
cut as soon as the compute thread waits for work with no wave cut for it;
the deadline stays the upper limit while the compute thread is busy, and
with no deadline waves stay strictly full until a drain."""

import queue
import threading
import time

import numpy as np

from repro.serving import MetricsSink, WaveRecord, WaveScheduler

WINDOW = np.zeros((4, 1), np.float32)


class HeldExecute:
    """An ``execute`` hook that records each wave and, while ``hold`` is
    set, blocks every call until :meth:`release` lets one through."""

    def __init__(self, hold: bool):
        self.hold = hold
        self.waves, self.starts, self.ends = [], [], []
        self._go = threading.Semaphore(0)
        self._cond = threading.Condition()

    def __call__(self, wave):
        with self._cond:
            self.starts.append(time.perf_counter())
            self._cond.notify_all()
        if self.hold:
            self._go.acquire(timeout=10.0)
        with self._cond:
            self.waves.append(wave)
            self.ends.append(time.perf_counter())
            self._cond.notify_all()

    def release(self, n: int = 1) -> float:
        t = time.perf_counter()
        for _ in range(n):
            self._go.release()
        return t

    def wait_for(self, what: str, n: int, timeout: float = 10.0) -> None:
        """Block until ``n`` executes have started (``what="starts"``) or
        finished (``"waves"``)."""
        with self._cond:
            assert self._cond.wait_for(
                lambda: len(getattr(self, what)) >= n, timeout)


class SlowHandOff(queue.Queue):
    """A wave queue whose hand-off to the compute thread takes 50 ms, so
    the compute thread is still marked waiting after it took a wave."""

    def get(self, *args, **kwargs):
        item = super().get(*args, **kwargs)
        time.sleep(0.05)
        return item


def _sched(execute, batch, deadline_s, **kw):
    return WaveScheduler(batch, execute, one_per_stream=False,
                         deadline_s=deadline_s, **kw)


def _submit(sched, n=1):
    for _ in range(n):
        sched.submit("s", WINDOW, lambda: 0)


def test_first_window_to_an_idle_scheduler_is_cut_at_once():
    ex = HeldExecute(hold=False)
    sched = _sched(ex, batch=8, deadline_s=10.0)
    try:
        t0 = time.perf_counter()
        _submit(sched)
        ex.wait_for("waves", 1)
        (wave,) = ex.waves
        assert wave.occupancy == 1
        assert wave.idle_cut and not wave.deadline_flush
        assert wave.t_built - t0 < 5.0        # long before the deadline
    finally:
        sched.close(abandon=True)


def test_deadline_cuts_while_the_compute_thread_is_busy():
    """Windows submitted while a wave executes wait for the deadline, and
    the deadline cuts them with the compute thread still busy."""
    deadline = 0.2
    ex = HeldExecute(hold=True)
    sched = _sched(ex, batch=8, deadline_s=deadline)
    try:
        _submit(sched)
        ex.wait_for("starts", 1)              # the compute thread is held
        _submit(sched, 2)
        time.sleep(deadline * 5)
        t_release = ex.release(2)
        ex.wait_for("waves", 2)
        first, second = ex.waves
        assert first.idle_cut and first.occupancy == 1
        assert second.occupancy == 2
        assert second.deadline_flush and not second.idle_cut
        assert second.t_built - second.t_oldest >= deadline
        assert second.t_built < t_release     # cut while the thread was busy
    finally:
        ex.release(8)
        sched.close(abandon=True)


def test_no_idle_cut_without_a_deadline():
    ex = HeldExecute(hold=False)
    sched = _sched(ex, batch=4, deadline_s=None)
    try:
        _submit(sched)
        time.sleep(0.3)
        assert ex.waves == []                 # strict full waves
        sched.flush(timeout=10.0)
        (wave,) = ex.waves
        assert wave.occupancy == 1
        assert not wave.idle_cut and not wave.deadline_flush
    finally:
        sched.close(abandon=True)


def test_no_idle_cut_while_a_wave_executes_or_is_queued():
    """A window waits while a wave executes, and while a full wave is
    queued behind it; it is cut only once the compute thread has finished
    both and waits again, even while it is slow to take a queued wave."""
    ex = HeldExecute(hold=True)
    sched = _sched(ex, batch=2, deadline_s=10.0, queue_depth=2)
    sched._waveq = SlowHandOff(maxsize=2)
    time.sleep(0.2)                           # the compute thread moves over
    try:
        _submit(sched)                        # idle cut: wave 0, held
        ex.wait_for("starts", 1)
        _submit(sched, 2)                     # a full wave 1, queued
        _submit(sched)                        # the window under test
        time.sleep(0.2)
        ex.release()                          # wave 0 ends, wave 1 starts
        ex.wait_for("starts", 2)
        time.sleep(0.2)
        ex.release()                          # wave 1 ends
        ex.wait_for("starts", 3)
        ex.release()
        ex.wait_for("waves", 3)
        w0, w1, w2 = ex.waves
        assert w0.idle_cut and w0.occupancy == 1
        assert w1.occupancy == 2 and not w1.idle_cut
        assert w2.idle_cut and w2.occupancy == 1
        assert w2.t_built >= ex.ends[1]       # not before wave 1 had ended
    finally:
        ex.release(8)
        sched.close(abandon=True)


def _record(t, idle_cut):
    return WaveRecord(t_done=t, compute_s=0.001, latency_s=0.002,
                      occupancy=1, batch=4, deadline_flush=not idle_cut,
                      idle_cut=idle_cut)


def test_idle_cuts_are_counted_and_merged():
    a, b = MetricsSink(), MetricsSink()
    for i, cut in enumerate([True, True, False]):
        a.record_wave(_record(float(i + 1), cut))
    b.record_wave(_record(5.0, True))
    sa = a.summary()
    assert sa["idle_cuts"] == 2 and sa["deadline_flushes"] == 1
    merged = MetricsSink.merge([a, b]).summary()
    assert merged["waves"] == 4
    assert merged["idle_cuts"] == 3
    assert merged["deadline_flushes"] == 1

