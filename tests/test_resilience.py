"""The serving reliability layer (PR 6): seeded chaos, guarded execution,
overload control.

The contract under test: a ``StreamServer`` under injected faults —
compute exceptions, latency spikes, state loss — keeps serving with zero
crashes; every stream untouched by state faults stays BIT-EXACT with the
concatenated-sequence oracle (retries and backend degradation change
latency, never results); every stream that was touched is FLAGGED
(``StreamResult.error`` / ``state_reset``), never silently wrong; and the
``faults`` block of ``metrics_summary()`` accounts for all of it."""

import threading
import time

import numpy as np
import pytest

import repro
from repro.core.qlstm import QLSTMConfig
from repro.serving import (ExecutionGuard, FaultConfig, FaultInjector,
                           InjectedFault, OverloadPolicy, ResiliencePolicy,
                           ServerOverloaded, ServingConfig, StreamServer,
                           WaveScheduler, WaveTimeout)

MODEL = QLSTMConfig(input_size=1, hidden_size=8, num_layers=2, seq_len=4)

FAST = ResiliencePolicy(max_retries=3, backoff_base_s=0.0)


@pytest.fixture(scope="module")
def sess():
    return repro.build(MODEL, seed=0).quantize()


def _windows(n, seed=0, t=4, m=1):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 1.0, (n, t, m)).astype(np.float32)


def _oracle(sess, windows):
    """Per-window predictions of one stream run stateful on the bit-exact
    ref engine — the concatenated-sequence ground truth."""
    fn = sess.compiled_stateful("ref")
    state, ys = sess.init_state(1), []
    for w in windows:
        y, state = fn(w[None], state)
        ys.append(np.asarray(y)[0])
    return ys


def _run_chaos(sess, backend, seed=11, n_streams=6, k=3, policy=FAST,
               **rates):
    """One seeded chaos run: submit k windows on each of n_streams,
    drain, return ({sid: windows}, {sid: {seq: row}}, summary, injector)."""
    xs = {f"s{i}": _windows(k, seed=50 + i) for i in range(n_streams)}
    inj = FaultInjector(seed=seed, **rates)
    cfg = ServingConfig(batch=4, backend=backend, deadline_s=0.005,
                        resilience=policy)
    rows = {}
    with StreamServer(sess, cfg, fault_injector=inj) as srv:
        for w in range(k):
            for sid in xs:
                srv.submit(sid, xs[sid][w])
        for r in srv.drain(timeout=120):
            rows.setdefault(r.stream_id, {})[r.seq] = r
        summary = srv.metrics_summary()
    return xs, rows, summary, inj


def _check_partition(sess, xs, rows, inj):
    """The chaos post-conditions: survivors bit-exact, casualties flagged
    (and bit-exact up to their first flagged window)."""
    touched = inj.lost_streams | inj.corrupted_streams
    for sid, wins in xs.items():
        oracle = _oracle(sess, wins)
        got = rows[sid]
        assert sorted(got) == list(range(len(wins)))   # no window lost
        flagged = [q for q in sorted(got)
                   if (not got[q].ok) or got[q].state_reset]
        if not flagged and sid not in touched:
            for q in sorted(got):                      # survivor: bit-exact
                np.testing.assert_array_equal(got[q].y, oracle[q])
        else:
            first = flagged[0] if flagged else len(wins)
            for q in range(first):                     # clean prefix only
                np.testing.assert_array_equal(got[q].y, oracle[q])
            for q in sorted(got):                      # errors carry no y
                if not got[q].ok:
                    assert got[q].y is None and got[q].error


# ---------------------------------------------------------------------------
# The chaos matrix: fault rates x backends (the PR's acceptance scenario)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.parametrize("backend", ["ref", "xla", "pallas"])
@pytest.mark.parametrize("rate", [0.0, 0.05, 0.2])
def test_chaos_wave_faults_absorbed_bit_exactly(sess, backend, rate):
    """Injected compute faults at 0/5/20% per attempt on every engine:
    retries absorb them, every stream completes, and every stream stays
    bit-exact with the oracle (faults change latency, never results)."""
    xs, rows, summary, inj = _run_chaos(sess, backend,
                                        wave_fault_rate=rate)
    _check_partition(sess, xs, rows, inj)
    f = summary["faults"]
    assert f["injected"]["wave_faults"] == inj.stats()["wave_faults"]
    if rate == 0.0:
        assert f["injected"]["wave_faults"] == 0 and f["retries"] == 0
    elif f["injected"]["wave_faults"] > 0:
        # Any injected fault forces at least one retry somewhere (a
        # 12-attempt full-ladder wipe-out at these rates is ~0).
        assert f["retries"] >= 1 and f["stream_errors"] == 0


@pytest.mark.slow
@pytest.mark.chaos
def test_acceptance_64_streams_20pct_faults_on_pallas(sess):
    """The PR's acceptance scenario: 64 streams through the fused pallas
    engine at a 20% per-attempt wave-fault rate — zero crashes, every
    window answered, every stream bit-exact, counters consistent with the
    injected schedule."""
    xs, rows, summary, inj = _run_chaos(sess, "pallas", seed=17,
                                        n_streams=64, k=2,
                                        wave_fault_rate=0.2)
    assert sum(len(by) for by in rows.values()) == 128
    _check_partition(sess, xs, rows, inj)
    f = summary["faults"]
    assert f["injected"] == inj.stats()
    assert f["stream_errors"] == 0 and f["sheds"] == 0
    if inj.stats()["wave_faults"]:
        assert f["retries"] >= 1


def test_chaos_injection_schedule_is_deterministic():
    """Same (seed, rates) -> the exact same raise/pass schedule; a
    different seed -> a different one (so chaos tests can assert exact
    counters)."""
    def schedule(seed):
        inj = FaultInjector(seed=seed, wave_fault_rate=0.3)
        fn = inj.wrap_fn(lambda: None)
        out = []
        for _ in range(64):
            try:
                fn()
                out.append(0)
            except InjectedFault:
                out.append(1)
        return out, inj.stats()

    a, sa = schedule(7)
    b, sb = schedule(7)
    c, _ = schedule(8)
    assert a == b and sa == sb
    assert a != c
    assert sa["wave_faults"] == sum(a) and sa["attempts"] == 64


@pytest.mark.chaos
def test_chaos_state_loss_flags_reset_not_silence(sess):
    """Lost carries (a crashed replica): the stream's next window is
    computed from the reset state and MUST come back ``state_reset=True``;
    untouched streams stay bit-exact; the resets are counted."""
    xs, rows, summary, inj = _run_chaos(sess, "ref", seed=3, k=4,
                                        state_loss_rate=0.4)
    assert inj.stats()["state_losses"] > 0      # seed 3 does inject
    _check_partition(sess, xs, rows, inj)
    n_reset = sum(r.state_reset for by in rows.values()
                  for r in by.values())
    assert n_reset > 0
    # no false flags: a reset row only ever appears on a stream the
    # injector actually touched (a loss on a stream's LAST put leaves no
    # later window to observe it, so the converse need not hold)
    for sid, by in rows.items():
        if any(r.state_reset for r in by.values()):
            assert sid in inj.lost_streams
    assert summary["faults"]["state_resets"] == n_reset


@pytest.mark.chaos
def test_chaos_state_corruption_is_recorded(sess):
    """Corrupted carries are the one fault the server cannot flag (the
    codes are plausible); the injector records the victims so tests can
    exclude them — and untouched streams still verify."""
    xs, rows, summary, inj = _run_chaos(sess, "ref", seed=5, k=3,
                                        state_corrupt_rate=0.5)
    assert inj.stats()["state_corruptions"] > 0
    assert inj.corrupted_streams
    for sid, wins in xs.items():
        if sid in inj.corrupted_streams:
            continue
        oracle = _oracle(sess, wins)
        for q, r in rows[sid].items():
            assert r.ok
            np.testing.assert_array_equal(r.y, oracle[q])


def test_wave_failure_isolated_to_error_results(sess):
    """A wave that fails on EVERY engine (100% per-attempt fault rate, no
    retries) kills no thread: each window comes back as a structured
    ``compute_failed`` row, the server stays alive, and close() is
    clean."""
    xs, rows, summary, inj = _run_chaos(
        sess, "ref", n_streams=4, k=2, wave_fault_rate=1.0,
        policy=ResiliencePolicy(max_retries=0, backoff_base_s=0.0))
    n = sum(len(by) for by in rows.values())
    assert n == 8                                # every window answered
    for by in rows.values():
        for r in by.values():
            assert not r.ok and "compute_failed" in r.error
            assert "InjectedFault" in r.error
    f = summary["faults"]
    assert f["wave_failures"] > 0
    assert f["stream_errors"] == 8


def test_degradation_and_promotion_round_trip_server(sess):
    """The preferred engine fails -> the guard serves the wave on the next
    ladder engine and (after degrade_after failures) officially degrades;
    once the engine heals, a recovery probe promotes back.  Results stay
    bit-exact through the whole round trip."""
    wins = _windows(6, seed=77)
    oracle = _oracle(sess, wins)
    cfg = ServingConfig(
        batch=2, backend="ref", deadline_s=0.005,
        resilience=ResiliencePolicy(max_retries=0, backoff_base_s=0.0,
                                    degrade_after=1, promote_after=1))
    srv = StreamServer(sess, cfg)
    preferred, real_fn = srv._fns[0][0]
    broken = {"on": True}

    def flaky(*args, **kwargs):
        if broken["on"]:
            raise RuntimeError("simulated engine outage")
        return real_fn(*args, **kwargs)

    srv._fns[0][0] = (preferred, flaky)
    try:
        rows = []
        for w in range(3):                        # outage: waves degrade
            srv.submit("s", wins[w])
            rows += srv.drain(timeout=60)
        assert srv.metrics_summary()["faults"]["degraded"]
        assert srv.health()["status"] == "degraded"
        broken["on"] = False                      # engine heals
        for w in range(3, 6):                     # probe promotes back
            srv.submit("s", wins[w])
            rows += srv.drain(timeout=60)
        f = srv.metrics_summary()["faults"]
        assert f["degradations"] >= 1 and f["promotions"] >= 1
        assert f["backend"] == preferred and not f["degraded"]
        assert srv.health()["status"] == "ok"
        by = {r.seq: r for r in rows}
        for q in range(6):                        # the bit-exactness claim
            assert by[q].ok
            np.testing.assert_array_equal(by[q].y, oracle[q])
        # the outage waves were carried by a non-preferred engine
        assert {by[q].backend for q in range(3)} != {preferred}
    finally:
        srv.close()


@pytest.mark.parametrize("stateful", [True, False],
                         ids=["device", "stateless"])
def test_preferred_engine_compile_refusal_raises_at_first_submit(
        sess, monkeypatch, stateful):
    """A preferred engine the compiler refuses is an error, not a fault to
    degrade past: the first submit raises, no wave runs, and the guard
    never counts a retry, wave failure or degradation.  The stand-in
    refusal is the fused pallas engine asked to compile its kernels for
    the chip (interpret=False) on a CPU."""
    import repro.backends.pallas as pallas_backend
    # A fresh session: the compiled-closure cache must not hold the
    # interpret-mode program already.
    fresh = repro.build(MODEL, params=sess.params, seed=0).quantize()
    monkeypatch.setattr(pallas_backend, "_interpret", lambda: False)
    srv = StreamServer(fresh, batch=2, deadline_s=0.005,
                       stateful=stateful)
    try:
        assert srv.guard.ladder[0] == "pallas"
        with pytest.raises(RuntimeError, match="'pallas' failed to compile"):
            srv.submit("s", _windows(1)[0])
        g = srv.guard.stats()
        assert (g["waves"], g["retries"], g["wave_failures"],
                g["degradations"]) == (0, 0, 0, 0)
        assert srv.health()["status"] == "ok" and not srv.poll()
    finally:
        srv.close(abandon=True)


# ---------------------------------------------------------------------------
# ExecutionGuard unit tests (plain callables, no server)
# ---------------------------------------------------------------------------

def test_guard_retries_with_backoff_then_succeeds():
    calls = {"n": 0}

    def flaky(x):
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return x * 2

    g = ExecutionGuard(("a",), ResiliencePolicy(max_retries=2,
                                                backoff_base_s=0.0))
    out = g.run([("a", flaky)], 21)
    assert out.ok and out.value == 42 and out.backend == "a"
    assert out.retries == 2 and len(out.attempt_errors) == 2
    assert g.stats()["retries"] == 2 and g.stats()["wave_failures"] == 0


def test_guard_total_failure_reports_last_error():
    g = ExecutionGuard(("a", "b"), ResiliencePolicy(max_retries=0,
                                                    backoff_base_s=0.0))
    out = g.run([("a", lambda: 1 / 0), ("b", lambda: [][1])])
    assert not out.ok and out.value is None
    assert "IndexError" in out.error
    assert len(out.attempt_errors) == 2
    assert g.stats()["wave_failures"] == 1


def test_guard_timeout_abandons_attempt_and_degrades():
    """A hung attempt is abandoned at wave_timeout_s (never waited on) and
    the wave lands on the next ladder engine."""
    release = threading.Event()

    def hung(x):
        release.wait(5.0)
        return -1

    g = ExecutionGuard(("slow", "fast"), ResiliencePolicy(
        max_retries=0, backoff_base_s=0.0, wave_timeout_s=0.05))
    t0 = time.perf_counter()
    out = g.run([("slow", hung), ("fast", lambda x: x + 1)], 1)
    assert out.ok and out.value == 2 and out.backend == "fast"
    assert out.timeouts == 1
    assert time.perf_counter() - t0 < 2.0        # did not wait the 5 s
    assert g.stats()["timeouts"] == 1
    assert g.stats()["abandoned_attempts"] == 1
    release.set()
    g.close()


def test_guard_degrade_then_probe_then_promote():
    """The full ladder state machine on plain lambdas: degrade after
    ``degrade_after`` preferred failures, probe after ``promote_after``
    clean degraded waves, promote when the probe lands."""
    broken = {"on": True}

    def pallas(x):
        if broken["on"]:
            raise RuntimeError("down")
        return ("pallas", x)

    fns = [("pallas", pallas), ("xla", lambda x: ("xla", x))]
    g = ExecutionGuard(("pallas", "xla"), ResiliencePolicy(
        max_retries=0, backoff_base_s=0.0, degrade_after=2,
        promote_after=2))
    assert g.run(fns, 0).backend == "xla"        # carried, not yet degraded
    assert not g.degraded
    out = g.run(fns, 1)
    assert out.degraded and g.degraded           # second failure: degrade
    assert g.backend == "xla"
    assert g.run(fns, 2).backend == "xla"        # clean degraded wave 1
    assert g.run(fns, 3).backend == "xla"        # clean degraded wave 2
    broken["on"] = False
    out = g.run(fns, 4)                          # probe fires and lands
    assert out.promoted and out.backend == "pallas"
    assert not g.degraded and g.backend == "pallas"
    s = g.stats()
    assert s["degradations"] == 1 and s["promotions"] == 1
    assert s["probes"] == 1


def test_guard_failed_probe_resets_clean_streak():
    """A probe that fails must wait another promote_after clean waves
    before re-probing — not hammer the broken engine every wave."""
    fns = [("a", lambda: 1 / 0), ("b", lambda: "b")]
    g = ExecutionGuard(("a", "b"), ResiliencePolicy(
        max_retries=0, backoff_base_s=0.0, degrade_after=1,
        promote_after=2))
    g.run(fns)                                   # degrade to b
    assert g.degraded
    g.run(fns)                                   # clean 1
    g.run(fns)                                   # clean 2
    g.run(fns)                                   # probe -> a fails -> b
    assert g.stats()["probes"] == 1
    g.run(fns)                                   # clean 1 again: NO probe
    assert g.stats()["probes"] == 1
    g.run(fns)                                   # clean 2
    g.run(fns)                                   # probe #2
    assert g.stats()["probes"] == 2


def test_resilience_policy_validation_and_backoff():
    with pytest.raises(ValueError, match="max_retries"):
        ResiliencePolicy(max_retries=-1)
    with pytest.raises(ValueError, match="backoff_factor"):
        ResiliencePolicy(backoff_factor=0.5)
    with pytest.raises(ValueError, match="wave_timeout_s"):
        ResiliencePolicy(wave_timeout_s=0.0)
    with pytest.raises(ValueError, match="degrade_after"):
        ResiliencePolicy(degrade_after=0)
    p = ResiliencePolicy(backoff_base_s=0.01, backoff_factor=2.0,
                         backoff_max_s=0.05)
    assert p.backoff_s(1) == pytest.approx(0.01)
    assert p.backoff_s(2) == pytest.approx(0.02)
    assert p.backoff_s(10) == pytest.approx(0.05)     # capped


def test_fault_config_validation():
    with pytest.raises(ValueError, match="wave_fault_rate"):
        FaultConfig(wave_fault_rate=1.5)
    with pytest.raises(ValueError, match="latency_spike_s"):
        FaultConfig(latency_spike_s=-1.0)
    with pytest.raises(ValueError, match="not both"):
        FaultInjector(FaultConfig(), wave_fault_rate=0.1)


# ---------------------------------------------------------------------------
# Overload: admission control and deadline-aware shedding
# ---------------------------------------------------------------------------

def test_overload_policy_validation():
    with pytest.raises(ValueError, match="admission"):
        OverloadPolicy(admission="panic")
    with pytest.raises(ValueError, match="reject_miss_rate"):
        OverloadPolicy(reject_miss_rate=2.0)
    with pytest.raises(ValueError, match="shed_after_s"):
        OverloadPolicy(shed_after_s=0.0)


def test_admission_control_rejects_when_saturated():
    """With a wedged compute thread and a reject-mode policy, submit
    raises ServerOverloaded in bounded time instead of blocking forever."""
    release = threading.Event()
    sched = WaveScheduler(2, lambda wave: release.wait(10.0),
                          one_per_stream=False, deadline_s=None,
                          queue_depth=1, max_pending=2,
                          overload=OverloadPolicy(admission="reject",
                                                  reject_miss_rate=0.0))
    try:
        with pytest.raises(ServerOverloaded, match="admission rejected"):
            for i in range(64):                  # must trip well before 64
                sched.submit("s", np.zeros((4, 1), np.float32), lambda: 0)
        assert sched.stats()["rejections"] >= 1
    finally:
        release.set()
        sched.close(abandon=True)


def test_deadline_shedding_drops_hopeless_windows(sess):
    """Windows older than shed_after_s are dropped uncomputed: the client
    gets an ``error="shed"`` row, the stream's carry is dropped, and its
    NEXT window restarts flagged ``state_reset=True``."""
    wins = _windows(2, seed=9)
    cfg = ServingConfig(batch=8, deadline_s=None, backend="ref",
                        resilience=FAST,
                        overload=OverloadPolicy(admission="block",
                                                shed_after_s=0.05))
    with StreamServer(sess, cfg) as srv:
        srv.submit("s", wins[0])
        # batch 8, no deadline: the window can only leave pending by aging
        # past shed_after_s.
        deadline = time.perf_counter() + 10.0
        rows = []
        while not rows and time.perf_counter() < deadline:
            rows = srv.poll(timeout=0.2)
        assert len(rows) == 1
        (r,) = rows
        assert not r.ok and r.error == "shed" and r.y is None
        assert srv.metrics_summary()["faults"]["sheds"] == 1
        srv.submit("s", wins[1])
        srv.flush(timeout=30)
        (r2,) = srv.poll()
        assert r2.ok and r2.state_reset           # hole in the recurrence
        # windows[1] from the reset carry == a fresh stream's first window
        np.testing.assert_array_equal(
            r2.y, _oracle(sess, wins[1:2])[0])


def test_scheduler_error_clears_after_recovery():
    """A transient compute-thread exception does not poison every later
    wave: in-flight waves keep executing, and the first clean one clears
    the stored error (counted as a recovery) so submit/flush work again."""
    both_in = threading.Event()
    calls = []

    def execute(wave):
        both_in.wait(10.0)            # hold wave 1 until wave 2 is queued
        calls.append(wave)
        if len(calls) == 1:
            raise RuntimeError("transient device error")

    sched = WaveScheduler(1, execute, one_per_stream=False,
                          deadline_s=None, queue_depth=2)
    try:
        sched.submit("s", np.zeros((4, 1), np.float32), lambda: 0)
        sched.submit("s", np.zeros((4, 1), np.float32), lambda: 1)
        both_in.set()
        deadline = time.perf_counter() + 10.0
        while sched.stats()["recoveries"] == 0 \
                and time.perf_counter() < deadline:
            time.sleep(0.005)
        assert sched.stats()["recoveries"] == 1   # error was set, then
        assert sched.error is None                # cleared by wave 2
        # the scheduler accepts work again — no stale re-raise
        sched.submit("s", np.zeros((4, 1), np.float32), lambda: 2)
        sched.flush(timeout=10)
    finally:
        sched.close(abandon=True)


def test_close_reports_leaked_threads():
    """close() with a wave wedged inside the datapath joins with a timeout
    and REPORTS the leaked thread instead of hanging forever."""
    release = threading.Event()
    sched = WaveScheduler(1, lambda wave: release.wait(30.0),
                          one_per_stream=False, deadline_s=None,
                          queue_depth=1)
    try:
        sched.submit("s", np.zeros((4, 1), np.float32), lambda: 0)
        time.sleep(0.1)                           # let compute pick it up
        leaked = sched.close(abandon=True, timeout=0.3)
        assert leaked == ["wave-compute"]
        assert sched.leaked_threads == ["wave-compute"]
    finally:
        release.set()


# ---------------------------------------------------------------------------
# submit() validation — malformed input never reaches the compute thread
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("window,match", [
    (np.full((4, 1), np.nan, np.float32), "NaN"),
    (np.zeros((4,), np.float32), r"\(T, M\)"),
    (np.zeros((4, 3), np.float32), "input_size"),
    (np.zeros((0, 1), np.float32), "input_size"),
    ([["not", "numbers"], ["at", "all"]], "not convertible"),
])
def test_submit_rejects_malformed_windows(sess, window, match):
    with StreamServer(sess, batch=2, deadline_s=0.005) as srv:
        with pytest.raises(ValueError, match=match):
            srv.submit("s", window)
        assert srv.metrics_summary()["waves"] == 0    # nothing computed
        assert srv.drain() == []


# ---------------------------------------------------------------------------
# Concurrency stress: submit/end_stream churn under chaos
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_concurrent_submit_end_stream_stress(sess):
    """4 client threads x 4 streams each, ending and reviving their
    streams mid-run, under a 5% injected fault rate: no deadlock, no
    crash, every submitted window answered exactly once, per-thread
    per-generation rows in submission order."""
    inj = FaultInjector(seed=21, wave_fault_rate=0.05)
    cfg = ServingConfig(batch=8, deadline_s=0.002, backend="ref",
                        resilience=FAST)
    srv = StreamServer(sess, cfg, fault_injector=inj)
    n_threads, n_streams, k = 4, 4, 6
    submitted = [0] * n_threads
    errors = []

    def client(ti):
        try:
            rng = np.random.default_rng(100 + ti)
            for sid_i in range(n_streams):
                sid = f"t{ti}-{sid_i}"
                for w in range(k):
                    win = rng.uniform(0, 1, (MODEL.seq_len, 1)) \
                             .astype(np.float32)
                    srv.submit(sid, win)
                    submitted[ti] += 1
                    if w == 2:                   # churn: end mid-stream
                        srv.end_stream(sid)
        except BaseException as e:               # surfaced to the assert
            errors.append(e)

    threads = [threading.Thread(target=client, args=(ti,))
               for ti in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert not errors, errors
    rows = srv.drain(timeout=120)
    assert srv.close() == []
    assert len(rows) == sum(submitted) == n_threads * n_streams * k
    # per (stream, generation) the seq numbers the server handed out are
    # consecutive from 0 — no duplicate or lost (stream_id, seq) keys
    per_stream = {}
    for r in rows:
        per_stream.setdefault(r.stream_id, []).append(r.seq)
    for sid, seqs in per_stream.items():
        assert sorted(seqs) == sorted(list(range(3)) * 2), sid


# ---------------------------------------------------------------------------
# Surfaces: ladder API, metrics counters
# ---------------------------------------------------------------------------

def test_api_degradation_ladder(sess):
    ladder = sess.degradation_ladder()
    assert set(ladder) == {"ref", "xla", "pallas"}
    assert ladder[0] == sess.plan["stateful_backend"]
    assert sess.degradation_ladder(backend="xla")[0] == "xla"
    from repro import backends
    assert ladder == backends.degradation_ladder(sess.model, sess.accel)


def test_metrics_sink_named_counters():
    from repro.serving import MetricsSink
    m = MetricsSink()
    assert m.counters() == {}
    m.count("sheds")
    m.count("state_resets", 3)
    m.count("sheds")
    assert m.counters() == {"sheds": 2, "state_resets": 3}


def test_eviction_reset_is_flagged_on_returning_stream(sess):
    """Satellite 1 end-to-end: a stream LRU-evicted while a window is
    still in flight keeps its numbering, and the in-flight window —
    computed from the reset carry — comes back ``state_reset=True`` and
    bumps the counter (silent zeros before this PR)."""
    xs = {sid: _windows(2, seed=60 + i) for i, sid in enumerate("ab")}
    with StreamServer(sess, batch=2, deadline_s=None,
                      max_streams=1) as srv:
        # waves assemble oldest-first, one per stream: {a0,b0} then {a1,b1}
        for w in range(2):
            for sid in "ab":
                srv.submit(sid, xs[sid][w])
        rows = {(r.stream_id, r.seq): r for r in srv.drain(timeout=30)}
        # wave 1's scatter (capacity 1) evicted "a"; its in-flight second
        # window ran from the reset carry and says so
        assert rows[("a", 1)].state_reset
        assert rows[("a", 1)].ok                 # still a real prediction
        assert not rows[("a", 0)].state_reset    # first window: fresh is
        assert not rows[("b", 0)].state_reset    # normal, not a reset
        assert srv.metrics_summary()["faults"]["state_resets"] >= 1
        np.testing.assert_array_equal(           # == a fresh stream's first
            rows[("a", 1)].y, _oracle(sess, xs["a"][1:2])[0])


def test_wave_timeout_exception_type():
    assert issubclass(WaveTimeout, RuntimeError)
    assert issubclass(InjectedFault, RuntimeError)
    assert issubclass(ServerOverloaded, RuntimeError)


# ---------------------------------------------------------------------------
# Device-resident state under chaos (the slot table's partition contract)
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_chaos_state_loss_on_device_store(sess):
    """The loss drill on the fused kernel's slot table, with wave faults
    on top: a committed row whose slot is dropped flags the stream's next
    window ``state_reset``, survivors stay bit-exact with the oracle, and
    only slot ids travel per wave."""
    xs, rows, summary, inj = _run_chaos(sess, "pallas", seed=3, k=4,
                                        state_loss_rate=0.15,
                                        wave_fault_rate=0.1)
    assert summary["health"]["ladder"][0] == "pallas"
    assert inj.stats()["state_losses"] > 0      # seed 3 does inject
    _check_partition(sess, xs, rows, inj)
    for sid, by in rows.items():
        if any(r.state_reset for r in by.values()):
            assert sid in inj.lost_streams
    assert summary["state_transfer"]["slot_id_bytes"] > 0


@pytest.mark.chaos
def test_chaos_state_corruption_on_device_store(sess):
    """Corrupted table rows (the device form of put-corruption) are
    recorded by the injector; untouched streams still verify bit-exactly
    against the oracle through the slot-gathered path."""
    xs, rows, summary, inj = _run_chaos(sess, "pallas", seed=5, k=3,
                                        state_corrupt_rate=0.5)
    assert summary["health"]["ladder"][0] == "pallas"
    assert inj.stats()["state_corruptions"] > 0
    for sid, wins in xs.items():
        if sid in inj.corrupted_streams:
            continue
        oracle = _oracle(sess, wins)
        for q, r in rows[sid].items():
            assert r.ok
            np.testing.assert_array_equal(r.y, oracle[q])


@pytest.mark.slow
def test_concurrent_device_store_stress(sess):
    """Satellite acceptance: N client threads churning end_stream against
    the device slot table under injected wave faults AND state loss —
    no deadlock, every window answered exactly once, per-generation seq
    numbering intact; streams the injector never touched are bit-exact
    with the oracle in BOTH generations; every reset flag traces back to
    a real cause (no silent corruption).  Legitimate causes: an injected
    slot loss, a wave the whole ladder failed (its carries are popped),
    or — first generation only — end_stream outrunning the compute
    thread, which tombstones the dying generation's in-flight carries at
    gather time (the slot table's pre-compute tombstone check)."""
    inj = FaultInjector(seed=29, wave_fault_rate=0.1, state_loss_rate=0.12)
    cfg = ServingConfig(batch=8, deadline_s=0.002, backend="pallas",
                        resilience=FAST)
    srv = StreamServer(sess, cfg, fault_injector=inj)
    n_threads, n_streams, k = 4, 3, 6
    windows = {}                                 # sid -> the k windows
    errors = []

    def client(ti):
        try:
            rng = np.random.default_rng(200 + ti)
            for sid_i in range(n_streams):
                sid = f"t{ti}-{sid_i}"
                wins = rng.uniform(0, 1, (k, MODEL.seq_len, 1)) \
                          .astype(np.float32)
                windows[sid] = wins
                for w in range(k):
                    srv.submit(sid, wins[w])
                    if w == 2:                   # churn: end mid-stream
                        srv.end_stream(sid)
        except BaseException as e:               # surfaced to the assert
            errors.append(e)

    threads = [threading.Thread(target=client, args=(ti,))
               for ti in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert not errors, errors
    rows = srv.drain(timeout=120)
    summary = srv.metrics_summary()
    assert srv.close() == []
    assert len(rows) == n_threads * n_streams * k
    assert summary["state_transfer"]["slot_id_bytes"] > 0
    per_stream = {}
    for r in rows:
        per_stream.setdefault(r.stream_id, []).append(r)
    verified = 0
    for sid, rs in per_stream.items():
        # Two generations of 3 (end_stream after window 2), rows arriving
        # in submission order within the stream.
        assert [r.seq for r in rs] == [0, 1, 2, 0, 1, 2], sid
        for idx, r in enumerate(rs):
            if not r.ok:
                assert r.y is None and r.error
            if r.state_reset and idx >= 3:
                # Second generation: the end-churn tombstone cannot reach
                # it, so a reset must trace to an injected loss or to a
                # failed wave that popped the stream's carry.
                assert sid in inj.lost_streams \
                    or any(not p.ok for p in rs[:idx]), sid
        if sid in inj.corrupted_streams:
            continue        # corruption is silent by design: skip values
        # Generations are state-independent (end_stream resets the carry),
        # so each is judged on its own: a generation with no error and no
        # reset flag promised faithful chaining — hold it to bit-exact.
        for gen, lo in ((rs[:3], 0), (rs[3:], 3)):
            if any((not r.ok) or r.state_reset for r in gen):
                continue    # flagged: the casualty was advertised
            oracle = _oracle(sess, windows[sid][lo:lo + 3])
            for q, r in enumerate(gen):
                np.testing.assert_array_equal(r.y, oracle[q])
            verified += 1
    assert verified >= 6     # the exactness sweep must not be vacuous
