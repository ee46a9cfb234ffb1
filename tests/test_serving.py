"""The streaming serving subsystem (`repro.serving`).

The load-bearing guarantee is STATEFUL CARRY: feeding a stream
window-by-window through the server, with its (h, c) carried in the
device slot table between windows, is bit-identical on the int path to running
the stream's concatenated sequence through the accelerator in one call.
Plus: deadline-bounded partial waves, LRU eviction semantics, padding
drop, and compat-wrapper parity for ``Accelerator.serve`` /
``WaveBatcher.for_accelerator``."""

import dataclasses
import time
from collections import OrderedDict

import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import backends
from repro.core.accelerator import AcceleratorConfig
from repro.core.qlstm import QLSTMConfig, init_int_state
from repro.kernels.qlstm_cell import state_table_shape
from repro.serving import ServingConfig, StreamServer, serve_windows

MODEL = QLSTMConfig(input_size=1, hidden_size=8, num_layers=2, seq_len=4)


@pytest.fixture(scope="module")
def sess():
    return repro.build(MODEL, seed=0).quantize()


def _windows(n, seed=0, t=4, m=1):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 1.0, (n, t, m)).astype(np.float32)


# ---------------------------------------------------------------------------
# Stateful carry — the bit-exactness contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["ref", "xla", "pallas"])
def test_stateful_carry_equals_unbatched_sequence(sess, backend):
    """k windows through run_stateful == one forward over the k*T sequence,
    bit-exact at the integer-code level, multi-layer."""
    from repro.core import fixed_point as fxp
    k = 3
    x = _windows(1, seed=1, t=MODEL.seq_len * k)
    x_int = fxp.quantize(jnp.asarray(x), sess.model.fxp)
    bk = backends.get(backend)
    y_full = bk.run(sess.qparams, x_int, sess.model, sess.accel)

    state = init_int_state(sess.model, 1)
    t = MODEL.seq_len
    for w in range(k):
        y, state = bk.run_stateful(sess.qparams, x_int[:, w * t:(w + 1) * t],
                                   sess.model, sess.accel, state)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_full))


def test_stateful_rejects_mismatched_state_length(sess):
    """A carry built for a different num_layers must fail loudly at the
    boundary — zip() truncation would silently skip whole layers."""
    from repro.core import fixed_point as fxp
    x_int = fxp.quantize(jnp.asarray(_windows(1, seed=2)), sess.model.fxp)
    wrong = init_int_state(MODEL, 1)[:1]          # 1 layer, model has 2
    with pytest.raises(ValueError, match="layer"):
        backends.get("ref").run_stateful(sess.qparams, x_int, sess.model,
                                         sess.accel, wrong)
    from repro.core.qlstm import forward_int_stateful
    with pytest.raises(ValueError, match="layer"):
        forward_int_stateful(sess.qparams, x_int, sess.model, wrong)


def test_stream_server_carry_equals_unbatched_sequence(sess):
    """The same guarantee end-to-end through StreamServer: interleaved
    multiplexed streams each match their own one-shot concatenated run."""
    k, t = 3, MODEL.seq_len
    streams = {f"c{i}": _windows(k, seed=10 + i) for i in range(5)}
    with StreamServer(sess, batch=4, deadline_s=0.005, max_streams=16) as srv:
        for w in range(k):
            for sid, xs in streams.items():
                srv.submit(sid, xs[w])
        results = srv.drain()
    by = {}
    for r in results:
        by.setdefault(r.stream_id, {})[r.seq] = r.y
    for sid, xs in streams.items():
        assert sorted(by[sid]) == list(range(k))  # per-stream order complete
        full = np.asarray(sess.infer(
            jnp.asarray(xs.reshape(1, k * t, 1)), path="int"))
        np.testing.assert_array_equal(by[sid][k - 1], full[0])
        # every intermediate window matches its prefix too
        for w in range(k - 1):
            prefix = np.asarray(sess.infer(
                jnp.asarray(xs[:w + 1].reshape(1, (w + 1) * t, 1)),
                path="int"))
            np.testing.assert_array_equal(by[sid][w], prefix[0])


def test_end_stream_resets_carry(sess):
    """After end_stream, the same id restarts from the zero reset state
    (and its sequence numbering restarts at 0)."""
    x = _windows(2, seed=3)
    fresh = np.asarray(sess.infer(jnp.asarray(x[1:2]), path="int"))
    with StreamServer(sess, batch=2, deadline_s=0.005) as srv:
        assert srv.submit("s", x[0]) == 0
        srv.flush()
        srv.end_stream("s")
        assert srv.submit("s", x[1]) == 0   # fresh stream, fresh numbering
        results = srv.drain()
    np.testing.assert_array_equal(results[-1].y, fresh[0])


def test_end_stream_stateless_restarts_numbering(sess):
    """On a stateless server, end_stream still forgets the stream: its
    ``_seq`` entry is pruned (the only bound on rotating client ids in
    this mode) and numbering restarts at 0."""
    x = _windows(2, seed=17)
    with StreamServer(sess, batch=2, stateful=False,
                      deadline_s=0.005) as srv:
        assert srv.submit("s", x[0]) == 0
        srv.drain()
        srv.end_stream("s")
        assert "s" not in srv._seq
        assert srv.submit("s", x[1]) == 0   # fresh numbering
        srv.drain()


def test_end_stream_during_scatter_is_not_undone(sess):
    """end_stream while the stream's wave program runs: the program still
    scatters the carry into the stream's slot, but end_stream released
    that slot under the lock the wave's slot transaction took, so the
    ended carry is unreachable — the stream's next window starts from the
    reset carry, with its numbering restarted and no reset flag."""
    import threading
    x = _windows(2, seed=18)
    fresh = np.asarray(sess.infer(jnp.asarray(x[1:2]), path="int"))
    with StreamServer(sess, batch=2, deadline_s=0.01) as srv:
        name, fn = srv._fns[0][0]
        in_call, release = threading.Event(), threading.Event()

        def slow(*args):
            in_call.set()              # the wave program is running
            release.wait(5.0)
            return fn(*args)

        srv._fns[0][0] = (name, slow)
        srv.submit("s", x[0])
        assert in_call.wait(10.0)
        srv.end_stream("s")
        assert "s" not in srv.states
        release.set()
        srv.flush(timeout=30)
        assert "s" not in srv.states   # the ended carry stayed dead
        assert srv.submit("s", x[1]) == 0
        results = srv.drain(timeout=30)
    assert results[-1].seq == 0 and not results[-1].state_reset
    np.testing.assert_array_equal(results[-1].y, fresh[0])


def test_end_stream_with_window_in_flight(sess):
    """end_stream issued while the stream's window is still pending: the
    in-flight window's carry must NOT be re-stored behind the reset, so
    the next window still starts from zero."""
    x = _windows(2, seed=13)
    fresh = np.asarray(sess.infer(jnp.asarray(x[1:2]), path="int"))
    with StreamServer(sess, batch=2, deadline_s=0.05) as srv:
        srv.submit("s", x[0])
        srv.end_stream("s")        # no flush: window 0 may still be queued
        srv.submit("s", x[1])
        results = srv.drain()
    np.testing.assert_array_equal(results[-1].y, fresh[0])
    assert len(srv.states) <= 1    # no resurrected carry for generation 0


# ---------------------------------------------------------------------------
# Deadline flush / padding semantics
# ---------------------------------------------------------------------------

def test_deadline_flushes_partial_wave(sess):
    """A slow stream is not stuck behind a full-wave quorum: with 3 windows
    against batch=8, a padded partial wave is cut (at once for the idle
    compute thread, else by the deadline) and exactly 3 predictions come
    back (padding dropped).  The deadline cut itself, with the compute
    thread busy, is pinned in test_idle_cut.py."""
    x = _windows(3, seed=4)
    srv = StreamServer(sess, ServingConfig(batch=8, stateful=False,
                                           deadline_s=0.05))
    try:
        for i in range(3):
            srv.submit(None, x[i])
        results = []
        end = time.perf_counter() + 30.0
        while len(results) < 3 and time.perf_counter() < end:
            results.extend(srv.poll(timeout=1.0))
        assert len(results) == 3
        m = srv.metrics_summary()
        assert m["deadline_flushes"] + m["idle_cuts"] >= 1
        assert m["samples"] == 3 and m["padded_slots"] >= 5
        want = np.asarray(sess.infer(jnp.asarray(x), path="int"))
        got = np.stack([r.y for r in sorted(results, key=lambda r: r.seq)])
        np.testing.assert_array_equal(got, want)
    finally:
        srv.close(abandon=True)


def test_serve_final_partial_wave_pads_and_drops(sess):
    """Accelerator.serve documented padding semantics: 11 windows at
    batch=4 -> exactly 11 predictions, bit-equal to batched infer; the
    padded slots of the final wave are never yielded."""
    x = _windows(11, seed=5)
    preds = list(sess.serve(iter(x), batch=4))
    assert len(preds) == 11          # never the padding's outputs
    want = np.asarray(sess.infer(jnp.asarray(x), path="int"))
    np.testing.assert_array_equal(np.stack(preds), want)


# ---------------------------------------------------------------------------
# LRU eviction
# ---------------------------------------------------------------------------

def test_eviction_resets_stream_to_zero_state(sess):
    """An evicted stream's next window behaves like a brand new stream:
    its prediction equals the zero-carry (fresh) prediction, not the
    continued-sequence one."""
    xs = {sid: _windows(2, seed=20 + i)
          for i, sid in enumerate(["s1", "s2", "s3"])}
    with StreamServer(sess, batch=4, deadline_s=0.005,
                      max_streams=2) as srv:
        for sid in ("s1", "s2", "s3"):       # 3 carries into capacity 2:
            srv.submit(sid, xs[sid][0])      # s1 is evicted at scatter
        srv.flush()
        assert srv.states.stats()["evictions"] == 1
        assert "s1" not in srv.states
        # eviction forgets s1 entirely: carry AND numbering restart
        assert srv.submit("s1", xs["s1"][1]) == 0
        assert srv.submit("s2", xs["s2"][1]) == 1
        results = srv.drain()
    # results arrive in wave order, so the reborn ("s1", 0) overwrites the
    # first-generation row of the same key
    by = {(r.stream_id, r.seq): r.y for r in results}
    # s1 restarted from zeros -> equals the fresh single-window run
    fresh = np.asarray(sess.infer(jnp.asarray(xs["s1"][1:2]), path="int"))
    np.testing.assert_array_equal(by[("s1", 0)], fresh[0])
    # s2 kept its carry -> equals the concatenated two-window run
    cont = np.asarray(sess.infer(
        jnp.asarray(xs["s2"].reshape(1, 2 * MODEL.seq_len, 1)), path="int"))
    np.testing.assert_array_equal(by[("s2", 1)], cont[0])


def test_eviction_with_window_in_flight_keeps_numbering(sess):
    """A victim with a window still in the pipeline keeps its sequence
    numbering (pruning it would hand out duplicate (stream_id, seq) keys
    for the undelivered in-flight results); a victim that stays evicted
    with nothing in flight is forgotten entirely."""
    xs = {sid: _windows(2, seed=40 + i) for i, sid in enumerate("ab")}
    with StreamServer(sess, batch=2, deadline_s=None,
                      max_streams=1) as srv:
        # waves assemble oldest-first, one per stream: {a0,b0} then {a1,b1}
        assert srv.submit("a", xs["a"][0]) == 0
        assert srv.submit("b", xs["b"][0]) == 0
        assert srv.submit("a", xs["a"][1]) == 1   # numbering survives the
        assert srv.submit("b", xs["b"][1]) == 1   # wave-1 eviction of "a"
        srv.drain(timeout=30)
        # store capacity 1: wave 2 leaves only "b" live; "a" has nothing
        # in flight any more, so it is forgotten entirely
        assert srv.submit("a", xs["a"][0]) == 0   # fresh stream
        assert srv.submit("b", xs["b"][0]) == 2   # continued stream
        srv.drain(timeout=30)


@pytest.mark.slow
def test_max_results_backpressure_and_abandon(sess):
    """max_results bounds computed-but-unpolled results: with a concurrent
    poller every prediction still arrives; with a stalled consumer,
    close(abandon=True) must not hang on the full results queue."""
    import threading
    x = _windows(12, seed=41)
    got = []
    with StreamServer(sess, batch=2, stateful=False, deadline_s=0.005,
                      max_results=2) as srv:
        stop = threading.Event()

        def consume():
            while not stop.is_set() or len(got) < 12:
                got.extend(srv.poll(timeout=0.05))
                if len(got) >= 12:
                    return

        t = threading.Thread(target=consume)
        t.start()
        for w in x:
            srv.submit(None, w)
        srv.flush(timeout=60)
        stop.set()
        t.join(30)
    assert len(got) == 12
    want = np.asarray(sess.infer(jnp.asarray(x), path="int"))
    np.testing.assert_array_equal(
        np.stack([r.y for r in sorted(got, key=lambda r: r.seq)]), want)
    # stalled consumer: results queue fills; abandon must still return
    srv2 = StreamServer(sess, batch=2, stateful=False, deadline_s=0.005,
                        max_results=1)
    for w in x[:6]:
        srv2.submit(None, w)
    time.sleep(0.5)                    # let the pipeline wedge on results
    srv2.close(abandon=True)           # must not hang


def test_same_wave_eviction_keeps_restored_stream_consistent(sess):
    """More distinct streams per wave than max_streams: a stream evicted
    by an earlier slot's put but re-stored by its own later slot of the
    SAME wave was never really forgotten — it must keep both its carry
    and its sequence numbering (carry-without-numbering would report a
    continued stream as seq 0)."""
    xs = {sid: _windows(2, seed=30 + i)
          for i, sid in enumerate(["a", "b", "c"])}
    with StreamServer(sess, batch=4, deadline_s=0.005,
                      max_streams=2) as srv:
        for w in range(2):
            for sid in ("a", "b", "c"):
                srv.submit(sid, xs[sid][w])
            srv.flush(timeout=30)
        # every live carry still has its numbering (forgotten means BOTH)
        live = {sid for sid in ("a", "b", "c") if sid in srv.states}
        assert all(sid in srv._seq for sid in live), (live, dict(srv._seq))
        # a surviving stream continues: next window is seq 2, and its
        # prediction equals the three-window concatenated run
        survivor = sorted(live)[-1]
        assert srv.submit(survivor, xs[survivor][0]) == 2
        results = srv.drain()
    cont = np.asarray(sess.infer(jnp.asarray(np.concatenate(
        [xs[survivor][0], xs[survivor][1], xs[survivor][0]])[None]),
        path="int"))
    last = [r for r in results if r.stream_id == survivor and r.seq == 2][0]
    np.testing.assert_array_equal(last.y, cont[0])


# ---------------------------------------------------------------------------
# Compat wrappers / selection
# ---------------------------------------------------------------------------

def test_serve_compat_parity_with_serve_windows(sess):
    """Accelerator.serve is a thin wrapper over serving.serve_windows."""
    x = _windows(9, seed=6)
    a = np.stack(list(sess.serve(iter(x), batch=4)))
    b = np.stack(list(serve_windows(sess, iter(x), batch=4)))
    np.testing.assert_array_equal(a, b)


def test_wave_batcher_delegates_to_serving(sess):
    from repro.launch.batcher import WaveBatcher
    x = _windows(7, seed=7)
    b = WaveBatcher.for_accelerator(sess, batch_size=4)
    rids = [b.submit_window(w) for w in x]
    out = b.run()
    want = np.asarray(sess.infer(jnp.asarray(x), path="int"))
    np.testing.assert_array_equal(np.stack([out[r] for r in rids]), want)


def test_stateful_requires_int_path():
    with pytest.raises(ValueError, match="stateful"):
        ServingConfig(path="float", stateful=True)


def test_stateful_backend_selection(sess):
    """Plan metadata: the stateful resolution now follows the stateless
    one — fused configs carry state on the fused pallas kernel itself
    (its VMEM scratch is seeded from the carry), per-step configs on
    xla; every engine is stateful-capable."""
    assert sess.plan["stateful_backend"] == "pallas"
    assert sess.plan["stateful_backend"] == sess.plan["backend"]
    assert set(sess.report()["stateful_backends"]) == {"ref", "pallas", "xla"}
    sess.compiled_stateful("pallas")    # explicit request resolves too
    per_step = repro.build(MODEL,
                           AcceleratorConfig(alu_mode="per_step")).quantize()
    assert per_step.plan["stateful_backend"] == "xla"
    assert per_step.report()["stateful_backends"] == ("xla",)
    # per-step cannot run the fused kernel, stateful or not
    with pytest.raises(backends.BackendUnsupported, match="alu_mode"):
        per_step.compiled_stateful("pallas")
    # a session PINNED to pallas carries state on pallas itself
    pinned = repro.build(MODEL, AcceleratorConfig(backend="pallas")).quantize()
    assert pinned.plan["stateful_backend"] == "pallas"
    pinned.compiled_stateful()          # resolves, no raise


@pytest.mark.parametrize("num_layers", [1, 2, 3])
def test_stream_server_carry_on_pallas_matches_concatenated(num_layers):
    """The serving hot path on the fused kernel: windowed streaming with
    the carry held by StreamServer, executed by the stateful pallas
    backend, is bit-identical to the one-shot concatenated run — per
    layer count."""
    model = QLSTMConfig(input_size=1, hidden_size=8, num_layers=num_layers,
                        seq_len=4)
    s = repro.build(model, seed=0).quantize()
    assert s.plan["stateful_backend"] == "pallas"
    k, t = 3, model.seq_len
    xs = _windows(k, seed=20 + num_layers)
    with StreamServer(s, batch=2, deadline_s=0.005,
                      backend="pallas") as srv:
        for w in range(k):
            srv.submit("s", xs[w])
        by = {r.seq: r.y for r in srv.drain()}
    full = np.asarray(s.infer(jnp.asarray(xs.reshape(1, k * t, 1)),
                              path="int", backend="ref"))
    np.testing.assert_array_equal(by[k - 1], full[0])


@pytest.mark.slow
def test_saturated_stateful_pipeline_does_not_deadlock(sess):
    """One stream, full-wave-only scheduling (deadline_s=None), tiny
    max_pending: a full wave can never assemble (one window per stream per
    wave), so saturation must flush partial waves instead of blocking
    submit forever."""
    x = _windows(6, seed=11)
    with StreamServer(sess, batch=4, deadline_s=None, max_pending=2) as srv:
        for w in x:                      # would deadlock without the
            srv.submit("lone", w)        # saturation flush
        results = srv.drain(timeout=60)
    assert len(results) == 6
    full = np.asarray(sess.infer(
        jnp.asarray(x.reshape(1, 6 * MODEL.seq_len, 1)), path="int"))
    last = [r for r in results if r.seq == 5][0]
    np.testing.assert_array_equal(last.y, full[0])


def test_unconsumed_serve_generator_leaks_no_threads(sess):
    """serve() allocates the server lazily: an abandoned, never-iterated
    generator must not leave scheduler threads behind."""
    import threading
    before = threading.active_count()
    for _ in range(3):
        sess.serve(iter(_windows(4)), batch=2)   # never iterated
    assert threading.active_count() == before


def test_serve_validates_at_call_site(sess):
    unquantised = repro.build(MODEL, seed=1)
    with pytest.raises(RuntimeError, match="quantize"):
        unquantised.serve(iter(_windows(2)), batch=2)
    with pytest.raises(ValueError, match="path"):
        sess.serve(iter(_windows(2)), batch=2, path="nope")


def test_window_shape_mismatch_rejected(sess):
    with StreamServer(sess, batch=2, stateful=False) as srv:
        srv.submit(None, _windows(1)[0])
        with pytest.raises(ValueError, match="shape"):
            srv.submit(None, np.zeros((5, 1), np.float32))


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

def test_metrics_summary_shape(sess):
    x = _windows(10, seed=8)
    with StreamServer(sess, batch=4, deadline_s=0.005, max_streams=4) as srv:
        t0 = time.perf_counter()
        for i, w in enumerate(x):
            srv.submit(f"s{i % 2}", w)
        srv.flush()
        m = srv.metrics_summary()
    assert m["samples"] == 10 and m["waves"] >= 3
    assert m["samples_per_s"] > 0
    assert 0 < m["latency_ms"]["p50"] <= m["latency_ms"]["p99"]
    assert m["latency_ms"]["p99"] / 1e3 <= time.perf_counter() - t0 + 1.0
    assert m["gops_per_watt"] > 0 and m["ops_per_inference"] > 0
    assert m["state"]["live_streams"] == 2
    assert m["stateful"] is True


def test_metrics_sink_is_bounded():
    """A long-lived server records one wave forever: the sink keeps only a
    rolling window of records for the percentile reductions, while the
    counts and samples/s stay lifetime-exact."""
    from repro.serving import MetricsSink, WaveRecord
    sink = MetricsSink(window=8)
    sink.note_submit(0.0)
    for i in range(100):
        sink.record_wave(WaveRecord(
            t_done=float(i + 1), compute_s=0.01,
            latency_s=0.001 * (i + 1), occupancy=3, batch=4,
            deadline_flush=(i % 10 == 0)))
    assert len(sink.waves) == 8                      # bounded retention
    m = sink.summary()
    assert m["waves"] == 100 and m["samples"] == 300  # lifetime counters
    assert m["deadline_flushes"] == 10 and m["padded_slots"] == 100
    assert m["samples_per_s"] == pytest.approx(3.0)   # lifetime wall rate
    # percentiles describe the window (latencies 93..100 ms), not history
    assert 92.0 < m["latency_ms"]["p50"] < 101.0


@pytest.mark.parametrize("stateful", [True, False],
                         ids=["stateful", "stateless"])
def test_stream_server_rejects_several_sessions(sess, stateful):
    """One server serves one session; replicas go behind a ClusterServer,
    and the error says so."""
    replica = repro.build(MODEL, params=sess.params, seed=0).quantize()
    for sessions in ([sess, replica], (sess,)):
        with pytest.raises(ValueError, match="ClusterServer"):
            StreamServer(sessions, batch=2, stateful=stateful)


def test_invalid_scheduler_bounds_rejected(sess):
    with pytest.raises(ValueError, match="max_pending"):
        StreamServer(sess, batch=2, max_pending=0)
    with pytest.raises(ValueError, match="queue_depth"):
        StreamServer(sess, batch=2, queue_depth=0)


# ---------------------------------------------------------------------------
# Device-resident state: SlotAllocator properties + DeviceStateStore
# ---------------------------------------------------------------------------

from hypothesis_compat import given, settings, st  # noqa: E402
from repro.serving import DeviceStateStore, SlotAllocator  # noqa: E402



class _LruOracle:
    """The LRU policy the allocator must follow, written plainly: an
    ordered map with hit/miss/eviction counters."""

    def __init__(self, capacity):
        self.capacity, self.live = capacity, OrderedDict()
        self.hits = self.misses = self.evictions = 0

    def get(self, sid):
        if sid not in self.live:
            self.misses += 1
            return False
        self.live.move_to_end(sid)
        self.hits += 1
        return True

    def put(self, sid):
        self.live[sid] = True
        self.live.move_to_end(sid)
        evicted = []
        while len(self.live) > self.capacity:
            evicted.append(self.live.popitem(last=False)[0])
            self.evictions += 1
        return evicted

    def pop(self, sid):
        return self.live.pop(sid, None) is not None

_ops_strategy = st.lists(
    st.tuples(st.sampled_from(["lookup", "assign", "release"]),
              st.integers(0, 7)),
    max_size=120)


def _drive(alloc, ops, on_assign=None):
    """Replay an op sequence, checking the structural invariants after
    every step: live slots unique and in range, occupancy bounded by
    capacity, high-water bounded by peak occupancy."""
    peak = 0
    for op, sid in ops:
        if op == "lookup":
            alloc.lookup(sid)
        elif op == "assign":
            slot, evicted = alloc.assign(sid)
            assert 0 <= slot < alloc.capacity
            if on_assign is not None:
                on_assign(sid, slot, evicted)
        else:
            alloc.release(sid)
        live = alloc.live()
        peak = max(peak, len(live))
        assert len(live) <= alloc.capacity
        slots = list(live.values())
        assert len(slots) == len(set(slots))           # unique live slots
        assert all(0 <= s < alloc.capacity for s in slots)
        assert alloc.high_water <= peak or alloc.high_water <= len(live)
    assert alloc.high_water <= peak if ops else alloc.high_water == 0


@given(st.integers(1, 5), _ops_strategy)
@settings(max_examples=120, deadline=None)
def test_slot_allocator_live_slots_unique_property(capacity, ops):
    """PROPERTY: under any lookup/assign/release sequence, live streams
    hold pairwise-distinct in-range slots, occupancy never exceeds
    capacity, and the high-water mark never exceeds peak occupancy (slots
    are not burned by churn)."""
    _drive(SlotAllocator(capacity), ops)


@given(st.integers(1, 5), _ops_strategy)
@settings(max_examples=120, deadline=None)
def test_slot_allocator_freed_slots_reused_before_growth(capacity, ops):
    """PROPERTY: a fresh assignment always reuses the most recently freed
    slot (LIFO) when one exists; the high-water mark only grows when the
    free list is empty."""
    alloc = SlotAllocator(capacity)
    shadow_free = []                 # mirrors the LIFO free list
    for op, sid in ops:
        if op == "lookup":
            alloc.lookup(sid)
        elif op == "assign":
            fresh = sid not in alloc
            hw = alloc.high_water
            slot, evicted = alloc.assign(sid)
            if fresh:
                if shadow_free:
                    assert slot == shadow_free.pop()   # LIFO reuse first
                    assert alloc.high_water == hw
                elif not evicted:
                    assert slot == hw and alloc.high_water == hw + 1
                else:
                    assert alloc.high_water == hw      # victim's slot
            else:
                assert alloc.high_water == hw
        else:
            if sid in alloc:
                shadow_free.append(alloc.release(sid))
            else:
                assert alloc.release(sid) is None


@given(st.integers(1, 5), _ops_strategy)
@settings(max_examples=120, deadline=None)
def test_slot_allocator_lru_matches_statestore_oracle(capacity, ops):
    """PROPERTY: the allocator IS a plain LRU policy with entries mapped
    to slot ids — identical op sequences produce identical live sets,
    identical eviction victims in identical order, and identical
    hit/miss/eviction counters."""
    alloc = SlotAllocator(capacity)
    store = _LruOracle(capacity)
    for op, sid in ops:
        if op == "lookup":
            assert (alloc.lookup(sid) is not None) == store.get(sid)
        elif op == "assign":
            _, evicted = alloc.assign(sid)
            assert evicted == store.put(sid)
        else:
            assert (alloc.release(sid) is not None) == store.pop(sid)
        assert set(alloc.live()) == set(store.live)
        assert (alloc.hits, alloc.misses, alloc.evictions) == \
            (store.hits, store.misses, store.evictions)


def test_slot_allocator_matches_statestore_oracle_seeded():
    """The LRU-oracle property replayed deterministically (the hypothesis
    variant skips on bare interpreters): 2000 seeded ops over a tight id
    space against every small capacity."""
    rng = np.random.default_rng(123)
    for capacity in (1, 2, 3, 5):
        alloc, store = SlotAllocator(capacity), _LruOracle(capacity)
        shadow_free = []
        for _ in range(2000):
            op = ("lookup", "assign", "release")[rng.integers(3)]
            sid = int(rng.integers(8))
            if op == "lookup":
                assert (alloc.lookup(sid) is not None) == store.get(sid)
            elif op == "assign":
                fresh = sid not in alloc
                hw = alloc.high_water
                slot, evicted = alloc.assign(sid)
                assert evicted == store.put(sid)
                if fresh and shadow_free:
                    assert slot == shadow_free.pop() and \
                        alloc.high_water == hw
            else:
                slot = alloc.release(sid)
                assert (slot is not None) == store.pop(sid)
                if slot is not None:
                    shadow_free.append(slot)
            assert set(alloc.live()) == set(store.live)
            live_slots = list(alloc.live().values())
            assert len(live_slots) == len(set(live_slots))
            assert (alloc.hits, alloc.misses, alloc.evictions) == \
                (store.hits, store.misses, store.evictions)


def test_slot_allocator_validates_capacity():
    with pytest.raises(ValueError, match="capacity"):
        SlotAllocator(0)


@pytest.mark.parametrize("backend", ["ref", "xla", "pallas"])
def test_every_stateful_server_serves_from_the_slot_table(sess, backend):
    """One carry store: whatever rung heads the ladder, a stateful server
    keeps its carries in its session's device table and ships only slot
    ids; a stateless server holds no store; and no option picks another
    place."""
    with StreamServer(sess, batch=2, backend=backend) as srv:
        assert srv.guard.ladder[0] == backend
        assert isinstance(srv.states, DeviceStateStore)
        srv.submit("s", _windows(1)[0])
        assert srv.drain(timeout=60)[0].backend == backend
        summary = srv.metrics_summary()
    assert summary["state"]["live_streams"] == 1
    assert summary["state_transfer"]["slot_id_bytes"] == 2 * 2 * 4
    assert len(dataclasses.fields(ServingConfig)) == 11
    with StreamServer(sess, batch=2, stateful=False) as srv:
        assert srv.states is None


def test_device_store_lru_eviction_order(sess):
    """The store's LRU: a lookup refreshes recency, an assignment past
    capacity evicts the least recently used stream, and the counters say
    so.  The table holds the live slots plus the ZERO and TRASH rows."""
    store = DeviceStateStore(sess, capacity=2)
    assert store.zero_slot == 2 and store.trash_slot == 3
    assert store.table.shape == state_table_shape(
        4, MODEL.num_layers, 2, MODEL.hidden_size)
    assert store.assign("a")[1] == [] and store.assign("b")[1] == []
    assert store.lookup("a") is not None    # refresh: b is now LRU
    assert store.assign("c")[1] == ["b"]    # evicts b
    assert "b" not in store and "a" in store and "c" in store
    stats = store.stats()
    assert stats["evictions"] == 1 and stats["live_streams"] == 2
    assert store.lookup("b") is None        # miss counted
    assert store.stats()["misses"] == 1


def test_device_vs_host_bit_exact_under_eviction_churn(sess):
    """The serving-level battery: more streams than slots (forced LRU
    evictions, slot reuse, mid-stream resets).  The slot table behind the
    XLA adapter and behind the fused pallas kernel give the same results,
    reset flags and state counters, and every window matches the chained
    ``compiled_stateful`` reference, restarted from the zero carry where
    the stream was reset or renumbered."""
    k, n_streams, cap = 3, 6, 4
    xs = {f"s{i}": _windows(k, seed=70 + i) for i in range(n_streams)}

    def run(backend):
        rows = {sid: [] for sid in xs}
        with StreamServer(sess, batch=4, deadline_s=0.005, max_streams=cap,
                          backend=backend) as srv:
            for w in range(k):
                for sid in xs:
                    srv.submit(sid, xs[sid][w])
                srv.flush(timeout=60)
            for r in srv.drain(timeout=60):
                rows[r.stream_id].append((r.seq, r.state_reset,
                                          np.asarray(r.y)))
            stats = srv.states.stats()
        return rows, {q: stats[q] for q in ("hits", "misses", "evictions",
                                            "live_streams")}

    xla_rows, xla_stats = run("xla")
    pallas_rows, pallas_stats = run("pallas")
    assert xla_stats == pallas_stats and xla_stats["evictions"] > 0
    fn = sess.compiled_stateful("ref")
    resets = 0
    for sid, wins in xs.items():
        assert len(xla_rows[sid]) == k
        state = sess.init_state(1)
        for w, ((seq, reset, y), (pseq, preset, py)) in enumerate(
                zip(xla_rows[sid], pallas_rows[sid])):
            assert (seq, reset) == (pseq, preset)  # same flags, same seqs
            np.testing.assert_array_equal(y, py)
            if reset or (w > 0 and seq == 0):
                state = sess.init_state(1)
                resets += 1
            want, state = fn(wins[w][None], state)
            np.testing.assert_array_equal(y, np.asarray(want)[0],
                                          err_msg=f"{sid} window {w}")
    assert resets > 0
