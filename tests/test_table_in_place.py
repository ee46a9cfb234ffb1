"""The device-resident state table is updated in place.

Every wave program donates the table, so the new table is the old one's
buffer and the array the server handed in is deleted once the wave is
dispatched.  These tests pin what that asks of the serving layer: the
``table_in_place`` counter says the mechanism engages, a wave that fails
after its dispatch costs every live stream its carry (flagged, counted,
never silently wrong), a fault before the dispatch retries on the live
table, and the planned surfaces never read a donated table."""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.qlstm import QLSTMConfig
from repro.serving import (ClusterServer, FaultInjector, ResiliencePolicy,
                           StreamServer)

FAST = ResiliencePolicy(max_retries=2, backoff_base_s=0.0)


def _model(cell="lstm", m=1):
    return QLSTMConfig(input_size=m, hidden_size=8, num_layers=2, seq_len=4,
                       cell=cell)


def _windows(n, seed=0, m=1):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 1.0, (n, 4, m)).astype(np.float32)


def _oracle(sess, xs):
    """The int path over windows ``xs`` concatenated, from the zero
    carry."""
    m = sess.model.input_size
    return np.asarray(sess.infer(jnp.asarray(xs.reshape(1, -1, m)),
                                 path="int"))[0]


def _ref_carry(sess, xs):
    """The ``ref`` engine's carry after windows ``xs``, from zero."""
    ref = sess.compiled_stateful("ref")
    state = sess.init_state(1)
    for w in xs:
        _, state = ref(w[None], state)
    return [tuple(np.asarray(a)[0] for a in layer) for layer in state]


def _transfer(srv):
    return srv.metrics_summary()["state_transfer"]


def _device_server(sess, **kw):
    kw.setdefault("batch", 4)
    kw.setdefault("deadline_s", 0.005)
    kw.setdefault("max_streams", 16)
    return StreamServer(sess, resilience=FAST, **kw)


class _Engine:
    """Wraps a session's wave program.  Armed, it calls the real program
    (which donates the table) and then fails, or blocks until released:
    a fault after the dispatch."""

    def __init__(self, fn):
        self.fn, self.calls = fn, 0
        self.fail = False
        self.block = None          # (dispatched, release) events
        self.lower = fn.lower      # the server compiles through it

    def __call__(self, *args):
        self.calls += 1
        out = self.fn(*args)
        if self.block is not None:
            dispatched, release = self.block
            self.block = None
            dispatched.set()
            release.wait(30)
        if self.fail:
            self.fail = False
            raise RuntimeError("device lost after dispatch")
        return out


def _wrapped_session(monkeypatch):
    """A fresh session whose wave programs are :class:`_Engine`s, by
    engine name."""
    sess = repro.build(_model(), seed=0).quantize()
    real = sess.compiled_stateful_slots
    engines = {}

    def wrapped(backend=None):
        if backend not in engines:
            engines[backend] = _Engine(real(backend))
        return engines[backend]

    monkeypatch.setattr(sess, "compiled_stateful_slots", wrapped)
    return sess, engines


@pytest.mark.parametrize("cell, backend", [
    ("lstm", "pallas"), ("lstm", "xla"), ("lstm", "ref"),
    ("gru", None), ("rglru", None)])
def test_every_wave_updates_the_table_in_place(cell, backend):
    """Each wave's new table is the old table's buffer, on every rung of
    the ladder and for every cell, and the streams stay bit-exact with
    their concatenated runs."""
    m = 1 if cell == "lstm" else 3
    sess = repro.build(_model(cell, m), seed=0).quantize()
    k = 3
    streams = {f"s{i}": _windows(k, seed=10 + i, m=m) for i in range(5)}
    with _device_server(sess, backend=backend) as srv:
        for w in range(k):
            for sid, xs in streams.items():
                srv.submit(sid, xs[w])
            srv.flush(timeout=60)
        results = srv.drain(timeout=60)
        s = srv.metrics_summary()
    by = {(r.stream_id, r.seq): r for r in results}
    for sid, xs in streams.items():
        for w in range(k):
            r = by[(sid, w)]
            assert r.ok and not r.state_reset
            np.testing.assert_array_equal(r.y, _oracle(sess, xs[:w + 1]))
    t = s["state_transfer"]
    assert t["table_in_place"] == s["waves"] >= k
    assert t["table_copied"] == 0 and t["table_losses"] == 0


def test_failure_after_dispatch_loses_the_table(monkeypatch):
    """A wave whose program is dispatched and then fails has donated the
    table: no further attempt is made, a zero table takes its place, and every live stream — in the wave or not — is
    answered next from the zero carry, flagged ``state_reset``."""
    sess, engines = _wrapped_session(monkeypatch)
    xs = {f"s{i}": _windows(3, seed=20 + i) for i in range(5)}
    with _device_server(sess, backend="xla") as srv:
        for sid in xs:
            srv.submit(sid, xs[sid][0])
        first = srv.drain(timeout=60)
        engines["xla"].fail = True
        calls = engines["xla"].calls
        for sid in ("s0", "s1"):
            srv.submit(sid, xs[sid][1])
        failed = srv.drain(timeout=60)
        assert engines["xla"].calls == calls + 1       # no retry
        assert engines["ref"].calls == 0               # no degradation
        assert len(srv.states) == 0                    # all released
        assert not srv.states.table.is_deleted()
        for sid in xs:
            srv.submit(sid, xs[sid][2])
        after = srv.drain(timeout=60)
        s = srv.metrics_summary()
    assert all(r.ok and not r.state_reset for r in first)
    assert sorted(r.stream_id for r in failed) == ["s0", "s1"]
    assert all(r.error.startswith("compute_failed") for r in failed)
    for r in after:
        assert r.ok and r.state_reset, r
        np.testing.assert_array_equal(r.y,
                                      _oracle(sess, xs[r.stream_id][2:]))
    assert len(after) == len(xs)
    assert s["faults"]["retries"] == 0
    assert s["faults"]["wave_failures"] == 1
    assert s["faults"]["state_resets"] == len(xs)
    t = s["state_transfer"]
    assert t["table_losses"] == 1 and t["table_copied"] == 0
    assert t["table_in_place"] == s["waves"] - 1       # all but the lost


def test_program_that_hands_back_its_donated_table(monkeypatch):
    """A wave program that returns its own (donated, so deleted) input as
    the new table leaves no table: the wave's answers stand, the loss is
    counted, and the next windows start from the zero carry, flagged."""
    sess, engines = _wrapped_session(monkeypatch)
    xs = {f"s{i}": _windows(2, seed=60 + i) for i in range(3)}
    with _device_server(sess, backend="xla") as srv:
        fn = engines["xla"].fn
        engines["xla"].fn = lambda x, table, g, s: (fn(x, table, g, s)[0],
                                                    table)
        for sid in xs:
            srv.submit(sid, xs[sid][0])
        first = srv.drain(timeout=60)
        engines["xla"].fn = fn
        for sid in xs:
            srv.submit(sid, xs[sid][1])
        after = srv.drain(timeout=60)
        t = _transfer(srv)
    assert all(r.ok and not r.state_reset for r in first)
    for r in after:
        assert r.ok and r.state_reset
        np.testing.assert_array_equal(r.y,
                                      _oracle(sess, xs[r.stream_id][1:]))
    assert t["table_losses"] == 1 and t["table_in_place"] == 1


class _Unreadable:
    """A wave output whose read-back fails, as a device error does."""

    def __array__(self, *args, **kwargs):
        raise RuntimeError("device error at read-back")


def test_device_error_at_read_back_gives_the_table_back(monkeypatch):
    """A device error that surfaces only when the results are read back
    (after the guard saw the call dispatched) still gives a table back to
    the store: the compute thread reports the error, a zero table
    replaces the donated one, and the waves behind it and the planned
    reads go on."""
    sess, engines = _wrapped_session(monkeypatch)
    xs = {f"s{i}": _windows(2, seed=50 + i) for i in range(3)}
    dispatched, release = threading.Event(), threading.Event()
    with _device_server(sess, backend="xla", batch=1) as srv:
        for sid in xs:
            srv.submit(sid, xs[sid][0])
        srv.drain(timeout=60)
        engine, fn = engines["xla"], engines["xla"].fn
        engine.fn = lambda *a: (_Unreadable(), fn(*a)[1])
        engine.block = (dispatched, release)
        srv.submit("s0", xs["s0"][1])
        assert dispatched.wait(30)
        engine.fn = fn
        for sid in ("s1", "s2"):              # queued behind the failure
            srv.submit(sid, xs[sid][1])
        release.set()
        after, t_end = [], time.monotonic() + 60
        while len(after) < 2 and time.monotonic() < t_end:
            try:                 # re-raised until a clean wave clears it
                after += srv.poll(timeout=0.5)
            except RuntimeError as e:
                assert "read-back" in str(e)
        carry = srv.read_stream_state("s1")
        t = _transfer(srv)
    assert sorted(r.stream_id for r in after) == ["s1", "s2"]
    assert t["table_losses"] == 1
    for r in after:
        assert r.ok and r.state_reset
        np.testing.assert_array_equal(r.y,
                                      _oracle(sess, xs[r.stream_id][1:]))
    for got, want in zip(carry, _ref_carry(sess, xs["s1"][1:])):
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)


def test_fault_before_dispatch_retries_on_the_live_table():
    """An injected fault raises before the program is called, so the
    table is alive and the retry runs on it: nothing is lost."""
    sess = repro.build(_model(), seed=0).quantize()
    inj = FaultInjector(seed=3, wave_fault_rate=0.4)
    xs = {f"s{i}": _windows(4, seed=30 + i) for i in range(6)}
    with StreamServer(sess, batch=4, deadline_s=0.005, max_streams=16,
                      backend="xla", resilience=FAST, fault_injector=inj) as srv:
        for w in range(4):
            for sid in xs:
                srv.submit(sid, xs[sid][w])
            srv.flush(timeout=60)
        results = srv.drain(timeout=60)
        s = srv.metrics_summary()
    assert inj.stats()["wave_faults"] > 0 and s["faults"]["retries"] > 0
    assert s["faults"]["wave_failures"] == 0
    for r in results:
        assert r.ok and not r.state_reset
        np.testing.assert_array_equal(
            r.y, _oracle(sess, xs[r.stream_id][:r.seq + 1]))
    t = s["state_transfer"]
    assert t["table_losses"] == 0 and t["table_copied"] == 0
    assert t["table_in_place"] == s["waves"]


def test_planned_surfaces_wait_for_the_table_in_flight(monkeypatch):
    """``read_state``, ``seed_state`` and ``corrupt_slot`` issued from
    other threads while a wave holds the (donated) table wait for the
    wave's commit, then act on the new table."""
    sess, engines = _wrapped_session(monkeypatch)
    xs = {sid: _windows(2, seed=40 + i) for i, sid in enumerate("ab")}
    seeded = _ref_carry(sess, _windows(1, seed=49))
    dispatched, release = threading.Event(), threading.Event()
    done, errors = {}, []

    def call(name, fn, *args):
        try:
            done[name] = fn(*args)
        except BaseException as e:            # surfaced to the assert
            errors.append(e)

    with _device_server(sess, backend="xla") as srv:
        for sid in xs:
            srv.submit(sid, xs[sid][0])
        srv.drain(timeout=60)
        engines["xla"].block = (dispatched, release)
        for sid in xs:
            srv.submit(sid, xs[sid][1])
        assert dispatched.wait(30)
        store = srv.states
        assert store.table.is_deleted()               # donated, in flight
        threads = [threading.Thread(target=call, args=a) for a in (
            ("read", store.read_state, "a"),
            ("seed", store.seed_state, "z", seeded),
            ("corrupt", store.corrupt_slot, "b"))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(0.3)
        assert all(th.is_alive() for th in threads) and not done
        release.set()
        for th in threads:
            th.join(30)
        srv.drain(timeout=60)
        got_z = srv.read_stream_state("z")
        got_b = srv.read_stream_state("b")
        s = srv.metrics_summary()
    assert not errors, errors
    assert done["corrupt"] is True and done["seed"] == []
    for got, want in ((done["read"], _ref_carry(sess, xs["a"])),
                      (got_z, seeded)):
        for g, w in zip(got, want):
            for ga, wa in zip(g, w):
                np.testing.assert_array_equal(ga, wa)
    for g, w in zip(got_b, _ref_carry(sess, xs["b"])):
        for ga, wa in zip(g, w):
            np.testing.assert_array_equal(ga, np.bitwise_xor(wa, 1))
    t = s["state_transfer"]
    assert t["table_in_place"] == 2 and t["table_copied"] == 0


def test_cluster_warm_handoff_keeps_tables_in_place():
    """A drained replica's carries, read back and seeded into their new
    homes' tables, continue bit-exact; every replica's table stays on its
    own device, and every wave updates it in place."""
    sess = repro.build(_model(), seed=0).quantize()
    k = 2
    streams = {f"w{i}": _windows(k + 1, seed=60 + i) for i in range(8)}
    with ClusterServer(sess.replicate(3), batch=4,
                       deadline_s=0.002) as cluster:
        for w in range(k):
            for sid, xs in streams.items():
                cluster.submit(sid, xs[w])
        cluster.drain()
        victim = cluster.replica_for("w0")
        moved = cluster.remove_replica(victim)
        assert moved
        for sid, xs in streams.items():
            cluster.submit(sid, xs[k])
        results = cluster.drain()
        for srv in cluster._servers.values():
            assert srv.states.table.devices() == {srv._session.device}
        per = cluster.metrics_summary()["replicas"].values()
    for r in results:
        assert r.ok and not r.state_reset, r
        np.testing.assert_array_equal(r.y, _oracle(sess, streams[r.stream_id]))
    for p in per:
        t = p["state_transfer"]
        assert t["table_in_place"] == p["waves"]
        assert t["table_copied"] == 0 and t["table_losses"] == 0
