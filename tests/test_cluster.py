"""The multi-replica serving cluster (`repro.serving.cluster` / `routing`).

The load-bearing guarantee is the ROUTING INVARIANT: every named stream's
windows all execute on ONE replica (consistent hash), so its (h, c) carry
stays replica-local — and windowed-through-the-cluster is therefore
bit-identical on the int path to the concatenated one-shot run on a single
session.  Plus: HashRing determinism and minimal-disruption properties,
MetricsSink.merge units, drain/rebalance with ``state_reset`` provenance,
failover off a failed replica, and the device-pinning of
``Accelerator.replicate``."""

import json
import math
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.qlstm import QLSTMConfig
from repro.serving import (ClusterConfig, ClusterServer, DeviceStateStore,
                           HashRing, MetricsSink, OverloadPolicy,
                           ServerOverloaded)
from repro.serving.metrics import WaveRecord

MODEL = QLSTMConfig(input_size=1, hidden_size=8, num_layers=2, seq_len=4)


@pytest.fixture(scope="module")
def sess():
    return repro.build(MODEL, seed=0).quantize()


def _windows(n, seed=0, t=4, m=1):
    rng = np.random.default_rng(seed)
    return rng.uniform(0.0, 1.0, (n, t, m)).astype(np.float32)


# ---------------------------------------------------------------------------
# HashRing — determinism and minimal disruption
# ---------------------------------------------------------------------------

def test_ring_deterministic_across_instances():
    """Two fresh rings with the same nodes and seed agree on every key —
    the property that lets an external balancer compute the same routing
    (blake2b, never Python's per-process-randomised hash())."""
    keys = [f"stream-{i}" for i in range(500)]
    a = HashRing(["r0", "r1", "r2"], seed=7)
    b = HashRing(["r2", "r0", "r1"], seed=7)   # insertion order irrelevant
    assert a.assignments(keys) == b.assignments(keys)
    # ...and a different seed is a different (but still valid) mapping.
    c = HashRing(["r0", "r1", "r2"], seed=8)
    assert c.assignments(keys) != a.assignments(keys)


def test_ring_balance():
    """With vnodes smoothing, no replica owns a wildly disproportionate
    key share (loose bound — consistent hashing is approximate)."""
    keys = [f"s{i}" for i in range(3000)]
    ring = HashRing(["r0", "r1", "r2", "r3"], vnodes=64, seed=0)
    counts = {n: 0 for n in ring.nodes}
    for n in ring.assignments(keys).values():
        counts[n] += 1
    for n, c in counts.items():
        assert 0.4 * 3000 / 4 < c < 2.2 * 3000 / 4, counts


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n_keys", [64, 500])
def test_ring_leave_moves_exactly_the_leavers_keys(seed, n_keys):
    """Removing a node re-routes EXACTLY that node's keys (the consistent-
    hashing contract, with no slack: surviving nodes' points don't move)."""
    keys = [f"k{i}" for i in range(n_keys)]
    ring = HashRing(["r0", "r1", "r2", "r3"], seed=seed)
    before = ring.assignments(keys)
    ring.remove("r2")
    after = ring.assignments(keys)
    for k in keys:
        if before[k] == "r2":
            assert after[k] != "r2"
        else:
            assert after[k] == before[k]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ring_join_moves_at_most_its_fair_share(seed):
    """Adding a node steals only the keys it now owns — bounded by the
    fair share ceil(K/N) plus slack for hashing variance; every stolen key
    moves TO the new node (never between old nodes)."""
    keys = [f"k{i}" for i in range(600)]
    ring = HashRing(["r0", "r1", "r2"], seed=seed)
    before = ring.assignments(keys)
    ring.add("r3")
    after = ring.assignments(keys)
    moved = [k for k in keys if after[k] != before[k]]
    assert all(after[k] == "r3" for k in moved)
    fair = math.ceil(len(keys) / 4)
    assert len(moved) <= 2 * fair, (len(moved), fair)


def test_ring_edge_cases():
    with pytest.raises(RuntimeError):
        HashRing().route("k")                   # empty ring
    with pytest.raises(ValueError):
        HashRing(vnodes=0)
    ring = HashRing(["a"])
    with pytest.raises(ValueError):
        ring.add("a")                           # duplicate
    with pytest.raises(KeyError):
        ring.remove("b")                        # absent
    assert ring.route("anything") == "a"        # single node owns all
    assert "a" in ring and len(ring) == 1


# ---------------------------------------------------------------------------
# MetricsSink.merge — the cluster aggregation primitive
# ---------------------------------------------------------------------------

def _rec(t, lat=0.010, occ=4, batch=4):
    return WaveRecord(t_done=t, compute_s=lat / 2, latency_s=lat,
                      occupancy=occ, batch=batch, deadline_flush=False)


def test_merge_empty_and_partial():
    """merge([]) is the empty sink; sinks that never saw a wave contribute
    nothing (no None-vs-float crashes on the wall interval)."""
    assert MetricsSink.merge([]).summary()["waves"] == 0
    empty, live = MetricsSink(), MetricsSink()
    live.note_submit(100.0)
    live.record_wave(_rec(100.5))
    s = MetricsSink.merge([empty, live]).summary()
    assert s["waves"] == 1 and s["samples"] == 4
    assert s["wall_s"] == pytest.approx(0.5)


def test_merge_sums_counters_and_spans_walls():
    """Lifetime counts sum; the wall spans earliest-submit to latest-done
    across replicas, so merged samples/s is the aggregate rate; named
    event counters sum too."""
    a, b = MetricsSink(), MetricsSink()
    a.note_submit(10.0)
    b.note_submit(10.2)
    for t in (10.5, 11.0):
        a.record_wave(_rec(t, occ=3))
    b.record_wave(_rec(12.0, occ=5))
    a.count("sheds", 2)
    b.count("sheds")
    b.count("state_resets", 4)
    m = MetricsSink.merge([a, b])
    s = m.summary()
    assert s["waves"] == 3 and s["samples"] == 11
    assert s["wall_s"] == pytest.approx(2.0)        # 10.0 -> 12.0
    assert s["samples_per_s"] == pytest.approx(11 / 2.0)
    assert m.counters() == {"sheds": 3, "state_resets": 4}


def test_merge_percentiles_union_recent_window():
    """The merged rolling window is the union of the inputs' retained
    records ordered by completion — its percentiles equal those computed
    over the pooled latencies directly."""
    a, b = MetricsSink(), MetricsSink()
    lats = []
    for i in range(20):
        (a if i % 2 else b).record_wave(_rec(100.0 + i, lat=0.001 * (i + 1)))
        lats.append(0.001 * (i + 1))
    s = MetricsSink.merge([a, b]).summary()
    want = np.percentile(np.asarray(lats), [50, 95, 99]) * 1e3
    assert s["latency_ms"]["p50"] == pytest.approx(want[0])
    assert s["latency_ms"]["p99"] == pytest.approx(want[2])


def test_merge_truncates_to_window():
    """A small merge window keeps only the most RECENT records across the
    union (deque semantics), like a single server's sink would."""
    a = MetricsSink()
    for i in range(10):
        a.record_wave(_rec(100.0 + i))
    m = MetricsSink.merge([a], window=4)
    assert [r.t_done for r in m.waves] == [106.0, 107.0, 108.0, 109.0]
    assert m.summary()["waves"] == 10                # lifetime count intact


# ---------------------------------------------------------------------------
# Accelerator.replicate — per-device pinned replicas
# ---------------------------------------------------------------------------

def test_replicate_pins_bit_identical_codes(sess):
    """Replicas carry the SAME integer codes (pinned, not re-quantised),
    committed to a device, and produce bit-identical int-path output."""
    reps = sess.replicate(2)
    x = _windows(3, seed=5)
    want = np.asarray(sess.infer(jnp.asarray(x), path="int"))
    for rep in reps:
        assert rep.device in jax.devices()
        leaves = jax.tree_util.tree_leaves(rep.qparams)
        assert all(l.devices() == {rep.device} for l in leaves)
        np.testing.assert_array_equal(
            np.asarray(rep.infer(jnp.asarray(x), path="int")), want)


def test_replicate_requires_quantized():
    with pytest.raises(RuntimeError, match="quantised"):
        repro.build(MODEL, seed=0).replicate(2)


def test_serving_devices_contract():
    from repro.launch.mesh import serving_devices
    devs = serving_devices(3)                       # oversubscribe by default
    assert len(devs) == 3
    with pytest.raises(ValueError):
        serving_devices(0)
    if len(jax.devices()) < 3:
        with pytest.raises(RuntimeError, match="XLA_FLAGS"):
            serving_devices(3, oversubscribe=False)
    with pytest.raises(ValueError):
        serving_devices(2, devices=jax.devices()[:1])


def test_serving_devices_never_shares_a_tpu(monkeypatch):
    """On a TPU, replicas never share a chip, even with the CPU-test
    posture's ``oversubscribe=True``; enough chips are dealt out one each."""
    import types

    from repro.launch import mesh
    chips = [types.SimpleNamespace(platform="tpu", id=i) for i in range(2)]
    monkeypatch.setattr(mesh.jax, "devices", lambda: chips)
    with pytest.raises(RuntimeError, match="never share a chip"):
        mesh.serving_devices(4)
    assert mesh.serving_devices(2) == chips


# ---------------------------------------------------------------------------
# ClusterServer — the routing invariant, end to end
# ---------------------------------------------------------------------------

def _cluster(sess, n=3, **kw):
    kw.setdefault("batch", 4)
    kw.setdefault("deadline_s", 0.002)
    return ClusterServer(sess.replicate(n), **kw)


@pytest.mark.slow
def test_cluster_routing_invariant_and_bit_exact_carry(sess):
    """THE acceptance property: every stream's windows run on exactly one
    replica (``routed_replica`` constant per stream, equal to the ring's
    assignment), and each stream's windowed-on-the-cluster predictions are
    bit-exact against the single-session concatenated oracle — the carry
    stayed replica-local the whole way."""
    k, t = 3, MODEL.seq_len
    streams = {f"c{i}": _windows(k, seed=30 + i) for i in range(9)}
    with _cluster(sess, 3) as cluster:
        expect = {sid: cluster.replica_for(sid) for sid in streams}
        for w in range(k):
            for sid, xs in streams.items():
                cluster.submit(sid, xs[w])
        results = cluster.drain()
    by = {}
    for r in results:
        assert r.ok
        assert r.routed_replica == expect[r.stream_id]
        by.setdefault(r.stream_id, {})[r.seq] = r.y
    assert len({expect[s] for s in streams}) > 1    # actually spread out
    for sid, xs in streams.items():
        assert sorted(by[sid]) == list(range(k))
        for w in range(k):
            oracle = np.asarray(sess.infer(
                jnp.asarray(xs[:w + 1].reshape(1, (w + 1) * t, 1)),
                path="int"))
            np.testing.assert_array_equal(by[sid][w], oracle[0])


def test_cluster_rejects_non_replicas(sess):
    other = repro.build(MODEL, seed=42).quantize()
    with pytest.raises(ValueError, match="weights"):
        ClusterServer([sess, other], batch=2)
    with pytest.raises(ValueError, match="replica"):
        ClusterServer([], batch=2)
    with pytest.raises(ValueError, match="names"):
        ClusterServer(sess.replicate(2), names=["a"], batch=2)


def test_cluster_metrics_aggregate(sess):
    """metrics_summary: merged aggregate block + per-replica breakdown +
    summed fault/state counters + the ring block — the schema report.py's
    serving table renders."""
    with _cluster(sess, 2) as cluster:
        for i, w in enumerate(_windows(12, seed=6)):
            cluster.submit(f"m{i % 4}", w)
        cluster.drain()
        s = cluster.metrics_summary()
    assert s["samples"] == 12 and s["waves"] >= 3
    assert set(s["replicas"]) == {"r0", "r1"}
    assert s["samples_per_s"] > 0 and s["samples_per_s_sum"] > 0
    assert {"p50", "p95", "p99"} <= set(s["latency_ms"])
    assert s["faults"]["sheds"] == 0 and s["faults"]["backend"]
    assert s["state"]["live_streams"] == 4          # summed across replicas
    assert s["ring"]["vnodes"] == 64
    assert s["ring"]["streams_routed"] == 4
    assert s["health"]["status"] == "ok"
    assert s["gops_per_watt"] > 0


def test_cluster_end_stream(sess):
    """end_stream forgets the stream cluster-wide: numbering restarts and
    the carry resets (fresh-stream output), on whatever replica owns it."""
    x = _windows(2, seed=8)
    fresh = np.asarray(sess.infer(jnp.asarray(x[1:2]), path="int"))
    with _cluster(sess, 2, batch=2) as cluster:
        assert cluster.submit("e", x[0]) == 0
        cluster.flush()
        cluster.end_stream("e")
        assert cluster.submit("e", x[1]) == 0
        results = cluster.drain()
    last = [r for r in results if r.seq == 0][-1]
    np.testing.assert_array_equal(last.y, fresh[0])


def test_cluster_overload_propagates_replica_name(sess):
    """A saturated replica's admission rejection surfaces to the client as
    ServerOverloaded naming the replica — never silently re-routed, which
    would break state locality."""
    policy = OverloadPolicy(admission="reject")
    with _cluster(sess, 2, batch=2, deadline_s=None, max_pending=2,
                  queue_depth=1, overload=policy) as cluster:
        sid = "hot"
        with pytest.raises(ServerOverloaded, match="replica 'r[01]'"):
            for w in _windows(64, seed=9):
                cluster.submit(sid, w)
        cluster.drain()


def test_cluster_remove_replica_moves_only_its_streams(sess):
    """Drain/rebalance on the replicas' device tables: the ring shrink
    moves ONLY the removed replica's streams (~K/N of K); each lands on
    another replica with its numbering restarted.  Unmoved streams keep
    replica, numbering, and carry: their next prediction continues the
    concatenated sequence bit-exactly.  (What a moved stream's carry does
    — the warm handoff — is pinned by the next test.)"""
    k = 2
    streams = {f"d{i}": _windows(k + 1, seed=40 + i) for i in range(24)}
    with _cluster(sess, 3) as cluster:
        for w in range(k):
            for sid, xs in streams.items():
                cluster.submit(sid, xs[w])
        cluster.drain()
        before = {sid: cluster.replica_for(sid) for sid in streams}
        victim = before["d0"]
        moved = cluster.remove_replica(victim)
        assert sorted(moved) == sorted(
            s for s, r in before.items() if r == victim)
        assert 0 < len(moved) < len(streams) * 2 / 3   # ~K/N, never all
        assert victim not in cluster.replicas
        for sid, xs in streams.items():
            cluster.submit(sid, xs[k])
        results = cluster.drain()
        by = {r.stream_id: r for r in results}
        t = MODEL.seq_len
        for sid, xs in streams.items():
            r = by[sid]
            assert r.ok, sid
            if sid in moved:
                assert r.seq == 0 and r.routed_replica != victim
                assert r.routed_replica == cluster.replica_for(sid)
            else:
                assert r.seq == k and not r.state_reset
                assert r.routed_replica == before[sid]
                oracle = np.asarray(sess.infer(
                    jnp.asarray(xs.reshape(1, (k + 1) * t, 1)), path="int"))
                np.testing.assert_array_equal(r.y, oracle[0])
        with pytest.raises(KeyError):
            cluster.remove_replica(victim)          # already gone


def test_cluster_remove_replica_warm_handoff_device_residency(sess):
    """A planned drain is a WARM handoff between the replicas' device
    tables.  The ring shrink moves
    exactly the victim's streams, but each moved carry is read back from
    the dying replica's slot table and seeded into the stream's new ring
    home — the destination's read-back rows must reproduce the ref
    oracle's threaded state — so the stream's next window continues the
    recurrence bit-exactly against the concatenated oracle: per-replica
    seq restarts at 0 with NO ``state_reset`` flag.  Unmoved streams
    keep replica, numbering, and carry."""
    k, t = 2, MODEL.seq_len
    streams = {f"w{i}": _windows(k + 1, seed=60 + i) for i in range(8)}
    ref = sess.compiled_stateful("ref")

    def carry_after(xs, n):
        state = sess.init_state(1)
        for w in xs[:n]:
            _, state = ref(w[None], state)
        return state

    with _cluster(sess, 3) as cluster:
        assert all(isinstance(s.states, DeviceStateStore)
                   for s in cluster._servers.values())
        for w in range(k):
            for sid, xs in streams.items():
                cluster.submit(sid, xs[w])
        cluster.drain()
        before = {sid: cluster.replica_for(sid) for sid in streams}
        victim = before["w0"]
        moved = cluster.remove_replica(victim)
        assert sorted(moved) == sorted(
            s for s, r in before.items() if r == victim)
        assert victim not in cluster.replicas
        for sid in moved:
            # The handoff seeded the carry at the stream's new ring home,
            # and what reads back row-for-row IS the oracle's state.
            dest = cluster.replica_for(sid)
            assert dest != victim
            got = cluster._servers[dest].read_stream_state(sid)
            assert got is not None
            oracle_state = carry_after(streams[sid], k)
            for li, (h, c) in enumerate(got):
                oh, oc = oracle_state[li]
                np.testing.assert_array_equal(h, np.asarray(oh)[0])
                np.testing.assert_array_equal(c, np.asarray(oc)[0])
        for sid, xs in streams.items():
            cluster.submit(sid, xs[k])
        results = cluster.drain()
        by = {r.stream_id: r for r in results}
        for sid, xs in streams.items():
            r = by[sid]
            assert r.ok and not r.state_reset, sid
            if sid in moved:
                assert r.seq == 0 and r.routed_replica != victim
            else:
                assert r.seq == k and r.routed_replica == before[sid]
            oracle = np.asarray(sess.infer(
                jnp.asarray(xs.reshape(1, (k + 1) * t, 1)), path="int"))
            np.testing.assert_array_equal(r.y, oracle[0])


def test_cluster_remove_replica_abandon_skips_handoff(sess):
    """``abandon=True`` on a drain: the replica died, so there is nothing
    to read back — moved streams restart COLD at their new home with the
    flagged reset."""
    k = 1
    streams = {f"a{i}": _windows(k + 1, seed=80 + i) for i in range(8)}
    with _cluster(sess, 3) as cluster:
        for sid, xs in streams.items():
            cluster.submit(sid, xs[0])
        cluster.drain()
        before = {sid: cluster.replica_for(sid) for sid in streams}
        victim = before["a0"]
        moved = cluster.remove_replica(victim, abandon=True)
        for sid, xs in streams.items():
            cluster.submit(sid, xs[k])
        by = {r.stream_id: r for r in cluster.drain()}
        t = MODEL.seq_len
        for sid, xs in streams.items():
            r = by[sid]
            if sid in moved:
                assert r.seq == 0 and r.state_reset
                fresh = np.asarray(sess.infer(
                    jnp.asarray(xs[k].reshape(1, t, 1)), path="int"))
                np.testing.assert_array_equal(r.y, fresh[0])
            else:
                assert r.seq == k and not r.state_reset


def test_cluster_cannot_remove_last_replica(sess):
    with _cluster(sess, 1) as cluster:
        with pytest.raises(RuntimeError, match="last"):
            cluster.remove_replica("r0")
        assert cluster.replicas == ["r0"]           # ring intact after undo


def test_cluster_add_replica_rebalances_lazily(sess):
    """Growing the ring steals only the new node's fair share; stolen
    streams move on their NEXT submit with flagged resets, the rest are
    untouched."""
    streams = {f"g{i}": _windows(2, seed=60 + i) for i in range(8)}
    with _cluster(sess, 2) as cluster:
        for sid, xs in streams.items():
            cluster.submit(sid, xs[0])
        cluster.drain()
        before = {sid: cluster.replica_for(sid) for sid in streams}
        name = cluster.add_replica(sess.replicate(1)[0])
        assert name == "r2" and name in cluster.replicas
        after = {sid: cluster.replica_for(sid) for sid in streams}
        stolen = [s for s in streams if after[s] != before[s]]
        assert all(after[s] == name for s in stolen)
        for sid, xs in streams.items():
            cluster.submit(sid, xs[1])
        results = cluster.drain()
        for r in results:
            if r.stream_id in stolen:
                assert r.seq == 0 and r.state_reset
                assert r.routed_replica == name
            else:
                assert r.seq == 1 and not r.state_reset
        with pytest.raises(ValueError, match="weights"):
            cluster.add_replica(repro.build(MODEL, seed=42).quantize())


def test_cluster_failover_reroutes_on_failed_replica(sess, monkeypatch):
    """When a replica's health says ``failed`` at submit time, failover
    takes it off the ring and re-routes (flagged reset) instead of raising
    the replica's error; the dead replica shows up in health()."""
    streams = {f"f{i}": _windows(2, seed=70 + i) for i in range(6)}
    with _cluster(sess, 2) as cluster:
        for sid, xs in streams.items():
            cluster.submit(sid, xs[0])
        cluster.drain()
        owners = {sid: cluster.replica_for(sid) for sid in streams}
        victim = owners[next(iter(streams))]
        srv = cluster._servers[victim]
        monkeypatch.setattr(
            srv, "submit",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("dead")))
        monkeypatch.setattr(
            srv, "health", lambda: {"status": "failed"})
        hit = [s for s, r in owners.items() if r == victim]
        seq = cluster.submit(hit[0], streams[hit[0]][1])
        assert seq == 0                             # restarted at new home
        assert victim not in cluster.replicas
        results = cluster.drain()
        moved = [r for r in results if r.stream_id == hit[0]]
        assert moved and moved[0].state_reset
        assert moved[0].routed_replica != victim
        h = cluster.health()
        assert h["status"] == "degraded"
        assert victim in h["unhealthy"]
    # restore path: back on the ring, streams may hash home again
    # (exercised separately to keep the monkeypatched server out of play)


def test_cluster_restore_replica(sess):
    """mark_unhealthy -> restore_replica round-trip: streams move away
    with flagged resets and may move back the same way; no stale carry
    survives on the sidelined replica."""
    with _cluster(sess, 2) as cluster:
        xs = _windows(3, seed=80)
        sid = "rt"
        home = cluster.replica_for(sid)
        other = next(n for n in cluster.replicas if n != home)
        cluster.submit(sid, xs[0])
        cluster.drain()
        cluster.mark_unhealthy(home, reason="drill")
        assert cluster.replica_for(sid) == other
        r1 = None
        cluster.submit(sid, xs[1])
        r1 = cluster.drain()[0]
        assert r1.routed_replica == other and r1.seq == 0 and r1.state_reset
        with pytest.raises(RuntimeError, match="last"):
            cluster.mark_unhealthy(other)
        cluster.restore_replica(home)
        assert cluster.replica_for(sid) == home
        cluster.submit(sid, xs[2])
        r2 = cluster.drain()[0]
        # Back home: fresh numbering AND flagged reset — the sidelined
        # replica's old carry was ended at mark_unhealthy time, so the
        # prediction equals a fresh stream's, not a stale continuation.
        assert r2.routed_replica == home and r2.seq == 0 and r2.state_reset
        fresh = np.asarray(sess.infer(
            jnp.asarray(xs[2].reshape(1, MODEL.seq_len, 1)), path="int"))
        np.testing.assert_array_equal(r2.y, fresh[0])


def test_cluster_poll_timeout_and_close(sess):
    """poll(timeout) waits for the first batch; close drains cleanly and
    further submits are refused."""
    with _cluster(sess, 2) as cluster:
        t0 = time.perf_counter()
        assert cluster.poll(timeout=0.05) == []
        assert time.perf_counter() - t0 >= 0.04
        cluster.submit("p", _windows(1, seed=90)[0])
        rows = cluster.poll(timeout=5.0)
        assert rows and rows[0].ok
    assert cluster.close() == []                    # idempotent
    with pytest.raises(RuntimeError, match="closed"):
        cluster.submit("p", _windows(1, seed=90)[0])


def test_cluster_bad_window_raises_to_caller_only(sess):
    """A malformed window is the CLIENT's error (ValueError at submit) —
    it must not trip failover or unhealth the replica."""
    with _cluster(sess, 2) as cluster:
        with pytest.raises(ValueError, match="window"):
            cluster.submit("b", np.zeros((4, 3), np.float32))
        assert cluster.health()["status"] == "ok"
        assert len(cluster.replicas) == 2


def test_cluster_config_validation():
    with pytest.raises(ValueError, match="vnodes"):
        ClusterConfig(vnodes=0)


def test_build_cluster_front_door(sess):
    """repro.build_cluster: one call from a quantised session to a serving
    cluster (the api.py wrapper over replicate + ClusterServer)."""
    cluster = repro.build_cluster(sess, 2, batch=2, deadline_s=0.002,
                                  vnodes=16)
    try:
        assert len(cluster.replicas) == 2
        assert cluster.config.vnodes == 16
        assert cluster.config.serving.batch == 2
        cluster.submit("q", _windows(1, seed=95)[0])
        rows = cluster.drain()
        assert rows[0].ok and rows[0].routed_replica in ("r0", "r1")
    finally:
        cluster.close()


#: A replica on the second of two host devices: its server hands each
#: wave's host arrays to the wave program, which must run on the replica's
#: device with the replica's table staying there.
PINNED_REPLICA = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    import repro
    from repro.core.qlstm import QLSTMConfig
    from repro.serving import StreamServer

    model = QLSTMConfig(input_size=1, hidden_size=8, num_layers=2,
                        seq_len=4)
    sess = repro.build(model, seed=0).quantize()
    rep = sess.replicate(2)[1]
    placed = []

    def recording(fn):
        def wave(x, table, gather, scatter):
            y, new_table = fn(x, table, gather, scatter)
            placed.append([type(x) is np.ndarray, type(gather) is np.ndarray,
                           type(scatter) is np.ndarray,
                           y.devices() == {rep.device},
                           new_table.devices() == {rep.device}])
            return y, new_table
        return wave

    x = np.random.default_rng(3).uniform(
        0.0, 1.0, (6, model.seq_len, 1)).astype(np.float32)
    srv = StreamServer(rep, batch=4, deadline_s=None, max_streams=16)
    srv._fns = [[(n, recording(f)) for n, f in ladder]
                for ladder in srv._fns]
    got = {}
    try:
        for _ in range(2):
            for i in range(6):
                srv.submit(i, x[i])
            for r in srv.drain(timeout=120):
                got[(r.stream_id, r.seq)] = r.y
        table_devices = srv.states.table.devices() == {rep.device}
    finally:
        srv.close()
    once = np.asarray(sess.infer(jnp.asarray(x), path="int"))
    twice = np.asarray(sess.infer(jnp.asarray(np.concatenate([x, x], 1)),
                                  path="int"))
    exact = all(np.array_equal(got[(i, 0)], once[i])
                and np.array_equal(got[(i, 1)], twice[i]) for i in range(6))
    print(json.dumps({"default": rep.device == jax.devices()[0],
                      "waves": placed, "table": table_devices,
                      "exact": exact, "answered": len(got)}))
""")


def test_pinned_replica_serves_host_arrays_on_its_device():
    """A replica pinned to a non-default device (two host devices, in a
    subprocess: the device count is fixed before jax starts) takes each
    wave's batch and slot vectors as host arrays; the wave program runs
    on the replica's device, its table stays there, and its results are
    bit-exact with the unpinned session's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", PINNED_REPLICA], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["default"] is False
    assert len(res["waves"]) == 4          # two waves of 4 + 2 a round
    assert all(all(w) for w in res["waves"]), res["waves"]
    assert res["table"] and res["exact"] and res["answered"] == 12
