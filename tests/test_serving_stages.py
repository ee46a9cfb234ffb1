"""Stage times and ``serve.*`` spans of the serving path: where each window
waits (pending list, wave queue, execute) and how long each execute stage
takes, kept in the metrics sink and marked on the profiler's trace."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro.core.qlstm import QLSTMConfig
from repro.serving import MetricsSink, ServingConfig, StreamServer
from repro.serving.metrics import STAGES

MODEL = QLSTMConfig(input_size=1, hidden_size=8, num_layers=2, seq_len=4)
BATCH = 4
#: Streams per round: two full waves and one partial (flushed) wave.
N_STREAMS = 10
#: The device table behind the ladder's head (the fused kernel) and
#: behind the XLA-level slot adapter, and a stateless server.
KINDS = {
    "device": dict(),
    "xla": dict(backend="xla"),
    "stateless": dict(stateful=False),
}
#: Stages each kind of server runs (no carries on a stateless server).
KIND_STAGES = {"device": STAGES, "xla": STAGES,
               "stateless": ("call", "ready", "emit")}


@pytest.fixture(scope="module")
def sess():
    return repro.build(MODEL, seed=0).quantize()


def _windows():
    return np.random.default_rng(1).uniform(
        0.0, 1.0, (N_STREAMS, MODEL.seq_len, 1)).astype(np.float32)


def _serve(sess, kind, rounds=2, wrap=None):
    """A server of ``kind`` after ``rounds`` rounds of one window per
    stream (``_windows``), each round drained; ``wrap(server)`` runs
    before the first submit.  Returns (server, results)."""
    x = _windows()
    srv = StreamServer(sess, ServingConfig(batch=BATCH, deadline_s=None,
                                           **KINDS[kind]))
    if wrap is not None:
        wrap(srv)
    results = []
    for _ in range(rounds):
        for i in range(N_STREAMS):
            srv.submit(i, x[i])
        results += srv.drain(timeout=120)
    return srv, results


@pytest.fixture(scope="module", params=sorted(KINDS))
def served(request, sess):
    srv, results = _serve(sess, request.param)
    try:
        yield request.param, srv, results, srv.metrics.waves
    finally:
        srv.close()


def test_partial_and_full_waves(served):
    kind, srv, results, waves = served
    assert len(results) == 2 * N_STREAMS and all(r.ok for r in results)
    occ = sorted(w.occupancy for w in waves)
    assert occ == [2, 2, BATCH, BATCH, BATCH, BATCH]


def test_window_times_are_ordered(served):
    """t_submit <= t_built <= t_start <= t_done for every window."""
    _, _, _, waves = served
    for w in waves:
        assert w.pending_s.dtype == np.float32
        assert len(w.pending_s) == w.occupancy
        assert (w.pending_s >= 0).all()
        assert w.t_built <= w.t_start <= w.t_done


def test_stage_walls(served):
    """Every stage the server runs is timed, none negative, and the stages
    before emit fit inside compute_s."""
    kind, _, _, waves = served
    for w in waves:
        assert set(w.stage_s) == set(KIND_STAGES[kind])
        assert all(v >= 0 for v in w.stage_s.values())
        before_emit = sum(v for k, v in w.stage_s.items() if k != "emit")
        assert before_emit <= w.compute_s
        assert set(w.stage_cpu_s) == set(KIND_STAGES[kind]) - {"ready"}
        assert all(v >= 0 for v in w.stage_cpu_s.values())


def test_summary_counts_every_window(served):
    kind, srv, results, waves = served
    st = srv.metrics_summary()["stages"]
    assert st["waves"] == len(waves)
    assert st["windows"] == len(results) == sum(len(w.pending_s)
                                                for w in waves)
    assert set(st["stage_ms"]) == set(STAGES)
    assert st["stage_ms"]["slots"] > 0 or kind == "stateless"
    for key in ("pending_wait_ms", "queue_wait_ms", "submit_to_done_ms"):
        assert all(v >= 0 for v in st[key].values())
    host = sum(w.stage_s.get(k, 0.0) for w in waves for k in STAGES
               if k != "ready")
    assert st["host_wall_s"] == pytest.approx(host)
    assert st["host_cpu_s"] == pytest.approx(
        sum(sum(w.stage_cpu_s.values()) for w in waves))


def test_merge_keeps_stages(served):
    _, srv, _, _ = served
    assert MetricsSink.merge([srv.metrics]).summary()["stages"] == \
        srv.metrics.summary()["stages"]


def test_result_wave_matches_record(served):
    """Each result names the wave that computed it: one wave's rows share
    an id, and each id's row count is that wave's occupancy."""
    _, _, results, waves = served
    by_wave = {}
    for r in results:
        by_wave.setdefault(r.wave, set()).add(r.stream_id)
    assert {w.wave: w.occupancy for w in waves} == \
        {k: len(v) for k, v in by_wave.items()}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_wave_program_takes_host_arrays(sess, kind):
    """The execute hands the wave's host arrays to the wave program as they
    are (jit uploads them as it takes them): the batch and slot vectors
    arrive as NumPy arrays, the carry table as a device array, the
    results are the accelerator's, and no stage of its own uploads."""
    calls = []

    def recording(fn):
        def wave(*args):
            calls.append(args)
            return fn(*args)
        return wave

    def wrap(srv):
        srv._fns = [[(name, recording(fn)) for name, fn in ladder]
                    for ladder in srv._fns]

    srv, results = _serve(sess, kind, wrap=wrap)
    srv.close()
    waves = srv.metrics.waves
    assert len(calls) == len(waves) == 6
    for args in calls:
        if kind == "stateless":
            (x,) = args
        else:
            x, table, gather, scatter = args
            assert isinstance(table, jax.Array)
            for v in (gather, scatter):
                assert type(v) is np.ndarray and v.dtype == np.int32
                assert v.shape == (BATCH,)
        assert type(x) is np.ndarray and x.dtype == np.float32
        assert x.shape == (BATCH, MODEL.seq_len, 1)
    # A stateful server's second window continues the stream's carry: the
    # stream's two windows in one call; a stateless server starts over.
    x = _windows()
    want = [np.asarray(sess.infer(jnp.asarray(x), path="int"))]
    want.append(want[0] if kind == "stateless" else np.asarray(sess.infer(
        jnp.asarray(np.concatenate([x, x], axis=1)), path="int")))
    assert len(results) == 2 * N_STREAMS
    for r in results:
        np.testing.assert_array_equal(r.y, want[r.seq][r.stream_id])
    assert all("h2d" not in w.stage_s for w in waves)


def test_reset_metrics_clears_stages(sess):
    srv, _ = _serve(sess, "device", rounds=1)
    try:
        assert srv.metrics_summary()["stages"]["waves"] == 3
        srv.reset_metrics()
        assert "stages" not in srv.metrics_summary()
        assert MetricsSink().summary().get("stages") is None
    finally:
        srv.close()


def test_record_without_stage_times_stays_out_of_stages():
    from repro.serving import WaveRecord
    sink = MetricsSink()
    sink.record_wave(WaveRecord(t_done=1.0, compute_s=0.01, latency_s=0.02,
                                occupancy=3, batch=4, deadline_flush=False))
    s = sink.summary()
    assert s["waves"] == 1 and s["stages"] == {"waves": 0, "windows": 0}
    assert sink.waves[0].stage_s == sink.waves[0].stage_cpu_s == {}


@pytest.mark.parametrize("kind", ["device", "stateless"])
def test_spans_on_the_profiler_trace(sess, kind, tmp_path):
    """Under a profiler trace every wave's execute and its stages are
    ``serve.*`` events tagged with the wave's id."""
    import jax
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        srv, results = _serve(sess, kind, rounds=1)
        srv.close()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    waves = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    stats = dict(e.stats)
                    waves.setdefault(e.name, set()).add(stats.get("wave"))
    ids = {r.wave for r in results}
    want = ["serve." + s for s in KIND_STAGES[kind]] + [
        "serve.execute", "serve.assemble", "serve.put"]
    for name in want:
        assert waves.get(name) == ids, name
    # The last wait ends with the server's close, not with a wave.
    assert waves["serve.wait"] - {None} == ids
