"""The cell-agnostic recurrent contract (`repro.cells`).

Every registered cell — lstm, gru, rglru — must pass the SAME battery
shape that locked in the LSTM: bit-exact ref<->xla int-path parity across
fixed-point widths x HardSigmoid* methods x 1-3 layers, and
windowed-vs-concatenated bit-exactness through ``StreamServer`` on the
device-resident slot table (which non-LSTM cells reach through the
XLA-level slot adapter), on the ladder's head and on ``ref``.  Plus the
registry/plan surfaces the serving and explore layers dispatch on."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro
from repro import backends, cells, explore
from repro.backends import BackendUnsupported
from repro.core import fixed_point as fxp
from repro.core.accelerator import (AcceleratorConfig, HS_METHODS, plan,
                                    resolve_model)
from repro.core.fixed_point import FXP_4_8, FXP_8_16
from repro.core.qlstm import QLSTMConfig, init_int_state

CELLS = ("lstm", "gru", "rglru")
NON_FUSED_CELLS = ("gru", "rglru")


def _model(cell, layers=2, hidden=8, **kw):
    return QLSTMConfig(input_size=3, hidden_size=hidden, num_layers=layers,
                       seq_len=4, out_features=2, cell=cell, **kw)


def _x(batch=2, t=4, m=3, seed=0):
    rng = np.random.default_rng(seed)
    return rng.uniform(-1.0, 1.0, (batch, t, m)).astype(np.float32)


# ---------------------------------------------------------------------------
# Registry surfaces
# ---------------------------------------------------------------------------

def test_registry_lists_the_zoo():
    assert cells.available() == ("gru", "lstm", "rglru")
    for name in CELLS:
        spec = cells.get(name)
        assert spec.name == name
        assert spec.state_arity == len(spec.state_names)


def test_registry_unknown_cell_names_known_ones():
    with pytest.raises(KeyError, match="rglru"):
        cells.get("rwkv6")


def test_state_shape_and_init_state_follow_the_spec():
    for name in CELLS:
        m = _model(name, layers=3, hidden=5)
        arity = cells.get(name).state_arity
        assert cells.state_shape(m) == (3, arity, 5)
        st = cells.init_state(m, batch=4)
        assert len(st) == 3
        for layer in st:
            assert len(layer) == arity
            for a in layer:
                assert a.shape == (4, 5) and a.dtype == jnp.int32
                assert not np.any(np.asarray(a))


def test_lstm_init_state_matches_legacy_init_int_state():
    """The generic reset carry is bit-for-bit the historical LSTM one."""
    m = _model("lstm")
    legacy = init_int_state(m, 2)
    generic = cells.init_state(m, 2)
    assert len(legacy) == len(generic)
    for (lh, lc), (gh, gc) in zip(legacy, generic):
        np.testing.assert_array_equal(np.asarray(lh), np.asarray(gh))
        np.testing.assert_array_equal(np.asarray(lc), np.asarray(gc))


def test_unknown_cell_fails_at_build():
    with pytest.raises(KeyError, match="registered"):
        repro.build(_model("lstm").__class__(cell="nope"))


# ---------------------------------------------------------------------------
# The parity battery: ref <-> xla bit-exact, every cell, fxp x hs x layers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("layers", [1, 2, 3])
@pytest.mark.parametrize("fp", [FXP_4_8, FXP_8_16],
                         ids=["a4b8", "a8b16"])
@pytest.mark.parametrize("cell", CELLS)
def test_ref_xla_parity(cell, fp, layers):
    """The general (xla) int datapath must match the independently written
    pure-jnp oracle bit-for-bit, for every HardSigmoid* method."""
    base = _model(cell, layers=layers)
    spec = cells.get(cell)
    params = spec.init_params(
        dataclasses.replace(base, fxp=fp), jax.random.key(layers))
    x_int = fxp.quantize(jnp.asarray(_x(seed=layers)), fp)
    for hs_method in HS_METHODS:
        accel = AcceleratorConfig(fxp=fp, hs_method=hs_method)
        m = resolve_model(base, accel, warn=False)
        qp = spec.quantize_params(params, m)
        y_ref = backends.get("ref").run(qp, x_int, m, accel)
        y_xla = backends.get("xla").run(qp, x_int, m, accel)
        np.testing.assert_array_equal(
            np.asarray(y_ref), np.asarray(y_xla),
            err_msg=f"{cell} ref!=xla at {fp} {hs_method} L{layers}")


@pytest.mark.parametrize("cell", CELLS)
def test_stateful_windowed_equals_concatenated_backends(cell):
    """k windows through run_stateful == one run over the k*T sequence,
    bit-exact, on both int engines."""
    m = _model(cell)
    sess = repro.build(m, seed=1).quantize()
    k, t = 3, m.seq_len
    x_int = fxp.quantize(jnp.asarray(_x(t=k * t, seed=7)), sess.model.fxp)
    for name in ("ref", "xla"):
        bk = backends.get(name)
        y_full = bk.run(sess.qparams, x_int, sess.model, sess.accel)
        state = cells.init_state(sess.model, x_int.shape[0])
        for w in range(k):
            y, state = bk.run_stateful(
                sess.qparams, x_int[:, w * t:(w + 1) * t],
                sess.model, sess.accel, state)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_full),
                                      err_msg=f"{cell}@{name}")


@pytest.mark.parametrize("cell", CELLS)
def test_per_step_alu_runs_on_xla(cell):
    """The per-step (baseline [15]) ALU has no oracle, but the general
    datapath must run it for every cell; pipelined vs per-step codes
    genuinely differ (the rounding contract is doing something)."""
    m = _model(cell)
    per = repro.build(m, AcceleratorConfig(alu_mode="per_step"),
                      seed=2).quantize()
    pipe = repro.build(m, AcceleratorConfig(), params=per.params).quantize()
    assert per.plan["backend"] == "xla"
    x = jnp.asarray(_x(seed=3))
    y_per = np.asarray(per.infer(x, path="int"))
    y_pipe = np.asarray(pipe.infer(x, path="int"))
    assert np.all(np.isfinite(y_per))
    assert y_per.shape == y_pipe.shape


@pytest.mark.parametrize("cell", CELLS)
def test_float_and_qat_paths_run(cell):
    sess = repro.build(_model(cell), seed=4)
    x = jnp.asarray(_x(seed=4))
    for path in ("float", "qat"):
        y = np.asarray(sess.infer(x, path=path))
        assert y.shape == (2, 2) and np.all(np.isfinite(y))


# ---------------------------------------------------------------------------
# Plan / backend selection
# ---------------------------------------------------------------------------

def test_plan_carries_cell_and_state_shape():
    for cell in CELLS:
        m = _model(cell)
        p = plan(m, AcceleratorConfig())
        assert p["cell"] == cell
        assert p["state_shape"] == cells.state_shape(m)


def test_auto_backend_per_cell():
    """LSTM keeps the fused kernel; cells without one resolve to xla."""
    p = plan(_model("lstm"), AcceleratorConfig())
    assert p["backend"] == "pallas" and p["stateful_backend"] == "pallas"
    for cell in NON_FUSED_CELLS:
        p = plan(_model(cell), AcceleratorConfig())
        assert p["backend"] == "xla"
        assert p["stateful_backend"] == "xla"


def test_pallas_refuses_cells_without_fused_kernel():
    for cell in NON_FUSED_CELLS:
        with pytest.raises(BackendUnsupported, match="no fused kernel"):
            backends.select(_model(cell), AcceleratorConfig(),
                            override="pallas")
        with pytest.raises(ValueError, match="no fused kernel"):
            repro.build(_model(cell), AcceleratorConfig(backend="pallas"))


def test_stateful_ladder_per_cell():
    """Non-fused cells degrade xla -> ref; the fused LSTM keeps its
    three-rung ladder."""
    assert repro.build(_model("lstm")).degradation_ladder() == \
        ("pallas", "xla", "ref")
    for cell in NON_FUSED_CELLS:
        assert repro.build(_model(cell)).degradation_ladder() == \
            ("xla", "ref")


def test_report_runs_per_cell():
    for cell in CELLS:
        r = repro.build(_model(cell), seed=5).quantize().report()
        assert r["ops_per_inference"] > 0
        assert r["weight_bytes"] > 0
        assert r["plan"]["cell"] == cell


# ---------------------------------------------------------------------------
# Serving: windowed-vs-concatenated through StreamServer, on two rungs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", [None, "ref"], ids=["device", "ref"])
@pytest.mark.parametrize("cell", CELLS)
def test_stream_server_carry_equals_concatenated(cell, backend):
    """The serving contract, per cell: feeding a stream window-by-window
    through StreamServer is bit-identical to one shot over the
    concatenated sequence — from the device-resident slot table, on the
    ladder's head (the fused kernel for the LSTM, the XLA-level slot
    adapter for GRU/rGLRU) and on the ``ref`` rung."""
    from repro.serving import DeviceStateStore, StreamServer
    m = _model(cell)
    sess = repro.build(m, seed=6).quantize()
    k, t = 3, m.seq_len
    streams = {f"s{i}": _x(t=k * t, seed=20 + i)[0] for i in range(3)}
    with StreamServer(sess, batch=2, deadline_s=0.005, max_streams=8,
                      backend=backend) as srv:
        assert isinstance(srv.states, DeviceStateStore)
        head = srv.guard.ladder[0]
        for w in range(k):
            for sid, xs in streams.items():
                srv.submit(sid, xs[w * t:(w + 1) * t])
        results = srv.drain()
    by = {}
    for r in results:
        assert r.error is None and r.backend == head
        by.setdefault(r.stream_id, {})[r.seq] = r.y
    for sid, xs in streams.items():
        full = np.asarray(sess.infer(jnp.asarray(xs[None]), path="int"))
        np.testing.assert_array_equal(by[sid][k - 1], full[0],
                                      err_msg=f"{cell}@{head}:{sid}")


@pytest.mark.parametrize("cell", NON_FUSED_CELLS)
def test_stream_state_read_seed_roundtrip(cell):
    """Warm stream handoff (read_stream_state -> seed_stream_state) is
    carry-shape-agnostic: a moved stream continues bit-exactly."""
    from repro.serving import StreamServer
    m = _model(cell)
    sess = repro.build(m, seed=8).quantize()
    t = m.seq_len
    xs = _x(t=2 * t, seed=31)[0]
    with StreamServer(sess, batch=1, deadline_s=0.005) as src:
        src.submit("mv", xs[:t])
        src.drain()
        st = src.read_stream_state("mv")
    assert st is not None
    assert len(st) == m.num_layers
    assert all(len(layer) == cells.get(cell).state_arity for layer in st)
    with StreamServer(sess, batch=1, deadline_s=0.005) as dst:
        dst.seed_stream_state("mv", st)
        dst.submit("mv", xs[t:])
        (r,) = dst.drain()
    full = np.asarray(sess.infer(jnp.asarray(xs[None]), path="int"))
    np.testing.assert_array_equal(r.y, full[0])


# ---------------------------------------------------------------------------
# Explore: the cell axis
# ---------------------------------------------------------------------------

def test_explore_cell_axis():
    # cell sits between the Table-2 axes and the serving axis
    assert explore.AXES[-2:] == ("cell", "replicas")
    space = explore.SearchSpace(cell=("lstm", "gru"))
    assert space.size == 2
    labels = [p.label for p in space.grid()]
    assert labels[0].endswith("_auto")          # lstm label unchanged
    assert labels[1].endswith("_gru")
    with pytest.raises(ValueError, match="cell choice"):
        explore.SearchSpace(cell=("mamba",))


def test_point_from_config_defaults_old_records_to_lstm():
    from repro.explore.space import point_from_config
    p = next(iter(explore.SearchSpace().grid()))
    d = p.asdict()
    del d["cell"]                               # a pre-cell-axis record
    assert point_from_config(d).cell == "lstm"
    assert point_from_config(p.asdict()) == p


def test_point_configs_set_model_cell():
    space = explore.SearchSpace(cell=("rglru",))
    model, accel = next(iter(space.grid())).configs()
    assert model.cell == "rglru"
    assert plan(model, accel)["backend"] == "xla"
