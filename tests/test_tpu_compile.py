"""The serving path's fused kernels compile for a TPU v5e chip.

Interpret mode runs a kernel body on the CPU but never asks Mosaic to
lower it, so a kernel can pass every other test and still be refused by
the chip's compiler.  These tests compile each kernel of the main path,
at the paper's width and the server's default wave and table sizes, for
a v5e topology that is described, not attached.  Nothing runs.

The topology is described inside a fixture: only one process at a time
may load the TPU library, so describing it at import would break every
other test worker's collection."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.lstm_pems import CONFIG as PEMS
from repro.kernels.qlstm_cell import (qlstm_seq_multilayer_pallas,
                                      qlstm_seq_slot_pallas,
                                      state_table_shape)
from repro.serving import ServingConfig

BATCH = ServingConfig().batch              # 256
MAX_STREAMS = ServingConfig().max_streams  # 1024


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip cannot be read back without one, so
    keep it out of the persistent cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _weights(sharding, num_layers, m, h, sd):
    spec = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=sharding)
    w_xs = tuple(spec((m if li == 0 else h, 4 * h), sd)
                 for li in range(num_layers))
    w_hs = tuple(spec((h, 4 * h), sd) for _ in range(num_layers))
    bs = tuple(spec((4 * h,), jnp.int32) for _ in range(num_layers))
    return spec, w_xs, w_hs, bs


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("compute_unit", ["mxu", "vpu"])
def test_multilayer_kernel_compiles_for_v5e(one_chip, no_compile_cache,
                                            compute_unit):
    """The carry-in/carry-out kernel (``run_stateful``) at the paper's
    width."""
    cfg, t, m, h, n = (PEMS.fxp, PEMS.seq_len, PEMS.input_size,
                       PEMS.hidden_size, PEMS.num_layers)
    spec, w_xs, w_hs, bs = _weights(one_chip, n, m, h, cfg.storage_dtype)
    carry = tuple(spec((BATCH, h), jnp.int32) for _ in range(n))
    compiled = qlstm_seq_multilayer_pallas.lower(
        spec((t, BATCH, m), cfg.storage_dtype), w_xs, w_hs, bs, carry, carry,
        cfg=cfg, compute_unit=compute_unit, interpret=False).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("compute_unit", ["mxu", "vpu"])
def test_slot_kernel_compiles_for_v5e(one_chip, no_compile_cache,
                                      compute_unit):
    """The slot-table kernel — what a stateful ``StreamServer`` runs on
    pallas — at the server's default wave and table sizes."""
    cfg, t, m, h, n = (PEMS.fxp, PEMS.seq_len, PEMS.input_size,
                       PEMS.hidden_size, PEMS.num_layers)
    spec, w_xs, w_hs, bs = _weights(one_chip, n, m, h, cfg.storage_dtype)
    slots = spec((BATCH,), jnp.int32)
    table = spec(state_table_shape(MAX_STREAMS + 2, n, 2, h), jnp.int32)
    compiled = qlstm_seq_slot_pallas.lower(
        spec((t, BATCH, m), cfg.storage_dtype), slots, slots, table, w_xs,
        w_hs, bs, cfg=cfg, compute_unit=compute_unit,
        interpret=False).compile()
    _assert_kernel(compiled)


def test_slot_kernel_compiles_for_v5e_wide_deep(one_chip, no_compile_cache):
    """A 2-layer stack wider than one 128-lane table row (H=160 spans two
    row chunks) — the multi-chunk gather/scatter path."""
    cfg = PEMS.fxp
    spec, w_xs, w_hs, bs = _weights(one_chip, 2, 4, 160, cfg.storage_dtype)
    slots = spec((BATCH,), jnp.int32)
    table = spec(state_table_shape(MAX_STREAMS + 2, 2, 2, 160), jnp.int32)
    compiled = qlstm_seq_slot_pallas.lower(
        spec((6, BATCH, 4), cfg.storage_dtype), slots, slots, table, w_xs,
        w_hs, bs, cfg=cfg, interpret=False).compile()
    _assert_kernel(compiled)


PEMS_SENSORS = 2_160_000        # the benchmark's population (PERF.md)


@pytest.mark.parametrize("engine", ["pallas", "xla", "ref"])
def test_wave_program_updates_the_table_in_place(one_chip, no_compile_cache,
                                                 monkeypatch, engine):
    """The wave program as ``compiled_stateful_slots`` builds it, for a
    2.2-GB table: the table is aliased to the new table, and no op copies
    it.  A table that is not donated, or that XLA keeps in another layout
    than the kernel reads, costs a whole-table copy per wave each way."""
    import re

    import repro
    from repro.backends import pallas

    monkeypatch.setattr(pallas, "_interpret", lambda: False)
    sess = repro.build(PEMS).quantize()
    shape = state_table_shape(PEMS_SENSORS + 2, *sess.plan["state_shape"])
    table = jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    x = jax.ShapeDtypeStruct((BATCH, PEMS.seq_len, PEMS.input_size),
                             jnp.float32)
    slots = jax.ShapeDtypeStruct((BATCH,), jnp.int32)
    compiled = sess.compiled_stateful_slots(engine).lower(
        x, table, slots, slots).compile()
    table_bytes = 4 * shape[0] * shape[1]
    assert compiled.memory_analysis().alias_size_in_bytes >= table_bytes
    dims = ",".join(map(str, shape))
    copies = re.findall(rf"s32\[{dims}\]\{{[^}}]*\}} copy(?:-start)?\(",
                        compiled.as_text())
    assert not copies
    if engine == "pallas":
        _assert_kernel(compiled)


def test_stateless_wave_program_with_input_layer(one_chip, no_compile_cache,
                                                 monkeypatch):
    """The stateless wave program of the UCI HAR LSTM (9 -> 32 ReLU input
    layer, 2 x 32 LSTM, T = 128) as ``compiled("int")`` builds it: the input
    layer runs in front of the stack kernel, whose op is named
    ``qlstm_stack`` for the device trace."""
    import re

    import repro
    from repro.backends import pallas
    from repro.core.qlstm import QLSTMConfig

    monkeypatch.setattr(pallas, "_interpret", lambda: False)
    har = QLSTMConfig(input_size=9, input_dense=32, hidden_size=32,
                      num_layers=2, out_features=6, seq_len=128)
    sess = repro.build(har).quantize()
    x = jax.ShapeDtypeStruct((BATCH, har.seq_len, har.input_size),
                             jnp.float32, sharding=one_chip)
    compiled = sess.compiled("int", "pallas").lower(x).compile()
    _assert_kernel(compiled)
    assert re.search(r"%qlstm_stack\.\d+ = .*custom-call\(",
                     compiled.as_text())
