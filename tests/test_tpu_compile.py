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
    """The host-residency serving kernel at the paper's width."""
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
    """The device-residency serving kernel — what a ``StreamServer`` runs
    by default — at the server's default wave and table sizes."""
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
