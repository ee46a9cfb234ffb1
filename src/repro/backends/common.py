"""Shared scaffolding for the layered (ref / pallas) backends: both engines
implement one *layer* (time-major sequence of cell steps); the whole-model
``run`` — layer stacking plus the dense head with the single late rounding —
is identical and lives here so the two cannot drift."""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import fixed_point as fxp
from repro.core.accelerator import AcceleratorConfig
from repro.core.qlstm import QLSTMConfig, check_int_state
from repro.kernels.qlstm_cell import gather_carry, scatter_carry

Array = jax.Array


def supports_fused(model: QLSTMConfig,
                   accel: AcceleratorConfig) -> Optional[str]:
    """Can the FUSED (Pallas) datapath run this configuration?  Delegates
    to the cell spec: a cell without a fused kernel
    (``CellSpec.supports_fused is None`` — GRU, rGLRU today) is refused
    outright; a cell with one (LSTM) applies its own predicate — the
    paper's pipelined datapath with the hard activations (C2+C3).
    Anything refused here is the xla engine's job."""
    from repro import cells  # lazy: avoids the cells -> kernels -> backends cycle
    spec = cells.get(model.cell)
    if spec.supports_fused is None:
        return f"cell {model.cell!r} has no fused kernel"
    return spec.supports_fused(model, accel)


def dense_head(h_last: Array, qparams, model: QLSTMConfig) -> Array:
    """The shared dense head: final-step (B, H) hidden codes -> (B, P)
    output codes, with the single late rounding (S5).  Every layered
    engine — and the fused multi-layer pallas datapath — ends here, so the
    head cannot drift between them."""
    return fxp.fxp_matvec_late_rounding(
        h_last, qparams["dense"]["w"], qparams["dense"]["b"], model.fxp)


def run_layered(layer_fn: Callable, qparams, x_int: Array,
                model: QLSTMConfig, accel: AcceleratorConfig) -> Array:
    """Stack ``layer_fn`` over ``model.num_layers`` and apply the dense head.

    x_int: (B, T, M) integer codes in ``model.fxp`` -> (B, P) codes."""
    h_t = jnp.swapaxes(x_int, 0, 1).astype(jnp.int32)   # time-major (T, B, M)
    for p in qparams["layers"]:
        h_t = layer_fn(h_t, p["w_x"], p["w_h"], p["b"], model, accel)
        h_t = h_t.astype(jnp.int32)
    return dense_head(h_t[-1], qparams, model)


def run_slots_via_state(run_stateful: Callable, qparams, x_int: Array,
                        model: QLSTMConfig, accel: AcceleratorConfig,
                        table: Array, gather_slots: Array,
                        scatter_slots: Array):
    """Generic ``run_stateful_slots`` for engines without an in-kernel slot
    path: gather the per-layer carry batch from the state table, run the
    engine's ``run_stateful``, scatter the new carry back — all in jnp, so
    under jit the table never leaves the device even though the engine
    itself only understands dense state.  This keeps every rung of the
    serving degradation ladder device-resident: falling back from the
    fused pallas kernel to ``xla``/``ref`` changes latency, never where
    the state lives.  The carry layout comes from the cell spec
    (``cells.state_shape``), so the adapter serves every registered cell
    — LSTM's (h, c) and the single-array GRU / rGLRU carries alike.

    Same table contract as ``kernels/qlstm_cell.qlstm_seq_slot_pallas``
    (slots ``n_slots``/``n_slots + 1`` are the ZERO/TRASH slots); returns
    ``(y_int, new_table)``."""
    from repro import cells  # lazy: avoids the cells -> kernels -> backends cycle
    state = gather_carry(table, gather_slots, *cells.state_shape(model))
    y_int, new_state = run_stateful(qparams, x_int, model, accel, state)
    return y_int, scatter_carry(table, scatter_slots, new_state)


def run_layered_stateful(layer_fn: Callable, qparams, x_int: Array,
                         model: QLSTMConfig, accel: AcceleratorConfig,
                         state):
    """Stateful counterpart of :func:`run_layered` — threads the per-layer
    (h, c) carry through ``layer_fn`` and returns it alongside the output.

    ``layer_fn`` here takes the extra ``(h0, c0)`` carry and returns
    ``(h_seq, (h_last, c_last))``.  ``state`` is the per-layer carry tuple
    (``core.qlstm.IntState``); returns ``(y_int, new_state)``."""
    check_int_state(state, qparams)
    h_t = jnp.swapaxes(x_int, 0, 1).astype(jnp.int32)   # time-major (T, B, M)
    new_state = []
    for p, (h0, c0) in zip(qparams["layers"], state):
        h_t, carry = layer_fn(h_t, p["w_x"], p["w_h"], p["b"], model, accel,
                              h0, c0)
        h_t = h_t.astype(jnp.int32)
        new_state.append(carry)
    return dense_head(h_t[-1], qparams, model), tuple(new_state)
