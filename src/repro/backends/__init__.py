"""Backend-dispatch registry for the quantised recurrent accelerator
datapath — cell-agnostic: each engine serves whatever cell the model's
``repro.cells`` spec names (LSTM, GRU, rGLRU).

Every execution engine behind ``Accelerator.infer``/``Accelerator.serve``
lives here; nothing outside this package imports ``forward_int`` or
``qlstm_seq_pallas`` directly.  Three engines are registered:

  * ``ref``    — the bit-exact pure-jnp oracle (`kernels/ref.py`, via the
                 cell spec's ``ref_layer``): explicit matmuls per step,
                 pipelined (late-rounding) ALU with the hard activations.
                 The specification the other two must match bit-for-bit.
  * ``pallas`` — the fused TPU kernel (`kernels/qlstm_cell.py`): weights
                 VMEM-resident, double-buffered input DMA, MXU or VPU
                 compute.  Pipelined ALU + hard activations, and only for
                 cells with a fused kernel (today the LSTM; GRU/rGLRU
                 resolve to ``xla``).
  * ``xla``    — the ``lax.scan`` datapath (the cell spec's
                 ``run_int_stateful``): supports every Table-2 point of
                 every cell, including the per-step (non-pipelined,
                 baseline [15]) ALU and the 256-entry LUT activations.

Selection is plan-driven (``core/accelerator.resolve_backend``): ``auto``
picks ``pallas`` when the configuration fits the fused kernel, else
``xla``; ``AcceleratorConfig.backend`` or the ``backend=`` argument of
``Accelerator.infer`` overrides explicitly.

A backend exposes

  run(qparams, x_int, model, accel) -> y_int      # whole model, batch-major
  layer(x_int, w_x, w_h, b_wide, model, accel)    # one layer, time-major
  supports(model, accel) -> Optional[str]         # None = ok, else reason

and, when it can carry recurrent state across calls (the
``repro.serving`` stateful-streaming contract),

  run_stateful(qparams, x_int, model, accel, state) -> (y_int, new_state)

where ``state`` is the cell's carry: per layer, a tuple of
``state_arity`` int32 ``(B, H)`` code arrays (the LSTM's (h, c) is the
arity-2 instance; ``repro.cells.init_state`` builds the reset carry).
All three engines implement it — the fused ``pallas`` kernel seeds its
(h, c) VMEM scratch from the carried state and returns the final state —
so stateful selection (``select_stateful``, following the plan's
``stateful_backend``) resolves exactly like the stateless path
(docs/API.md §Backends documents the selection order).

For the DEVICE-RESIDENT carry table every stateful server keeps, an
engine may additionally expose

  run_stateful_slots(qparams, x_int, model, accel,
                     table, gather_slots, scatter_slots)
      -> (y_int, new_table)

where ``table`` is the persistent int32 state table of
``kernels.qlstm_cell.state_table_shape(n_slots + 2, L, S, H)`` and the
slot vectors are per-batch-row slot ids (the contract
of ``kernels/qlstm_cell.qlstm_seq_slot_pallas``).  The ``pallas`` engine
gathers/scatters inside the fused kernel; ``ref`` and ``xla`` use the
XLA-level adapter (``common.run_slots_via_state`` — still device-side,
so degrading down the ladder never moves the carry back to the host).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

from repro.core.accelerator import (AcceleratorConfig, resolve_backend,
                                    resolve_model, resolve_stateful_backend)
from repro.core.qlstm import QLSTMConfig


class BackendUnsupported(ValueError):
    """Raised when an explicitly requested backend cannot execute the
    resolved (model, accelerator) configuration."""


@dataclasses.dataclass(frozen=True)
class Backend:
    """One registered execution engine: the callables the dispatch layer
    (``select`` / ``select_stateful``) hands to ``Accelerator``."""

    name: str
    run: Callable                       # (qparams, x_int, model, accel) -> y_int
    supports: Callable                  # (model, accel) -> Optional[str]
    layer: Optional[Callable] = None    # (x_int, wx, wh, b, model, accel) -> h_seq
    # (qparams, x_int, model, accel, state) -> (y_int, new_state); None when
    # the engine cannot start from a non-zero (h, c) carry.
    run_stateful: Optional[Callable] = None
    # (qparams, x_int, model, accel, table, gather_slots, scatter_slots)
    # -> (y_int, new_table): the device-resident state-table entry point
    # (slot gather/scatter on the device; module docstring has the table
    # layout).  None when the engine has no slot path.
    run_stateful_slots: Optional[Callable] = None


_REGISTRY: Dict[str, Backend] = {}


def register(backend: Backend) -> Backend:
    """Add an engine to the registry (last registration under a name wins)
    and return it, so modules can ``BACKEND = register(Backend(...))``."""
    _REGISTRY[backend.name] = backend
    return backend


def get(name: str) -> Backend:
    """The registered engine under ``name``; KeyError names the known
    engines when it does not exist."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown backend {name!r}; "
                       f"registered: {sorted(_REGISTRY)}") from None


def available() -> Tuple[str, ...]:
    """Names of every registered engine, sorted."""
    return tuple(sorted(_REGISTRY))


def select(model: QLSTMConfig, accel: AcceleratorConfig,
           override: Optional[str] = None) -> Backend:
    """Resolve the backend for a configuration.

    ``override`` (or a non-``auto`` ``accel.backend``) is honoured verbatim
    — raising :class:`BackendUnsupported` if the engine can't run the
    configuration.  ``auto`` asks the plan."""
    model = resolve_model(model, accel, warn=False)
    name = override if override not in (None, "auto") \
        else resolve_backend(model, accel)
    backend = get(name)
    reason = backend.supports(model, accel)
    if reason is not None:
        raise BackendUnsupported(
            f"backend {name!r} cannot run this configuration: {reason}")
    return backend


def supported_backends(model: QLSTMConfig,
                       accel: AcceleratorConfig) -> Tuple[str, ...]:
    """Names of every registered backend able to run the configuration."""
    model = resolve_model(model, accel, warn=False)
    return tuple(n for n in available()
                 if _REGISTRY[n].supports(model, accel) is None)


def _stateful_reason(backend: Backend, model: QLSTMConfig,
                     accel: AcceleratorConfig) -> Optional[str]:
    reason = backend.supports(model, accel)
    if reason is not None:
        return reason
    if backend.run_stateful is None:
        return ("no stateful entry point (the engine cannot carry state "
                "across windows)")
    return None


def select_stateful(model: QLSTMConfig, accel: AcceleratorConfig,
                    override: Optional[str] = None) -> Backend:
    """Resolve a backend able to carry recurrent state across windows.

    Same contract as :func:`select`, but ``auto`` follows the plan's
    ``stateful_backend`` — currently identical to the stateless choice,
    since every engine (including the fused pallas kernel) implements
    ``run_stateful``.  An explicit request for an engine without a
    stateful entry point raises :class:`BackendUnsupported`."""
    model = resolve_model(model, accel, warn=False)
    name = override if override not in (None, "auto") \
        else resolve_stateful_backend(model, accel)
    backend = get(name)
    reason = _stateful_reason(backend, model, accel)
    if reason is not None:
        raise BackendUnsupported(
            f"backend {name!r} cannot run this configuration statefully: "
            f"{reason}")
    return backend


def stateful_backends(model: QLSTMConfig,
                      accel: AcceleratorConfig) -> Tuple[str, ...]:
    """Names of every engine able to run the configuration with a carried
    recurrent state — the ``repro.serving`` capability surface."""
    model = resolve_model(model, accel, warn=False)
    return tuple(n for n in available()
                 if _stateful_reason(_REGISTRY[n], model, accel) is None)


# Canonical fastest-first engine order for graceful degradation: the fused
# kernel, then the general scan, then the pure-jnp oracle.  All three are
# bit-identical on the int path, so moving down the ladder changes
# latency, never results.
DEGRADATION_ORDER = ("pallas", "xla", "ref")


def degradation_ladder(model: QLSTMConfig, accel: AcceleratorConfig,
                       override: Optional[str] = None,
                       stateful: bool = True) -> Tuple[str, ...]:
    """Ordered engine names the serving tier degrades through on repeated
    backend failure: the resolved (or explicitly ``override``-requested)
    engine first, then every other engine capable of this configuration in
    :data:`DEGRADATION_ORDER` (engines registered outside the canonical
    order go last).  ``stateful`` restricts the ladder to engines with a
    cross-window state entry point — the ``repro.serving`` case."""
    first = (select_stateful if stateful else select)(
        model, accel, override=override).name
    capable = (stateful_backends if stateful else supported_backends)(
        model, accel)
    rest = [n for n in DEGRADATION_ORDER if n in capable and n != first]
    rest += [n for n in capable
             if n not in DEGRADATION_ORDER and n != first]
    return (first, *rest)


# Importing the submodules registers the engines.
from repro.backends import pallas as _pallas  # noqa: E402,F401
from repro.backends import ref as _ref        # noqa: E402,F401
from repro.backends import xla as _xla        # noqa: E402,F401
