"""The quantised recurrent cell registry — one contract, many cells.

The paper's parameterised-design claim (and ROADMAP open item 2) is that
ONE accelerator datapath serves many recurrent scenarios.  This package is
that contract: a :class:`CellSpec` describes everything a cell must bring
to run on the accelerator — parameter tree, per-layer carry shape, the
bit-exact integer datapath, a pure-jnp ref oracle, and (optionally) a
fused Pallas kernel predicate — and every downstream layer (the backend
registry, ``repro.serving``, the explorer) dispatches through the spec
instead of hardcoding LSTM.

Registered cells:

  * ``lstm``  — the paper's quantised LSTM (``core.qlstm``): per-layer
    (h, c) carry, fused Pallas kernel for the pipelined + hard-activation
    point.
  * ``gru``   — quantised GRU (``cells.gru``): per-layer (h,) carry,
    gate order [r, z, n], same S5 single-late-rounding accumulator
    contract.
  * ``rglru`` — quantised RG-LRU (``cells.rglru``): the Griffin
    recurrence (``models/rglru.py``) re-derived for the fixed-point
    datapath — input-only sigmoid gates, a ``1 - r*lambda`` decay, and a
    convex ``a*h + (1-a)*(i*x)`` mix; per-layer (h,) carry.

Every cell shares the dense K -> P head and the int-path contract pinned
by ``tests/test_cells.py``: ref <-> xla bit-exactness, and
windowed-vs-concatenated bit-exactness through ``StreamServer``.

The per-layer integer carry is a tuple of ``state_arity`` int32 arrays of
shape ``(batch, hidden)`` — the LSTM's classic ``(h, c)`` is simply the
``arity == 2`` instance — and the whole-model state is a tuple of those
over layers (:func:`init_state` / :func:`state_shape`).  Serving keys its
device slot table
(``kernels.qlstm_cell.state_table_shape(slots + 2, L, S, H)``) on
:func:`state_shape`, never on a hardcoded ``(L, 2, H)``.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.qlstm import QLSTMConfig

Array = jax.Array


def paper_datapath_reason(model: QLSTMConfig, accel) -> Optional[str]:
    """Shared predicate for the engines that implement exactly the paper's
    pipelined (late-rounding) ALU with the hard activations — the ref
    oracles and the fused kernels.  Returns ``None`` when the resolved
    configuration is that point, else the reason it is not."""
    if model.alu_mode != "pipelined":
        return (f"alu_mode={model.alu_mode!r}: only the pipelined "
                "(late-rounding) ALU is implemented")
    if model.acts.gate != "hard_sigmoid_star":
        return f"gate activation {model.acts.gate!r}: needs hard_sigmoid_star"
    if model.acts.cell != "hard_tanh":
        return f"cell activation {model.acts.cell!r}: needs hard_tanh"
    return None


@dataclasses.dataclass(frozen=True)
class CellSpec:
    """Everything one recurrent cell brings to the accelerator contract.

    The callables mirror the LSTM surface of ``core.qlstm`` exactly;
    ``tests/test_cells.py`` holds every registered cell to the same
    battery shape (bit-exact ref <-> xla parity, stateful-serving
    bit-exactness)."""

    #: Registry id (the ``QLSTMConfig.cell`` value).
    name: str
    #: Arrays per layer in the integer carry (LSTM 2: (h, c); GRU/rGLRU 1).
    state_arity: int
    #: Debug/docs names of the carry arrays, e.g. ``("h", "c")``.
    state_names: Tuple[str, ...]
    #: (model, key) -> float master params ({"layers": [...], "dense": ...}).
    init_params: Callable
    #: (params, model) -> integer codes (weights (a,b), biases wide).
    quantize_params: Callable
    #: (params, x, model) -> y — float training/eval semantics.
    forward_float: Callable
    #: (params, x, model) -> y — STE fake-quant at every rounding point.
    forward_qat: Callable
    #: (qparams, x_int, model, state) -> (y_int, new_state) — the general
    #: integer datapath (both ALU modes, LUT acts); the xla engine.
    run_int_stateful: Callable
    #: (x_tm, layer_params, model, carry) -> (h_seq, new_carry) — one
    #: layer of the pure-jnp bit-exact oracle (time-major); the ref engine.
    ref_layer: Callable
    #: (model, accel) -> Optional[str] — can the general int datapath run
    #: this configuration (None = yes, else the reason).
    supports_int: Callable
    #: (model, accel) -> Optional[str] — can the ref oracle run it.
    supports_oracle: Callable
    #: (model) -> equivalent ops per inference (the GOP/s convention).
    ops_per_inference: Callable
    #: (model, accel) -> bytes of quantised weights+biases to hold.
    weight_bytes: Callable
    #: (model, accel) -> Optional[str] for the fused Pallas kernel, or
    #: ``None`` (the attribute) when the cell has no fused kernel at all.
    supports_fused: Optional[Callable] = None

    def run_int(self, qparams, x_int: Array, model: QLSTMConfig) -> Array:
        """Stateless integer forward: the stateful datapath started from
        the zero reset carry (how ``forward_int`` relates to
        ``forward_int_stateful`` for every cell)."""
        y, _ = self.run_int_stateful(qparams, x_int, model,
                                     init_state(model, x_int.shape[0]))
        return y


_REGISTRY: Dict[str, CellSpec] = {}


def register(spec: CellSpec) -> CellSpec:
    """Add a cell to the registry (last registration under a name wins)
    and return it, so cell modules can ``SPEC = register(CellSpec(...))``."""
    _REGISTRY[spec.name] = spec
    return spec


def get(name: str) -> CellSpec:
    """The registered cell spec under ``name``; KeyError names the known
    cells when it does not exist."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown cell {name!r}; "
                       f"registered: {sorted(_REGISTRY)}") from None


def available() -> Tuple[str, ...]:
    """Names of every registered cell, sorted."""
    return tuple(sorted(_REGISTRY))


def state_shape(model: QLSTMConfig) -> Tuple[int, int, int]:
    """The per-stream carry shape ``(num_layers, state_arity, hidden)``
    for the model's cell — what serving keys its state tables on
    (``plan()['state_shape']``)."""
    spec = get(model.cell)
    return (model.num_layers, spec.state_arity, model.hidden_size)


def init_state(model: QLSTMConfig, batch: int):
    """The reset integer carry for any cell: per layer, ``state_arity``
    zero ``(batch, hidden)`` int32 code arrays — exactly what the
    accelerator's state registers hold before a stream's first window.
    For ``cell='lstm'`` this is bit-for-bit ``core.qlstm.init_int_state``."""
    spec = get(model.cell)
    z = lambda: jnp.zeros((batch, model.hidden_size), jnp.int32)
    return tuple(tuple(z() for _ in range(spec.state_arity))
                 for _ in range(model.num_layers))


# Importing the cell modules registers the zoo.
from repro.cells import gru as _gru      # noqa: E402,F401
from repro.cells import lstm as _lstm    # noqa: E402,F401
from repro.cells import rglru as _rglru  # noqa: E402,F401
