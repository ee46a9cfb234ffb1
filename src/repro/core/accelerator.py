"""Parameterised accelerator configuration — the paper's Table 2 (C4),
re-targeted from Spartan-7 resources to the TPU v5e execution model.

FPGA meta-parameter            ->  TPU meta-parameter
  ALU_resource_type DSP|LUT    ->  compute_unit      mxu|vpu
  weight_resource_type
      BRAM|LUTRAM|AUTO         ->  weight_memory     vmem|hbm|auto
  HardSigmoid*_method          ->  hs_method         arithmetic|1to1|step
  HardTanh_threshold           ->  ht_min/ht_max
  ALU pipelining (C3)          ->  alu_mode          pipelined|per_step
  fixed-point format (a,b)     ->  fxp
  hidden_size / input_size /
  in_features / out_features   ->  (unchanged; QLSTMConfig)

``AcceleratorConfig`` is the SINGLE SOURCE OF TRUTH for the implementation
knobs (``hs_method``, ``ht_min``/``ht_max``, ``fxp``, ``alu_mode``,
``backend``).  ``QLSTMConfig``/``ActivationConfig`` retain mirror fields
for one deprecation release; ``resolve_model()`` merges the two, honouring
old-style model-side settings with a ``DeprecationWarning`` (see
docs/API.md for the deprecation table).

``plan()`` resolves AUTO decisions exactly like Vivado's BRAM->LUTRAM spill
in the paper's Fig. 4/5: weights live in VMEM while they fit the VMEM
budget, then spill to HBM streaming.  The plan selects the execution
backend (`repro/backends/`) and feeds the energy model (`core/energy.py`).
"""

from __future__ import annotations

import dataclasses
import warnings
from typing import Dict, Optional

from repro.core.fixed_point import FixedPointConfig, FXP_4_8
from repro.core.qlstm import ActivationConfig, QLSTMConfig

# TPU v5e per-core VMEM budget we allow the kernel to claim (bytes).  The
# physical VMEM is ~128 MiB; we keep headroom for pipeline buffers, like the
# paper keeps BRAM headroom for the dense layer.
VMEM_BUDGET_BYTES = 96 * 1024 * 1024

ALU_MODES = ("pipelined", "per_step")
HS_METHODS = ("arithmetic", "1to1", "step")
BACKENDS = ("auto", "ref", "pallas", "xla")


@dataclasses.dataclass(frozen=True, init=False)
class AcceleratorConfig:
    """Table-2 implementation meta-parameters (TPU form).

    ``backend`` picks the execution engine behind ``Accelerator.infer``:
    ``auto`` (plan-driven: fused Pallas kernel when the configuration
    supports it, else the XLA ``lax.scan`` datapath), or an explicit
    ``ref`` | ``pallas`` | ``xla`` override.

    ``pipelined_alu`` is accepted as a deprecated constructor alias for
    ``alu_mode`` (True -> pipelined, False -> per_step) and readable as a
    derived property; it is NOT a field, so ``dataclasses.replace`` round-
    trips cleanly through ``alu_mode`` alone.
    """

    compute_unit: str = "mxu"       # mxu (DSP) | vpu (LUT)
    weight_memory: str = "auto"     # vmem (BRAM) | hbm (LUTRAM) | auto
    hs_method: str = "step"         # arithmetic | 1to1 | step
    ht_min: float = -1.0
    ht_max: float = 1.0
    alu_mode: str = "pipelined"     # C3: pipelined (late rounding) | per_step
    fxp: FixedPointConfig = FXP_4_8
    vmem_budget: int = VMEM_BUDGET_BYTES
    backend: str = "auto"           # auto | ref | pallas | xla

    def __init__(self, compute_unit: str = "mxu", weight_memory: str = "auto",
                 hs_method: str = "step", ht_min: float = -1.0,
                 ht_max: float = 1.0, alu_mode: str = "pipelined",
                 fxp: FixedPointConfig = FXP_4_8,
                 vmem_budget: int = VMEM_BUDGET_BYTES, backend: str = "auto",
                 pipelined_alu: Optional[bool] = None):
        if pipelined_alu is not None:
            warnings.warn(
                "AcceleratorConfig(pipelined_alu=...) is deprecated; use "
                "alu_mode='pipelined'|'per_step'", DeprecationWarning,
                stacklevel=2)
            alu_mode = "pipelined" if pipelined_alu else "per_step"
        if compute_unit not in ("mxu", "vpu"):
            raise ValueError(f"compute_unit must be mxu|vpu, got {compute_unit}")
        if weight_memory not in ("vmem", "hbm", "auto"):
            raise ValueError("weight_memory must be vmem|hbm|auto")
        if hs_method not in HS_METHODS:
            raise ValueError(f"hs_method must be one of {HS_METHODS}")
        if alu_mode not in ALU_MODES:
            raise ValueError(f"alu_mode must be one of {ALU_MODES}")
        if backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}")
        for name, val in (("compute_unit", compute_unit),
                          ("weight_memory", weight_memory),
                          ("hs_method", hs_method), ("ht_min", ht_min),
                          ("ht_max", ht_max), ("alu_mode", alu_mode),
                          ("fxp", fxp), ("vmem_budget", vmem_budget),
                          ("backend", backend)):
            object.__setattr__(self, name, val)

    @property
    def pipelined_alu(self) -> bool:
        """Deprecated read alias: ``alu_mode == 'pipelined'``."""
        return self.alu_mode == "pipelined"


PAPER_DEFAULT = AcceleratorConfig()                      # "this work", col 5 of T4
PAPER_NO_MXU = AcceleratorConfig(compute_unit="vpu")     # DSP-free variant, col 6
BASELINE_15 = AcceleratorConfig(                         # Qian et al. [15]
    compute_unit="mxu", hs_method="1to1", alu_mode="per_step",
    fxp=FixedPointConfig(8, 16))


# ---------------------------------------------------------------------------
# Config unification: AcceleratorConfig is the source of truth
# ---------------------------------------------------------------------------

# (field on AcceleratorConfig, owner of the legacy mirror, legacy field name)
_MOVED_KNOBS = (
    ("fxp", "model", "fxp"),
    ("alu_mode", "model", "alu_mode"),
    ("hs_method", "acts", "hs_method"),
    ("ht_min", "acts", "ht_min"),
    ("ht_max", "acts", "ht_max"),
)


def _default_of(cls, field_name):
    for f in dataclasses.fields(cls):
        if f.name == field_name:
            if f.default is not dataclasses.MISSING:
                return f.default
            return f.default_factory()  # pragma: no cover
    raise KeyError(field_name)


def resolve_model(model: QLSTMConfig, accel: AcceleratorConfig,
                  warn: bool = True) -> QLSTMConfig:
    """Merge legacy model-side knobs into one consistent ``QLSTMConfig``.

    For each knob that moved to ``AcceleratorConfig`` (fxp, alu_mode,
    hs_method, ht_min/ht_max):

      * accelerator set (non-default)            -> accelerator wins,
      * only the legacy model/acts field set     -> it wins, with a
        ``DeprecationWarning`` (the one-release compatibility shim),
      * both set and disagreeing                 -> accelerator wins, with a
        warning naming the conflict.

    The returned config is what the datapaths (`forward_*`, backends,
    kernels) actually run."""
    resolved: Dict[str, object] = {}
    for acc_field, owner, legacy in _MOVED_KNOBS:
        acc_val = getattr(accel, acc_field)
        acc_dflt = _default_of(AcceleratorConfig, acc_field)
        if owner == "model":
            old_val = getattr(model, legacy)
            old_dflt = _default_of(QLSTMConfig, legacy)
        else:
            old_val = getattr(model.acts, legacy)
            old_dflt = _default_of(ActivationConfig, legacy)
        if acc_val == acc_dflt and old_val != old_dflt:
            if warn:
                warnings.warn(
                    f"setting {legacy!r} on "
                    f"{'QLSTMConfig' if owner == 'model' else 'ActivationConfig'}"
                    f" is deprecated; set AcceleratorConfig.{acc_field} "
                    f"instead (honouring the legacy value {old_val!r} for "
                    f"this release)", DeprecationWarning, stacklevel=3)
            resolved[acc_field] = old_val
        else:
            if (warn and acc_val != acc_dflt and old_val != old_dflt
                    and old_val != acc_val):
                warnings.warn(
                    f"{legacy!r} set to {old_val!r} on the model config but "
                    f"{acc_val!r} on AcceleratorConfig; the accelerator "
                    f"value wins", DeprecationWarning, stacklevel=3)
            resolved[acc_field] = acc_val

    acts = dataclasses.replace(model.acts, hs_method=resolved["hs_method"],
                               ht_min=resolved["ht_min"],
                               ht_max=resolved["ht_max"])
    return dataclasses.replace(model, acts=acts, fxp=resolved["fxp"],
                               alu_mode=resolved["alu_mode"])


def sync_accelerator(model: QLSTMConfig,
                     accel: AcceleratorConfig) -> AcceleratorConfig:
    """The inverse direction: an AcceleratorConfig whose moved knobs match a
    (possibly legacy-style) resolved model — what plan()/the energy model
    should score."""
    m = resolve_model(model, accel, warn=False)
    return dataclasses.replace(accel, fxp=m.fxp, alu_mode=m.alu_mode,
                               hs_method=m.acts.hs_method,
                               ht_min=m.acts.ht_min, ht_max=m.acts.ht_max)


def weight_bytes(model: QLSTMConfig, acc: AcceleratorConfig) -> int:
    """Bytes of quantised weights+biases the accelerator must hold, for
    whatever cell ``model.cell`` names (dispatched through the
    ``repro.cells`` registry)."""
    # Lazy import: repro.cells -> cells.lstm -> repro.kernels -> this module.
    from repro import cells
    return cells.get(model.cell).weight_bytes(model, acc)


def lstm_weight_bytes(model: QLSTMConfig, acc: AcceleratorConfig) -> int:
    """Back-compat alias of :func:`weight_bytes` (pre-cell-registry name);
    still correct for every cell, not just LSTM."""
    return weight_bytes(model, acc)


def resolve_weight_memory(model: QLSTMConfig, acc: AcceleratorConfig) -> str:
    """AUTO spill decision (Fig 4/5 analogue)."""
    if acc.weight_memory != "auto":
        return acc.weight_memory
    return "vmem" if weight_bytes(model, acc) <= acc.vmem_budget else "hbm"


def resolve_backend(model: QLSTMConfig, acc: AcceleratorConfig) -> str:
    """Plan-driven backend choice (the explicit override passes through).

    A fused Pallas kernel is used when the model's cell HAS one
    (``CellSpec.supports_fused`` is set — today only the LSTM) and the
    configuration is the point it implements (the paper's pipelined ALU
    with the hard activations); anything else (per-step ALU baseline, LUT
    acts, GRU/rGLRU cells) runs on the XLA ``lax.scan`` datapath."""
    if acc.backend != "auto":
        return acc.backend
    from repro import cells  # lazy: repro.cells imports this module
    spec = cells.get(model.cell)
    fused_ok = (spec.supports_fused is not None
                and spec.supports_fused(model, acc) is None)
    return "pallas" if fused_ok else "xla"


def resolve_stateful_backend(model: QLSTMConfig,
                             acc: AcceleratorConfig) -> str:
    """Backend choice for the cross-window STATEFUL path (`repro.serving`).

    Identical to the stateless resolution: every registered engine —
    including the fused Pallas kernel, whose per-layer (h, c) VMEM scratch
    is seeded from the carried state and returned after the last step —
    implements ``run_stateful``, so the serving hot path runs on the same
    engine ``plan()['backend']`` picks (docs/API.md §Backends documents
    the full selection order).  Kept as its own resolution point so a
    future stateless-only engine can be substituted away here again;
    `backends.select_stateful` raises if an explicitly requested engine
    can't carry state."""
    return resolve_backend(model, acc)


def plan(model: QLSTMConfig, acc: AcceleratorConfig) -> Dict:
    """Resolve every implementation decision for (model, accelerator).

    Returned dict drives backend dispatch and the energy/footprint report —
    the TPU analogue of the paper's Vivado configuration point."""
    from repro import cells  # lazy: repro.cells imports this module
    model = resolve_model(model, acc, warn=False)
    acc = sync_accelerator(model, acc)
    wmem = resolve_weight_memory(model, acc)
    wbytes = weight_bytes(model, acc)
    return {
        # Which recurrent cell the datapath runs, and the per-stream carry
        # shape (num_layers, state_arity, hidden) its spec declares —
        # serving keys every state table on this, never on a hardcoded
        # LSTM (L, 2, H).
        "cell": model.cell,
        "state_shape": cells.state_shape(model),
        "compute_unit": acc.compute_unit,
        "weight_memory": wmem,
        "weight_bytes": wbytes,
        "vmem_resident": wmem == "vmem",
        "hs_method": acc.hs_method,
        "pipelined_alu": acc.alu_mode == "pipelined",
        "alu_mode": acc.alu_mode,
        "fxp": acc.fxp,
        "backend": resolve_backend(model, acc),
        # The engine repro.serving uses for cross-window (h, c) carry —
        # currently always equal to "backend" (every engine is stateful;
        # see resolve_stateful_backend), kept as its own key so serving
        # code has one stable place to ask.
        "stateful_backend": resolve_stateful_backend(model, acc),
        # MXU tiles are 128x128: tiny LSTMs under-fill them, exactly like
        # tiny models under-fill DSP columns.  Report the padding waste.
        "mxu_fill_fraction": _mxu_fill(model) if acc.compute_unit == "mxu" else None,
    }


def _mxu_fill(model: QLSTMConfig) -> float:
    m, h = model.layer_in_dim(0), model.hidden_size
    k, n = m + h, 4 * h
    pad = lambda d: -(-d // 128) * 128
    return (k * n) / (pad(k) * pad(n))
