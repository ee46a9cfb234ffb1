"""TPU v5e performance & energy model — the paper's evaluation method (C5)
re-expressed for TPUs.

The paper scores configurations by GOP/s (throughput) and GOP/s/W (energy
efficiency), splitting power into STATIC (leakage — burns regardless of
work) and DYNAMIC (switching — proportional to activity).  The TPU analogue:

  P_total(t) = P_STATIC + E_dynamic / t
  E_dynamic  = e_mxu|vpu * ops  +  e_hbm * hbm_bytes  +  e_ici * ici_bytes

Roofline terms (the §Roofline deliverable) use the hardware constants below
(task-specified: 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI).

Energy constants are documented engineering estimates (no public per-op
energy exists for v5e); they are chosen so a compute-bound bf16 run draws
~160 W and a memory-bound run ~140 W — consistent with published v5e system
figures.  All *relative* comparisons (MXU vs VPU, int8 vs bf16, quantised vs
not — the paper's Table 4 structure) are robust to the absolute calibration,
and the constants live in one place so they can be re-calibrated.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

#: ``device_kind`` strings of the one chip the constants below describe.
#: Off the TPU (CPU runs) the model is a projection onto this chip.
MODELLED_TPU_KINDS = ("TPU v5 lite", "TPU v5e")


def check_modelled_device(device) -> None:
    """Raise unless the energy model describes ``device``: any TPU other
    than a v5e would get v5e numbers printed under its own name."""
    if device.platform == "tpu" and device.device_kind not in \
            MODELLED_TPU_KINDS:
        raise RuntimeError(
            f"the energy model's constants describe a TPU v5e; this device "
            f"is {device.device_kind!r} — add its constants before "
            f"reporting energy on it")


# --- Roofline peaks (task-specified) ---------------------------------------
PEAK_BF16_FLOPS = 197e12          # per chip
PEAK_INT8_OPS = 394e12            # MXU int8 = 2x bf16
PEAK_VPU_FLOPS = 1.9e12           # 8x128 lanes * 2 (fma) * ~940 MHz — estimate
HBM_BW = 819e9                    # bytes/s per chip
ICI_BW_PER_LINK = 50e9            # bytes/s per link
ICI_LINKS = 4                     # v5e: 4 ICI links per chip (2D torus)

# --- Energy model constants (documented estimates) -------------------------
P_STATIC_W = 60.0                 # idle/leakage per chip
E_MXU_BF16_J_PER_FLOP = 0.50e-12
E_MXU_INT8_J_PER_OP = 0.25e-12    # narrow multipliers switch less — C1's point
E_VPU_J_PER_FLOP = 2.0e-12        # vector datapath, no systolic reuse
E_HBM_J_PER_BYTE = 100e-12
E_ICI_J_PER_BYTE = 30e-12


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    """The three §Roofline terms, in seconds (per device)."""

    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def bound(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_s(self) -> float:
        """Lower-bound step time: terms overlap perfectly -> max()."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def step_s_serial(self) -> float:
        """Upper-bound step time: no overlap -> sum()."""
        return self.compute_s + self.memory_s + self.collective_s

    def asdict(self) -> Dict:
        return {"compute_s": self.compute_s, "memory_s": self.memory_s,
                "collective_s": self.collective_s, "bound": self.bound,
                "step_s": self.step_s}


def roofline_terms(flops: float, hbm_bytes: float, collective_bytes: float,
                   unit: str = "mxu", dtype: str = "bf16",
                   ici_links: int = ICI_LINKS) -> RooflineTerms:
    """Per-device terms from per-device HLO counts (see launch/dryrun.py)."""
    if unit == "vpu":
        peak = PEAK_VPU_FLOPS
    elif dtype == "int8":
        peak = PEAK_INT8_OPS
    else:
        peak = PEAK_BF16_FLOPS
    return RooflineTerms(
        compute_s=flops / peak,
        memory_s=hbm_bytes / HBM_BW,
        collective_s=collective_bytes / (ICI_BW_PER_LINK * ici_links),
    )


def dynamic_energy_j(flops: float, hbm_bytes: float, ici_bytes: float = 0.0,
                     unit: str = "mxu", dtype: str = "bf16") -> float:
    if unit == "vpu":
        e_op = E_VPU_J_PER_FLOP
    elif dtype == "int8":
        e_op = E_MXU_INT8_J_PER_OP
    else:
        e_op = E_MXU_BF16_J_PER_FLOP
    return e_op * flops + E_HBM_J_PER_BYTE * hbm_bytes + E_ICI_J_PER_BYTE * ici_bytes


def power_report(flops: float, hbm_bytes: float, ici_bytes: float,
                 latency_s: float, unit: str = "mxu",
                 dtype: str = "bf16") -> Dict:
    """The paper's Table-4 row: static/dynamic/total power, energy/inference,
    throughput and energy efficiency."""
    e_dyn = dynamic_energy_j(flops, hbm_bytes, ici_bytes, unit, dtype)
    e_static = P_STATIC_W * latency_s
    p_dyn = e_dyn / latency_s if latency_s > 0 else 0.0
    gops = flops / latency_s / 1e9 if latency_s > 0 else 0.0
    p_total = P_STATIC_W + p_dyn
    return {
        "static_w": P_STATIC_W,
        "dynamic_w": p_dyn,
        "total_w": p_total,
        "latency_s": latency_s,
        "energy_j": e_dyn + e_static,
        "throughput_gops": gops,
        "gops_per_watt": gops / p_total if p_total > 0 else 0.0,
    }


def model_flops_train(n_params: float, n_tokens: float,
                      n_active_params: Optional[float] = None) -> float:
    """MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) — §Roofline."""
    n = n_active_params if n_active_params is not None else n_params
    return 6.0 * n * n_tokens


def model_flops_decode(n_params: float, n_tokens: float,
                       n_active_params: Optional[float] = None) -> float:
    """2*N per generated token (forward only)."""
    n = n_active_params if n_active_params is not None else n_params
    return 2.0 * n * n_tokens
