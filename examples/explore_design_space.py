"""Design-space exploration: the paper's Table-4 comparison as a *search*.

Table 4 compares three hand-picked configurations — the [15] baseline
((8,16) fixed point, per-step ALU), "this work" on DSPs (MXU), and the
DSP-free variant (VPU).  The parameterised design makes that table one
slice of a space: here we sweep compute unit x ALU mode x fixed-point
format through ``repro.explore``, score every point by measured throughput,
modelled GOP/s/W, and int-vs-float fidelity, print the Pareto front, and
let ``autotune`` pick the deployment point under a power constraint —
ending at the same configuration the paper hand-picks ((4,8), pipelined,
step activations) when the constraint allows it.

Run:  PYTHONPATH=src python examples/explore_design_space.py
"""
from repro import explore
from repro.analysis.report import pareto_table
from repro.compile_cache import use_compile_cache
from repro.core.fixed_point import FXP_4_8, FXP_8_16

use_compile_cache()

# The Table-4 axes.  hs_method stays at the paper's 'step' (Table 1 showed
# the three methods are accuracy-equivalent; 'step' is the cheapest) and
# batch at 64 to keep this example CPU-friendly.
space = explore.SearchSpace(
    fxp=(FXP_4_8, FXP_8_16),
    compute_unit=("mxu", "vpu"),
    alu_mode=("pipelined", "per_step"),
    batch=(64,),
)
print(f"sweeping {space.size} configurations "
      f"(Table 4 compared 3 hand-picked ones)...\n")

objectives = dict(explore.DEFAULT_OBJECTIVES, int_float_mse="min")
result = explore.sweep(space, iters=10, objectives=objectives, log=print)

print()
print(pareto_table(result))

# Deployment: maximise energy efficiency under a power envelope — the
# paper's embedded scenario (its whole board draws ~0.76 W; our TPU energy
# model's static floor is 60 W, so the cap below is the analogous "fit the
# budget" constraint, not the paper's number).  Reuses the sweep above
# (payload=) instead of re-measuring all points.
session = explore.autotune(
    payload=result,
    objective="gops_per_watt",
    constraints={"total_w": (None, 61.0)},
)
best = session.autotune_summary["best"]
print(f"\n[autotune] deployed point: {best['label']}")
print(f"[autotune] {best['metrics']['samples_per_s']:,.0f} samples/s, "
      f"{best['metrics']['gops_per_watt']:.4f} GOP/s/W "
      f"(paper's FPGA point: 32,873 samples/s, 11.89 GOP/s/W)")
print(f"[autotune] session ready: {session!r}")
