"""Design-space exploration over the parameterised accelerator.

The paper's claim is not one good configuration but a *parameterised
design*: Table-2 meta-parameters span a space of accelerators, each scored
by throughput (GOP/s), energy efficiency (GOP/s/W) and accuracy.  This
package makes that claim executable — offline and at a serving operating
point:

    from repro import explore

    space = explore.paper_space()            # Table-4 axes as a SearchSpace
    result = explore.sweep(space, iters=5)   # build+measure every point
    front = [p for p in result["points"] if p["pareto"]]

    session = explore.autotune(              # best deployable session
        objective="gops_per_watt",
        constraints={"samples_per_s": (30_000, None)})

    scenario = explore.ServingScenario(streams=8, deadline_ms=5.0)
    session = explore.autotune(              # serving-aware: SLO-constrained
        objective="samples_per_s",           # successive halving over real
        constraint="p99_ms<=5",              # StreamServer runs
        space=space, scenario=scenario)

Layout:

  * ``space``       — :class:`SearchSpace` / :class:`Point` over the
                      Table-2 axes (fxp, hs_method, compute_unit,
                      alu_mode, layer width/depth, serve batch, backend,
                      cell) plus the serving deployment axis
                      (replicas).
  * ``constraints`` — declarative, composable validity rules
                      (node-composition: ``&``/``|``/``~``) pruning
                      structurally infeasible points before measurement.
  * ``measure``     — :func:`evaluate_point` / :func:`sweep`: build each
                      point through ``repro.build``; offline timed loops
                      or real ``ServingScenario`` runs per point
                      (``strategy="halving"`` for successive halving).
  * ``serving_objective`` — :class:`ServingScenario`,
                      :func:`parse_constraint` (SLO strings like
                      ``"p99_ms<=5"``), :func:`serving_plan`.
  * ``halving``     — :func:`successive_halving`: the pure seeded
                      rung-promotion algorithm.
  * ``pareto``      — :func:`dominates` / :func:`pareto_front` /
                      :func:`constrained_pareto_front` (any number of
                      objectives, max/min senses; raises
                      :class:`ExploreError` instead of returning a silent
                      empty front).
  * ``autotune``    — :func:`autotune`: constrained argmax on the feasible
                      Pareto front, returning a quantised ``Accelerator``.

``benchmarks/run.py --sweep`` drives :func:`sweep` into
``BENCH_pareto.json`` (schema v2); ``repro.analysis.report --pareto``
renders that artifact as a markdown table.
"""

from repro.explore.autotune import autotune  # noqa: F401
from repro.explore.constraints import (AllOf, AnyOf,  # noqa: F401
                                       ConstraintNode, InfeasiblePoint, Not,
                                       Rule, backend_supported,
                                       default_constraints,
                                       replicas_fit_devices)
from repro.explore.halving import (rung_schedule,  # noqa: F401
                                   successive_halving)
from repro.explore.measure import (METRIC_KEYS, SCHEMA_VERSION,  # noqa: F401
                                   SERVING_OBJECTIVES, evaluate_point, sweep)
from repro.explore.pareto import (DEFAULT_OBJECTIVES,  # noqa: F401
                                  ExploreError, constrained_pareto_front,
                                  dominates, pareto_front, pareto_indices)
from repro.explore.serving_objective import (SERVING_METRIC_KEYS,  # noqa: F401
                                             SERVING_MINIMISE, SLO, SLOSet,
                                             ServingScenario,
                                             evaluate_serving_point,
                                             parse_constraint, serving_plan)
from repro.explore.space import (AXES, Point, SearchSpace,  # noqa: F401
                                 paper_space, point_from_config, smoke_space)

__all__ = [
    "AXES", "AllOf", "AnyOf", "ConstraintNode", "DEFAULT_OBJECTIVES",
    "ExploreError", "InfeasiblePoint", "METRIC_KEYS", "Not", "Point",
    "Rule", "SCHEMA_VERSION", "SERVING_METRIC_KEYS", "SERVING_MINIMISE",
    "SERVING_OBJECTIVES", "SLO", "SLOSet", "SearchSpace", "ServingScenario",
    "autotune", "backend_supported", "constrained_pareto_front",
    "default_constraints", "dominates",
    "evaluate_point", "evaluate_serving_point", "paper_space",
    "pareto_front", "pareto_indices", "parse_constraint",
    "point_from_config", "replicas_fit_devices", "rung_schedule",
    "serving_plan", "smoke_space", "successive_halving", "sweep",
]
