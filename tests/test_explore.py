"""Design-space explorer: Pareto dominance, the sweep artifact schema, and
constrained autotune.

The sweep tests run the same deterministic 4-point smoke space CI sweeps
(``benchmarks/run.py --sweep --smoke``) and assert the ``BENCH_pareto.json``
schema plus dominance-correctness of the extracted front — timing values
themselves are machine-dependent and never asserted."""

import json
import math

import pytest

from repro import explore
from repro.core.fixed_point import FXP_4_8, FXP_8_16, FixedPointConfig
from repro.core.qlstm import QLSTMConfig

# ---------------------------------------------------------------------------
# Pareto dominance / front extraction (pure, no jax)
# ---------------------------------------------------------------------------

MAXMIN = {"gops": "max", "mse": "min"}


def test_dominates_basic_and_senses():
    a = {"gops": 2.0, "mse": 0.1}
    b = {"gops": 1.0, "mse": 0.2}
    assert explore.dominates(a, b, MAXMIN)
    assert not explore.dominates(b, a, MAXMIN)
    # better on one axis, worse on the other: neither dominates
    c = {"gops": 1.0, "mse": 0.05}
    assert not explore.dominates(a, c, MAXMIN)
    assert not explore.dominates(c, a, MAXMIN)


def test_dominates_ties():
    a = {"gops": 2.0, "mse": 0.1}
    same = dict(a)
    assert not explore.dominates(a, same, MAXMIN)
    assert not explore.dominates(same, a, MAXMIN)
    # equal on one objective, strictly better on the other: dominates
    better = {"gops": 2.0, "mse": 0.05}
    assert explore.dominates(better, a, MAXMIN)
    assert not explore.dominates(a, better, MAXMIN)


def test_pareto_front_hand_built_2d():
    pts = [
        {"gops": 3.0, "mse": 0.3},   # front
        {"gops": 2.0, "mse": 0.1},   # front
        {"gops": 1.0, "mse": 0.2},   # dominated by the one above
        {"gops": 3.0, "mse": 0.3},   # duplicate of a front point: kept
        {"gops": 0.5, "mse": 0.4},   # dominated by everything
    ]
    idx = explore.pareto_indices(pts, MAXMIN)
    assert idx == [0, 1, 3]
    assert explore.pareto_front(pts, MAXMIN) == [pts[0], pts[1], pts[3]]


def test_pareto_front_three_objectives():
    obj = {"gops": "max", "gops_w": "max", "mse": "min"}
    pts = [
        {"gops": 3.0, "gops_w": 1.0, "mse": 0.30},  # fastest
        {"gops": 1.0, "gops_w": 3.0, "mse": 0.30},  # most efficient
        {"gops": 1.0, "gops_w": 1.0, "mse": 0.01},  # most accurate
        {"gops": 1.0, "gops_w": 1.0, "mse": 0.30},  # dominated by all three
    ]
    assert explore.pareto_indices(pts, obj) == [0, 1, 2]
    # dropping the accuracy objective collapses the accurate point too
    assert explore.pareto_indices(pts, {"gops": "max", "gops_w": "max"}) \
        == [0, 1]


def test_pareto_front_excludes_non_finite():
    pts = [
        {"gops": float("nan"), "mse": 0.0},   # failed measurement
        {"gops": float("inf"), "mse": 0.1},   # bogus timer
        {"gops": 1.0, "mse": 0.2},
    ]
    assert explore.pareto_indices(pts, MAXMIN) == [2]


def test_dominates_rejects_bad_sense():
    with pytest.raises(ValueError, match="sense"):
        explore.dominates({"g": 1}, {"g": 2}, {"g": "maximize"})


# ---------------------------------------------------------------------------
# SearchSpace
# ---------------------------------------------------------------------------

def test_search_space_size_grid_and_sample():
    s = explore.SearchSpace(fxp=(FXP_4_8, FXP_8_16),
                            alu_mode=("pipelined", "per_step"),
                            hidden_size=(8, 20))
    assert s.size == 8
    grid = list(s.grid())
    assert len(grid) == 8 and len({p.label for p in grid}) == 8
    sampled = s.sample(3, seed=0)
    assert len(sampled) == 3 and len(set(sampled)) == 3
    assert s.sample(3, seed=0) == sampled          # deterministic
    assert set(s.sample(99, seed=1)) == set(grid)  # n >= size: whole grid
    # singletons auto-wrap
    assert explore.SearchSpace(hidden_size=16).hidden_size == (16,)


def test_search_space_validation():
    with pytest.raises(ValueError, match="hs_method"):
        explore.SearchSpace(hs_method=("bogus",))
    with pytest.raises(ValueError, match="no choices"):
        explore.SearchSpace(batch=())
    with pytest.raises(ValueError, match="positive ints"):
        explore.SearchSpace(hidden_size=(0,))


def test_point_configs_and_roundtrip():
    p = next(iter(explore.SearchSpace(fxp=FXP_8_16, alu_mode="per_step",
                                      hidden_size=12, batch=7).grid()))
    base = QLSTMConfig(input_size=3, seq_len=9)
    model, accel = p.configs(base)
    assert model.hidden_size == 12 and model.input_size == 3 \
        and model.seq_len == 9
    assert accel.fxp == FXP_8_16 and accel.alu_mode == "per_step"
    from repro.explore.space import point_from_config
    assert point_from_config(p.asdict()) == p
    assert isinstance(point_from_config(p.asdict()).fxp, FixedPointConfig)


# ---------------------------------------------------------------------------
# The smoke sweep: schema + dominance correctness (the CI artifact)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_payload(tmp_path_factory):
    """One shared ``--sweep --smoke`` run, through the benchmark writer so
    the on-disk artifact is what gets schema-checked."""
    from benchmarks.bench_pareto import write_sweep
    out = tmp_path_factory.mktemp("sweep") / "BENCH_pareto.json"
    write_sweep(str(out), smoke=True, iters=2)
    with open(out) as f:
        return json.load(f)


def test_smoke_sweep_schema(smoke_payload):
    p = smoke_payload
    assert p["suite"] == "pareto"
    assert p["schema_version"] == explore.SCHEMA_VERSION
    assert p["mode"] == "grid"
    assert isinstance(p["seed"], int)
    assert set(p["space"]) == set(explore.AXES)
    assert all(v in ("max", "min") for v in p["objectives"].values())
    assert len(p["points"]) >= 4
    for r in p["points"]:
        assert set(r) >= {"label", "config", "status", "pareto"}
        assert set(r["config"]) == set(explore.AXES)
        if r["status"] == "ok":
            assert set(r["metrics"]) >= {
                "us_per_wave", "samples_per_s", "throughput_gops",
                "gops_per_watt", "total_w", "int_float_mse",
                "int_float_max_abs", "weight_bytes"}
            assert r["plan"]["backend"] in ("ref", "pallas", "xla")
            assert all(math.isfinite(v) for v in r["metrics"].values()
                       if isinstance(v, float))


def test_smoke_sweep_front_dominance_correct(smoke_payload):
    p = smoke_payload
    ok = [r for r in p["points"] if r["status"] == "ok"]
    assert len(ok) >= 4
    front = [r for r in ok if r["pareto"]]
    assert front and sorted(p["front"]) == sorted(r["label"] for r in front)
    obj = p["objectives"]
    for r in front:                      # nothing dominates a front point
        assert not any(explore.dominates(o["metrics"], r["metrics"], obj)
                       for o in ok)
    for r in ok:                         # every non-front point is dominated
        if not r["pareto"]:
            assert any(explore.dominates(f["metrics"], r["metrics"], obj)
                       for f in front), r["label"]


def test_sweep_records_unsupported_backend_instead_of_raising():
    # per-step ALU is exactly what the fused engines refuse: explicit
    # backend=pallas must surface as an 'unsupported' row, not an exception
    space = explore.SearchSpace(alu_mode="per_step", backend="pallas",
                                batch=4)
    payload = explore.sweep(space, iters=1)
    (row,) = payload["points"]
    assert row["status"] == "unsupported" and "pallas" in row["reason"]
    assert payload["front"] == [] and row["pareto"] is False


def test_sweep_respects_base_model_and_eval_x():
    import numpy as np
    base = QLSTMConfig(input_size=2, seq_len=4)
    space = explore.SearchSpace(backend="ref", batch=4, hidden_size=8)
    x = np.zeros((3, 4, 2), np.float32)
    payload = explore.sweep(space, base, iters=1, eval_x=x)
    (row,) = payload["points"]
    assert row["status"] == "ok"
    with pytest.raises(ValueError, match="windows"):
        explore.sweep(space, base, iters=1,
                      eval_x=np.zeros((3, 6, 1), np.float32))


# ---------------------------------------------------------------------------
# autotune: constrained argmax on the feasible front (ref backend)
# ---------------------------------------------------------------------------

def test_autotune_constraint_satisfaction_ref_backend():
    import repro
    space = explore.SearchSpace(fxp=(FXP_4_8, FXP_8_16), backend="ref",
                                batch=8)
    # (8,16) is ~256x more accurate; bound int_float_mse so only it is
    # feasible regardless of which format happens to measure faster.
    session = explore.autotune(
        space=space, iters=2,
        constraints={"int_float_mse": (None, 1e-4)})
    assert isinstance(session, repro.Accelerator)
    assert session.accel.fxp == FXP_8_16
    assert session.plan["backend"] == "ref"
    assert session.qparams is not None     # ready to infer/serve

    s = session.autotune_summary
    assert s["best"]["label"] in s["front"]
    assert s["best"]["metrics"]["int_float_mse"] <= 1e-4
    # the winner maximises the objective over the feasible front
    feasible = [r for r in s["sweep"]["points"]
                if r["status"] == "ok"
                and r["metrics"]["int_float_mse"] <= 1e-4]
    best_val = max(r["metrics"]["gops_per_watt"] for r in feasible)
    assert s["best"]["metrics"]["gops_per_watt"] == best_val

    # and the built session actually runs the winning configuration
    import jax
    y = session.infer(jax.random.normal(jax.random.key(0), (4, 6, 1)),
                      path="int")
    assert y.shape == (4, 1)


def test_autotune_infeasible_constraints_raise():
    space = explore.SearchSpace(backend="ref", batch=4)
    with pytest.raises(ValueError, match="no feasible point"):
        explore.autotune(space=space, iters=1,
                         constraints={"samples_per_s": (1e18, None)})


def test_autotune_reuses_payload_without_resweeping():
    import jax
    import repro
    from repro.explore.space import point_from_config

    space = explore.SearchSpace(fxp=(FXP_4_8, FXP_8_16), backend="ref",
                                batch=8)
    # Seed 1: under JAX's partitionable threefry (the default since 0.5)
    # seed 3's (4,8) weights happen to quantise with the lower error.
    payload = explore.sweep(space, iters=2, seed=1)
    assert payload["seed"] == 1
    calls = []
    session = explore.autotune(payload=payload, objective="int_float_mse",
                               log=calls.append)
    # objective is cost-like -> minimised -> the (8,16) point wins
    assert session.accel.fxp == FXP_8_16
    assert session.autotune_summary["sense"] == "min"
    assert not any("/2]" in c for c in calls)   # no sweep progress lines
    # rebuilt with the PAYLOAD's seed: the deployed weights are the ones
    # the stored metrics were measured on
    cfgs = point_from_config(session.autotune_summary["best"]["config"])
    want = repro.build(*cfgs.configs(), seed=1).params
    assert all(bool((a == b).all()) for a, b in
               zip(jax.tree.leaves(session.params), jax.tree.leaves(want)))


def test_sweep_and_autotune_validate_metric_names_upfront():
    space = explore.SearchSpace(backend="ref", batch=4)
    with pytest.raises(ValueError, match="unknown objective.*gops_per_wat"):
        explore.sweep(space, objectives={"gops_per_wat": "max"})
    with pytest.raises(ValueError, match="sense"):
        explore.sweep(space, objectives={"gops_per_watt": "maximize"})
    with pytest.raises(ValueError, match="unknown objective"):
        explore.autotune(space=space, objective="latency")
    with pytest.raises(ValueError, match="unknown constraint"):
        explore.autotune(space=space, constraints={"watts": (None, 1.0)})


def test_sweep_base_accel_is_honoured():
    from repro.core.accelerator import AcceleratorConfig
    space = explore.SearchSpace(backend="ref", batch=4)
    payload = explore.sweep(space, None, AcceleratorConfig(ht_max=0.5),
                            iters=1)
    (row,) = payload["points"]
    assert row["status"] == "ok"
    session = explore.autotune(space=space, iters=1,
                               accel=AcceleratorConfig(ht_max=0.5))
    assert session.model.acts.ht_max == 0.5

# ---------------------------------------------------------------------------
# ExploreError: empty/eliminated fronts fail loudly, naming the eliminator
# ---------------------------------------------------------------------------

def test_pareto_front_of_nothing_raises_explore_error():
    with pytest.raises(explore.ExploreError, match="0 measurements"):
        explore.pareto_front([], MAXMIN)
    assert issubclass(explore.ExploreError, ValueError)   # old catches work


def test_pareto_front_all_non_finite_raises_explore_error():
    pts = [{"gops": float("nan"), "mse": 0.1},
           {"gops": float("inf"), "mse": 0.2}]
    with pytest.raises(explore.ExploreError, match="non-finite"):
        explore.pareto_indices(pts, MAXMIN)


def test_dominates_missing_metric_names_it():
    with pytest.raises(explore.ExploreError, match="mse"):
        explore.dominates({"gops": 3.0}, {"gops": 2.0, "mse": 0.1}, MAXMIN)


def test_constrained_front_raises_naming_the_constraint():
    slo = explore.parse_constraint("p99_ms<=5")
    pts = [{"samples_per_s": 10.0, "p99_ms": 9.0},
           {"samples_per_s": 99.0, "p99_ms": 6.0}]
    with pytest.raises(explore.ExploreError, match=r"p99_ms<=5"):
        explore.constrained_pareto_front(
            pts, {"samples_per_s": "max"}, constraint=slo)
    # the closest miss is named by magnitude (6 - 5 = 1)
    try:
        explore.constrained_pareto_front(
            pts, {"samples_per_s": "max"}, constraint=slo)
    except explore.ExploreError as e:
        assert "1" in str(e)


def test_constrained_front_filters_violators_keeps_feasible():
    slo = explore.parse_constraint("p99_ms<=5")
    pts = [{"samples_per_s": 10.0, "p99_ms": 4.0},
           {"samples_per_s": 99.0, "p99_ms": 6.0},   # fastest but violating
           {"samples_per_s": 5.0, "p99_ms": 1.0}]
    front = explore.constrained_pareto_front(
        pts, {"samples_per_s": "max", "p99_ms": "min"}, constraint=slo)
    assert pts[1] not in front
    assert pts[0] in front and pts[2] in front


# ---------------------------------------------------------------------------
# SLO parsing
# ---------------------------------------------------------------------------

def test_slo_parse_ok_violation_roundtrip():
    slo = explore.parse_constraint("p99_ms<=5")
    assert slo.ok({"p99_ms": 5.0}) and not slo.ok({"p99_ms": 5.01})
    assert slo.violation({"p99_ms": 7.5}) == 2.5
    assert slo.violation({"p99_ms": 2.0}) == 0.0
    assert slo.violation({}) == float("inf")
    assert explore.parse_constraint(slo.describe()) == slo
    multi = explore.parse_constraint("p99_ms<=5,samples_per_s>=100")
    assert multi.ok({"p99_ms": 4.0, "samples_per_s": 200.0})
    assert not multi.ok({"p99_ms": 4.0, "samples_per_s": 50.0})
    assert multi.violation({"p99_ms": 6.0, "samples_per_s": 50.0}) == 51.0


def test_slo_parse_rejects_garbage():
    with pytest.raises(ValueError, match="cannot parse"):
        explore.parse_constraint("p99_ms ~ 5")
    with pytest.raises(ValueError, match="unknown SLO metric"):
        explore.parse_constraint("p99<=5")
    with pytest.raises(ValueError, match="empty"):
        explore.parse_constraint(" , ")


# ---------------------------------------------------------------------------
# hypothesis property: the constrained front never admits an SLO violator
# while a feasible point exists
# ---------------------------------------------------------------------------

from tests.hypothesis_compat import given, settings, st  # noqa: E402

metrics_strategy = st.lists(
    st.fixed_dictionaries({
        "samples_per_s": st.floats(1.0, 1e6, allow_nan=False),
        "p99_ms": st.floats(0.01, 100.0, allow_nan=False),
    }), min_size=1, max_size=12)


@pytest.mark.property
@settings(max_examples=60, deadline=None)
@given(pts=metrics_strategy, bound=st.floats(0.01, 100.0, allow_nan=False))
def test_property_constrained_front_respects_slo(pts, bound):
    slo = explore.SLO("p99_ms", "<=", bound)
    feasible_exists = any(slo.ok(p) for p in pts)
    objectives = {"samples_per_s": "max", "p99_ms": "min"}
    if not feasible_exists:
        with pytest.raises(explore.ExploreError):
            explore.constrained_pareto_front(pts, objectives, constraint=slo)
        return
    front = explore.constrained_pareto_front(pts, objectives, constraint=slo)
    assert front
    for p in front:
        assert slo.ok(p), "front admitted an SLO violator"
    # and no feasible point dominates a front member
    feas = [p for p in pts if slo.ok(p)]
    for f in front:
        assert not any(explore.dominates(o, f, objectives) for o in feas)


# ---------------------------------------------------------------------------
# serving axes: declarative prune agrees with the imperative serving plan
# ---------------------------------------------------------------------------

def test_space_gains_serving_axes_and_labels():
    assert explore.AXES[-1] == "replicas"
    sp = explore.SearchSpace(backend="xla", batch=4, replicas=(1, 2),
                             cell=("lstm", "gru"))
    labels = {p.label for p in sp.grid()}
    assert len(labels) == 4
    assert any(lab.endswith("_gru_r2") for lab in labels)
    base = next(iter(explore.SearchSpace(backend="xla", batch=4).grid()))
    assert "_r" not in base.label and base.label.endswith("_xla")
    from repro.explore.space import point_from_config
    for p in sp.grid():
        assert point_from_config(p.asdict()) == p
    import dataclasses
    assert tuple(f.name for f in dataclasses.fields(explore.SearchSpace)) \
        == explore.AXES + ("constraints",)   # no axis beyond AXES
    with pytest.raises(ValueError, match="positive ints"):
        explore.SearchSpace(replicas=(0,))


def test_prune_and_serving_plan_agree_across_the_axes():
    """The declarative constraint tree and the imperative serving_plan are
    two forms of one contract: a point prunes iff its plan raises, with
    matching rule names."""
    import itertools
    from repro.explore.constraints import InfeasiblePoint
    from repro.explore.serving_objective import serving_plan
    from repro.explore.space import Point

    sp = explore.SearchSpace(backend=("auto", "ref", "xla", "pallas"),
                             batch=4, hidden_size=8,
                             cell=("lstm", "gru", "rglru"),
                             replicas=(1, 3),
                             alu_mode=("pipelined", "per_step"))
    checked = 0
    for p in sp.grid():
        reason = sp.feasible(p)
        try:
            pl = serving_plan(p)
            planned = None
        except InfeasiblePoint as e:
            planned = str(e)
        if reason is None:
            assert planned is None, (p.label, planned)
            assert pl["replicas"] == p.replicas
            assert pl["stateful_backend"] in ("ref", "xla", "pallas")
        else:
            assert planned is not None, (p.label, reason)
            # same rule fired, modulo the declarative/imperative prefix
            decl = reason.split(":", 1)[0]
            imp = planned.split(":", 1)[0]
            assert {("backend_supported", "backend"),
                    ("replicas_fit_devices", "replicas")} >= {(decl, imp)} \
                or decl.startswith(imp) or imp in decl, (decl, imp)
        checked += 1
    assert checked == sp.size == 4 * 3 * 2 * 2


def test_constraint_node_composition_operators():
    from repro.explore.constraints import AllOf, AnyOf, Not, Rule

    yes = Rule("yes", lambda *a: None)
    no = Rule("no", lambda *a: "bad value")
    assert (yes & no).check(None, None, None) == "no: bad value"
    assert (yes | no).check(None, None, None) is None
    assert (~yes).check(None, None, None) == \
        "~yes: point satisfies the negated rule"
    assert (~no).check(None, None, None) is None
    both = AllOf((yes, AnyOf((no, yes))))
    assert both.check(None, None, None) is None
    assert "no" in AnyOf((no, no)).check(None, None, None)


def test_sweep_all_infeasible_records_front_reason_no_builds():
    # the fused pallas engine on a cell with no fused kernel: every point
    # pruned before measurement; the sweep reports WHY the front is empty.
    space = explore.SearchSpace(backend="pallas", batch=4, cell="gru")
    payload = explore.sweep(space, scenario=explore.ServingScenario(
        streams=2, windows_per_stream=1), strategy="full")
    (row,) = payload["points"]
    assert row["status"] == "unsupported"
    assert row["reason"].startswith("backend_supported:")
    assert "no fused kernel" in row["reason"]
    assert payload["front"] == []
    assert payload["front_reason"] is not None
    assert "0 of 1 points" in payload["front_reason"]


def test_halving_without_scenario_is_rejected():
    space = explore.SearchSpace(backend="ref", batch=4)
    with pytest.raises(ValueError, match="halving"):
        explore.sweep(space, strategy="halving")
    with pytest.raises(ValueError, match="SLO"):
        explore.sweep(space, constraint="p99_ms<=5")


# ---------------------------------------------------------------------------
# live serving-aware search: schema v2, SLO satisfaction, determinism
# (a tiny 2-point space so the battery stays tier-1 fast)
# ---------------------------------------------------------------------------

SERVING_SLO = "p99_ms<=60000"          # generous: CI runners are slow


@pytest.fixture(scope="module")
def halving_payload():
    """One shared serving halving sweep over a 2-point space whose ranking
    is robust (batch 1 vs 16 differ by an order of magnitude)."""
    space = explore.SearchSpace(backend="xla", batch=(1, 16), hidden_size=8,
                                num_layers=1)
    scenario = explore.ServingScenario(streams=3, windows_per_stream=3,
                                       deadline_ms=60000.0, name="t")
    return explore.sweep(space, scenario=scenario, strategy="halving",
                         objective="samples_per_s", constraint=SERVING_SLO,
                         eta=2, seed=0)


def test_serving_sweep_schema_v2(halving_payload):
    p = halving_payload
    assert p["schema_version"] == 2
    assert p["strategy"] == "halving"
    assert p["constraint"] == "p99_ms<=60000"
    assert p["scenario"]["streams"] == 3
    assert p["objective"] == "samples_per_s"
    tr = p["halving"]
    assert tr["sizes"] == [2, 1]
    assert tr["fractions"] == [0.5, 1.0]
    assert tr["total_measurements"] == 3 <= tr["budget_bound"]
    assert len(tr["rungs"]) == 2
    for r in p["points"]:
        assert r["status"] == "ok"
        m = r["metrics"]
        assert set(m) == set(explore.SERVING_METRIC_KEYS)
        op = r["operating_point"]
        assert set(op) >= {"scenario", "rung", "fraction", "final",
                           "p99_ms", "deadline_miss_rate", "feasible"}
        assert op["p99_ms"] == m["p99_ms"]
    finals = [r for r in p["points"] if r["operating_point"]["final"]]
    assert len(finals) == 1            # only the rung-1 survivor is final
    assert finals[0]["operating_point"]["fraction"] == 1.0
    # non-final rows were measured on the truncated scenario
    truncated = [r for r in p["points"] if not r["operating_point"]["final"]]
    assert truncated and all(
        r["operating_point"]["scenario"]["windows_per_stream"] == 2
        for r in truncated)
    # the front only ever contains final-rung points
    assert set(p["front"]) <= {r["label"] for r in finals}


def test_serving_autotune_satisfies_slo_on_remeasure(halving_payload):
    import repro
    session = explore.autotune(payload=halving_payload,
                               objective="samples_per_s",
                               constraint=SERVING_SLO)
    assert isinstance(session, repro.Accelerator)
    s = session.autotune_summary
    assert s["strategy"] == "halving"
    assert s["constraint"] == "p99_ms<=60000"
    assert s["operating_point"]["final"] is True
    assert s["operating_point"]["feasible"] is True
    assert s["halving"]["winner_label"] == s["best"]["label"]
    # re-measure the winner at the recorded operating point: the deployed
    # session must satisfy the SLO it was selected under
    scenario = explore.ServingScenario.from_dict(halving_payload["scenario"])
    remeasured = session.measure_scenario(scenario)
    slo = explore.parse_constraint(SERVING_SLO)
    assert slo.ok(remeasured), remeasured


def test_serving_autotune_impossible_slo_names_it(halving_payload):
    with pytest.raises(explore.ExploreError,
                       match=r"no feasible point.*p99_ms<=0.0001"):
        explore.autotune(payload=halving_payload,
                         constraint="p99_ms<=0.0001")


def test_serving_halving_same_seed_identical_traces(halving_payload):
    """The acceptance property: a second same-seed sweep reproduces the
    rung-promotion trace and picks the same config."""
    space = explore.SearchSpace(backend="xla", batch=(1, 16), hidden_size=8,
                                num_layers=1)
    scenario = explore.ServingScenario(streams=3, windows_per_stream=3,
                                       deadline_ms=60000.0, name="t")
    p2 = explore.sweep(space, scenario=scenario, strategy="halving",
                       objective="samples_per_s", constraint=SERVING_SLO,
                       eta=2, seed=0)
    strip = lambda tr: [(r["rung"], r["fraction"], r["measured"],  # noqa: E731
                         r["promoted"]) for r in tr["rungs"]]
    assert strip(p2["halving"]) == strip(halving_payload["halving"])
    assert p2["halving"]["winner_label"] == \
        halving_payload["halving"]["winner_label"]
    assert p2["front"] == halving_payload["front"]


def test_measure_scenario_session_api():
    import repro
    sess = repro.build(QLSTMConfig(hidden_size=8),
                       seed=0).quantize()
    sc = explore.ServingScenario(streams=2, windows_per_stream=2,
                                 deadline_ms=60000.0)
    m = sess.measure_scenario(sc)
    assert set(m) == set(explore.SERVING_METRIC_KEYS)
    assert m["samples_per_s"] > 0
    assert m["waves"] >= 1
