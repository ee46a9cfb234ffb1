"""Design-space sweep benchmark — the paper's Table-4 comparison as a
search, not a hand-picked pair of configurations.

Entry points:

  * ``write_sweep(out_path, smoke=..., strategy=...)`` — run the sweep
    through ``repro.explore`` and write the ``BENCH_pareto.json`` artifact
    (``--sweep [--smoke]`` in ``benchmarks/run.py``, or this module's own
    CLI: ``python -m benchmarks.bench_pareto --smoke --strategy halving
    --rungs 2 out.json``).  Smoke mode is the deterministic per-cell space
    CI runs on CPU; full mode walks ``explore.paper_space()``.
  * ``strategy="halving"`` switches to the serving-aware search: every
    point is scored by a short real ``StreamServer``/``ClusterServer`` run
    under a pinned ``ServingScenario``, with seeded successive halving
    promoting the top ``1/eta`` per rung on the SLO-constrained objective
    ("max samples/s s.t. p99 within deadline").  The payload is schema v2
    with per-point ``operating_point`` records and the full halving trace
    (checked in CI by ``tools/check_pareto_schema.py``).
  * ``run()`` — the harness-shaped row view of the smoke sweep
    (``name,us_per_call,derived``; Pareto-front members get a ``*pareto``
    name suffix) so the ``pareto`` suite plots on the same trend tooling
    as every other benchmark.
"""

import argparse
import json
import sys


# The pinned serving operating point CI's search-smoke measures under:
# small enough to finish in seconds on forced-host XLA devices, a deadline
# loose enough that wave assembly never times out on a loaded CI runner,
# and an SLO generous enough to stay feasible while still exercising the
# constrained-ranking path.
SMOKE_SCENARIO = dict(streams=4, windows_per_stream=4, deadline_ms=250.0,
                      seed=0, name="smoke-serving")
SMOKE_SLO = "p99_ms<=5000"


def _smoke_space(serving: bool):
    from repro import explore
    if not serving:
        return explore.smoke_space(cell=("lstm", "gru", "rglru"))
    # The serving smoke walks the cell zoo AND the serving axis: a
    # 2-replica point (feasible under forced-host device counts >= 2,
    # pruned as infeasible on a single-device runner).
    return explore.smoke_space(cell=("lstm", "gru", "rglru"),
                               replicas=(1, 2))


def sweep_payload(smoke: bool = False, iters: int = 20, seed: int = 0,
                  strategy: str = "full", eta: int = 2, rungs=None):
    from repro import explore
    log = lambda s: print(s, file=sys.stderr)  # noqa: E731
    if strategy == "halving":
        space = _smoke_space(serving=True) if smoke \
            else explore.paper_space(batch=256)
        scenario = explore.ServingScenario(**SMOKE_SCENARIO) if smoke \
            else explore.ServingScenario(streams=16, windows_per_stream=8,
                                         deadline_ms=10.0, name="paper-serving")
        return explore.sweep(space, scenario=scenario, strategy="halving",
                             objective="samples_per_s", constraint=SMOKE_SLO,
                             eta=eta, rungs=rungs, seed=seed, log=log)
    # The smoke sweep walks the whole cell zoo: 4 deterministic points per
    # cell.  LSTM labels stay suffix-free, so pre-cell-axis artifacts and
    # trend lines keep their names; gru/rglru points land on the xla
    # backend (no fused kernel) and are labelled "<base>_<cell>".
    space = explore.smoke_space(cell=("lstm", "gru", "rglru")) if smoke \
        else explore.paper_space(batch=256)
    # 3-objective front: the paper's GOP/s + GOP/s/W pair plus quantisation
    # fidelity, so the wide (8,16) baseline format earns its place on the
    # front through accuracy rather than vanishing behind (4,8)'s speed.
    objectives = dict(explore.DEFAULT_OBJECTIVES, int_float_mse="min")
    return explore.sweep(space, iters=iters, seed=seed, objectives=objectives,
                         log=log)


def write_sweep(out_path: str = "BENCH_pareto.json", smoke: bool = False,
                iters: int = 20, seed: int = 0, strategy: str = "full",
                eta: int = 2, rungs=None) -> dict:
    payload = sweep_payload(smoke=smoke, iters=iters, seed=seed,
                            strategy=strategy, eta=eta, rungs=rungs)
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in payload["points"])
    print(f"[sweep] wrote {len(payload['points'])} points ({n_ok} ok, "
          f"{len(payload['front'])} on the Pareto front) to {out_path}",
          file=sys.stderr)
    if payload.get("front_reason"):
        print(f"[sweep] empty front: {payload['front_reason']}",
              file=sys.stderr)
    return payload


def _rows(payload):
    serving = payload.get("scenario") is not None
    rows = []
    for r in payload["points"]:
        if r["status"] != "ok":
            rows.append((f"pareto_{r['label']}_{r['status']}", 0.0, 0))
            continue
        m = r["metrics"]
        name = f"pareto_{r['label']}" + ("*pareto" if r["pareto"] else "")
        if serving:
            # Serving rows have no per-wave closed-loop time; report tail
            # latency as the time column and achieved rate as derived.
            rows.append((name, round(m["p99_ms"] * 1e3, 2),
                         round(m["samples_per_s"], 1)))
        else:
            rows.append((name, round(m["us_per_wave"], 2),
                         round(m["gops_per_watt"], 4)))
    return rows


def run():
    return _rows(sweep_payload(smoke=True, iters=5))


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="design-space sweep -> BENCH_pareto.json (schema v2)")
    ap.add_argument("out", nargs="?", default="BENCH_pareto.json")
    ap.add_argument("--smoke", action="store_true",
                    help="deterministic CPU-sized space")
    ap.add_argument("--strategy", choices=("full", "halving"),
                    default="full",
                    help="halving = serving-aware successive halving")
    ap.add_argument("--rungs", type=int, default=None)
    ap.add_argument("--eta", type=int, default=2)
    ap.add_argument("--iters", type=int, default=None,
                    help="offline timing iterations (default 5 smoke / 20)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    iters = args.iters if args.iters is not None else (5 if args.smoke
                                                       else 20)
    payload = write_sweep(args.out, smoke=args.smoke, iters=iters,
                          seed=args.seed, strategy=args.strategy,
                          eta=args.eta, rungs=args.rungs)
    print("name,us_per_call,derived")
    for n, us, d in _rows(payload):
        print(f"{n},{us:.2f},{d}")


if __name__ == "__main__":
    main()
