"""Benchmark harness — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [table1 table3 table4 fig45 cells pareto serving]
  PYTHONPATH=src python -m benchmarks.run --smoke [out.json]
  PYTHONPATH=src python -m benchmarks.run --sweep [--smoke] \
      [--strategy=halving --rungs=2 --eta=2] [out.json]
  PYTHONPATH=src python -m benchmarks.bench_serving [--smoke] [out.json]

Prints ``name,us_per_call,derived`` CSV (one row per measurement).

``--smoke`` runs a fast subset — the analytical accelerator-grid cells
plus one timed int-datapath measurement per backend through the session
API — and writes it to ``BENCH_smoke.json`` (override with a positional
path) so CI records the perf trajectory.

``--sweep`` runs the design-space exploration (``repro.explore`` over the
Table-4 space; ``--smoke`` restricts it to the deterministic 4-point CPU
space) and writes the scored points + Pareto front to ``BENCH_pareto.json``
(override with a positional path).  ``--strategy=halving`` switches to the
serving-aware successive-halving search (each point scored by a short real
server run; schema v2 with per-point operating points).  Render either
with ``python -m repro.analysis.report --pareto BENCH_pareto.json``.
"""

import json
import sys
import time


def _smoke_rows():
    """Cheap, deterministic-shape rows: plan/energy grid + one timed call
    per backend (small batch so CPU interpret mode stays fast)."""
    import jax
    import repro
    from benchmarks import bench_cells

    rows = list(bench_cells._lstm_grid_rows())

    sess = repro.build().quantize()
    x = jax.random.normal(jax.random.key(0), (32, 6, 1)) * 0.5
    for backend in ("ref", "pallas", "xla"):
        fn = sess.compiled("int", backend)
        fn(x).block_until_ready()           # compile outside the clock
        t0 = time.perf_counter()
        iters = 5
        for _ in range(iters):
            out = fn(x)
        out.block_until_ready()
        us = (time.perf_counter() - t0) / iters * 1e6
        rows.append((f"smoke_int_b32_{backend}", round(us, 2), 32))
    return rows


def smoke(out_path: str = "BENCH_smoke.json") -> None:
    rows = _smoke_rows()
    payload = {
        "suite": "smoke",
        "rows": [{"name": n, "us_per_call": us, "derived": d}
                 for n, us, d in rows],
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=1)
    print("name,us_per_call,derived")
    for n, us, d in rows:
        print(f"{n},{us:.2f},{d}")
    print(f"[smoke] wrote {len(rows)} rows to {out_path}", file=sys.stderr)


def sweep(argv) -> None:
    from benchmarks import bench_pareto
    smoke_mode = "--smoke" in argv
    opts = dict(a[2:].split("=", 1) for a in argv
                if a.startswith("--") and "=" in a)
    paths = [a for a in argv if not a.startswith("--")]
    payload = bench_pareto.write_sweep(paths[0] if paths
                                       else "BENCH_pareto.json",
                                       smoke=smoke_mode,
                                       iters=5 if smoke_mode else 20,
                                       strategy=opts.get("strategy", "full"),
                                       eta=int(opts.get("eta", 2)),
                                       rungs=(int(opts["rungs"])
                                              if "rungs" in opts else None))
    print("name,us_per_call,derived")
    for n, us, d in bench_pareto._rows(payload):
        print(f"{n},{us:.2f},{d}")


def main() -> None:
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    argv = sys.argv[1:]
    if argv and argv[0] == "--smoke":
        smoke(*argv[1:2])
        return
    if argv and argv[0] == "--sweep":
        sweep(argv[1:])
        return
    from benchmarks import (bench_activations, bench_cells, bench_energy,
                            bench_pareto, bench_resources, bench_serving,
                            bench_throughput)
    suites = {
        "table1": bench_activations.run,
        "table3": bench_throughput.run,
        "table4": bench_energy.run,
        "fig45": bench_resources.run,
        "cells": bench_cells.run,
        "pareto": bench_pareto.run,
        "serving": bench_serving.run,
    }
    want = argv or list(suites)
    print("name,us_per_call,derived")
    for key in want:
        for name, us, derived in suites[key]():
            print(f"{name},{us:.2f},{derived}")


if __name__ == "__main__":
    main()
