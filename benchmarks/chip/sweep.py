"""Knee sweep: one cell's traffic mix at several offered rates, in one
process on one chip, without the reference check.

    python3 benchmarks/chip/sweep.py --workload pems-steady \\
        --rates 8000,12000,16000 --seconds 5 --seed 1 [--streams 3600000]

For each rate it prints one JSON line: the offered rate, the windows
answered per second of the window, the share of the windows due in the
window that were answered without error, their latency p50 and p99 (due
time to the client's ``poll``), and the generator's lag p99.  The knee is
the highest rate at which at least 99% of the windows due are answered
and the latency p99 is at most ``--limit-ms`` (100 ms: the server's 10-ms
flush deadline plus about four waves of a state table that fills the
chip); the last line names it.  The chosen rates are then
written as numbers into the traffic files.  ``--streams`` replaces the
mix's stream count (and ``max_streams``) to try another population.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import numpy as np

import run as harness
from metrics import (_latency, gen_lag_p99_ms, wave_exec_ms, wave_fill,
                     windows_per_s)


def point(workload: str, rate: float, seed: int, seconds: float,
          streams=None) -> dict:
    over = {"rate_per_s": rate}
    if streams:
        over.update(streams=streams, serving={"max_streams": streams})
    served = harness.serve_cell(workload, seed, seconds, trace=False,
                                mix_overrides=over)
    rec = served["rec"]
    lo, hi = rec.due_range()
    lat = _latency.latencies_ms(rec)
    return {"rate_per_s": rate,
            "streams": rec.traffic.streams,
            "windows_per_s": windows_per_s.read(rec),
            "answered_share": float((rec.status[lo:hi] == 1).mean()),
            "latency_p50_ms": float(np.percentile(lat, 50)),
            "latency_p99_ms": float(np.percentile(lat, 99)),
            "gen_lag_p99_ms": gen_lag_p99_ms.read(rec),
            "windows_due": int(hi - lo),
            "wave_fill": wave_fill.read(rec),
            "wave_exec_ms": wave_exec_ms.read(rec),
            "memory_peak_bytes": max(served["peaks_mem"], default=0),
            "setup_s": rec.setup_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, windows/s")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--limit-ms", type=float, default=100.0)
    ap.add_argument("--streams", type=int, default=None)
    args = ap.parse_args(argv)
    knee = None
    for rate in (float(r) for r in args.rates.split(",")):
        p = point(args.workload, rate, args.seed, args.seconds, args.streams)
        gc.collect()              # free the last server's state table
        p["meets"] = (p["answered_share"] >= 0.99
                      and p["latency_p99_ms"] <= args.limit_ms)
        print(json.dumps(p), flush=True)
        if p["meets"]:
            knee = rate if knee is None else max(knee, rate)
    print(json.dumps({"workload": args.workload, "knee_per_s": knee}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
