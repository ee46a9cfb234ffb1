"""The control of the correctness check, on the chip, at a cell's size.

    python3 benchmarks/chip/control.py --workload pems-saturate \\
        --seeds 11,12,13 --seconds 3

Serves the cell as ``run.py`` does, then holds two things to the run's
checks: what the program served, and the control, the reference with its
weights rounded to 4-bit codes (the int4 step down from the
configuration's int8 codes) put in the program's place.  One JSON line per
seed with both verdicts and the numbers compared, then a summary line: the
program's largest gaps and the control's smallest, and whether the control
came out not correct on every seed.  The benchmark's own runs do not run
this.
"""

from __future__ import annotations

import argparse
import json
import sys

import run as harness

COMPARED = ("max_gap_lsb", "carry_gap_lsb", "carries_held")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    runs = []
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               control=True)
        ctl = res["info"]["control"]
        numbers = [k for k in COMPARED if k in res["checks"]]
        line = {"seed": seed, "correct": res["correct"],
                "control_correct": ctl["correct"],
                "compared": res["checks"]["answered_ok"]["value"],
                **{k: res["checks"][k]["value"] for k in numbers},
                **{f"control_{k}": ctl["checks"][k]["value"]
                   for k in numbers}}
        runs.append(line)
        print(json.dumps(line), flush=True)
    print(json.dumps({
        "workload": args.workload,
        **{f"program_max_{k}": max(r[k] for r in runs) for k in numbers},
        **{f"control_min_{k}": min(r[f"control_{k}"] for r in runs)
           for k in numbers},
        "control_not_correct_on_every_seed": not any(
            r["control_correct"] for r in runs)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
