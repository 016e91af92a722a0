"""Readings that set a cell's correctness limit and its offered load, on
the chip.

    python3 bench/control.py --workload <name> --seeds 11 12 13 ... \
        --seconds 10 [--controls int8,fp8] [--rates 1 2 3 [--paired]] \
        [--out chiprun_out/x.json]

For each rate (default: the cell's own) and seed, or with ``--paired``
each rate with its own seed, in one process (the programs compile once):
set up, run the cell's load for ``--seconds``, then read the widest logit
gap of the served tokens against the float32 reference (the sound
reading) and, at the same positions, the widest gap of each
lower-precision control's first choice (the reading a precision cut would
give), beside the run's end-to-end numbers.  A sweep over ``--rates``
finds the highest rate the cell sustains.  The benchmark's own runs never
run this.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)

import run  # noqa: E402,F401  (the TPU runtime flags the runs use)
from harness.main import devices_for, log, run_once, start_jax  # noqa: E402
from harness.spec import load_cell  # noqa: E402


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default="int8,fp8")
    ap.add_argument("--rates", type=float, nargs="*", default=None)
    ap.add_argument("--paired", action="store_true",
                    help="the i-th rate with the i-th seed")
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()
    cell = load_cell(args.workload)
    devs = devices_for(cell.chips, args.cpu_rehearsal)
    clock = start_jax()
    controls = tuple(c for c in args.controls.split(",") if c)
    rows = []
    rates = args.rates or [cell.settings["load"]["rate"]]
    if args.paired:
        if len(rates) != len(args.seeds):
            ap.error("--paired needs as many seeds as rates")
        runs = list(zip(rates, args.seeds))
    else:
        runs = [(r, s) for r in rates for s in args.seeds]
    for rate, seed in runs:
        cell.settings["load"] = dict(cell.settings["load"], rate=rate)
        res = run_once(cell, seed, args.seconds, False, devs, clock,
                       time.perf_counter(), rehearse=args.cpu_rehearsal,
                       controls=controls)
        row = {"rate": rate, "seed": seed, "correct": res["correct"],
               "served_gap": res["checks"]["max_logit_gap"]["value"],
               "tokens": res["checks"]["tokens_checked"]["value"],
               "control_gap": res.get("control_gap", {}),
               "attempted": res["attempted"], "failed": res["failed"],
               "metrics": {k: v["value"] for k, v in
                           res.get("metrics", {}).items()}}
        rows.append(row)
        log(f"control: {json.dumps(row)}")
    summary = {"workload": cell.name, "rows": rows,
               "lower": max(r["served_gap"] for r in rows),
               "upper": {c: min(r["control_gap"][c] for r in rows)
                         for c in controls} if rows[0]["control_gap"] else {}}
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
