#!/usr/bin/env python3
"""Readings that a cell's limit is set from, on the chip at the cell's
own size, in one process.

    python3 benchmarks/chip/tools/control.py --workload <cell> --seconds <s> \\
        --seeds 1,2,...,12 --control 1,2,3 [--out <file>]

For every seed it runs the cell as a check does (set-up, a window of the
cell's own load, the comparison) and prints the program's widest logit
gap: the lower reading is the largest of these.  On the ``--control``
seeds the run puts the float8 control in the program's place
(``run.run_cell(control=True)``): the gap of the tokens the control ranks
first goes through the run's own ``correct`` decision, which has to come
out false; the upper reading is the smallest of these gaps.  The limit
goes between the two (``cells/<cell>.json``).  Exits non-zero if a sound
run is not correct or a control run is.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--out")
    args = ap.parse_args()

    import run

    cell = run.load_cell(run.ROOT, args.workload)
    control = {int(s) for s in args.control.split(",") if s}
    readings = []
    for s in (int(s) for s in args.seeds.split(",")):
        ctl = s in control
        line = run.run_cell(cell, s, args.seconds, False, control=ctl)
        info = line["info"]
        gap = line["checks"]["max_logit_gap"]["value"]
        r = {"seed": s, "correct": line["correct"], "failed": line["failed"],
             "program": info["program_max_logit_gap"] if ctl else gap,
             "control": gap if ctl else None,
             "limit": line["checks"]["max_logit_gap"]["limit"],
             "requests": info["requests_compared"],
             "tokens": info["tokens_compared"], "reference_s": info["reference_s"]}
        readings.append(r)
        print(json.dumps(r), flush=True)
    lower = max(r["program"] for r in readings)
    ctrl = [r["control"] for r in readings if r["control"] is not None]
    out = {"workload": args.workload, "readings": readings, "lower": lower,
           "upper": min(ctrl) if ctrl else None}
    print(json.dumps({k: out[k] for k in ("lower", "upper")}))
    if args.out:
        Path(args.out).write_text(json.dumps(out, indent=1))
    wrong = [r["seed"] for r in readings
             if r["correct"] == (r["control"] is not None)]
    if wrong:
        raise SystemExit(f"seeds whose correct came out wrong: {wrong}")


if __name__ == "__main__":
    main()
