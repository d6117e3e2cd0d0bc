#!/usr/bin/env python3
"""Run one cell several times, each run its own process as a check runs
it, and report the spread that the bounds rest on.

    python3 benchmarks/chip/tools/spread.py --workload <cell> --seconds <s> \\
        --seeds 11,12,13,14,15,16 --sets 2 [--traced 21,22,23] [--out <file>]

Every set runs the same seeds.  For each end-to-end metric it prints each
set's median and the spread: the distance between the first and third
quartile (``statistics.quantiles(values, n=4)``) over the median.
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

from stats import iqr_share  # noqa: E402


def one(workload: str, seed: int, seconds: float, trace: int) -> dict:
    cmd = [sys.executable, str(HERE / "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.perf_counter()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.perf_counter() - t0
    lines = p.stdout.strip().splitlines()
    if p.returncode != 0 or not lines:
        return {"seed": seed, "trace": trace, "rc": p.returncode, "wall_s": wall,
                "stderr": p.stderr[-3000:]}
    return {"seed": seed, "trace": trace, "rc": 0, "wall_s": wall,
            **json.loads(lines[-1])}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", default="")
    ap.add_argument("--out")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    runs = []
    for k in range(args.sets):
        for s in seeds:
            r = one(args.workload, s, args.seconds, 0)
            r["set"] = k
            runs.append(r)
            print(json.dumps({key: r.get(key) for key in (
                "set", "seed", "rc", "wall_s", "correct", "failed", "attempted",
                "metrics", "info", "checks")}), flush=True)
    for s in filter(None, args.traced.split(",")):
        r = one(args.workload, int(s), args.seconds, 1)
        runs.append(r)
        print(json.dumps({key: r.get(key) for key in (
            "seed", "rc", "wall_s", "correct", "metrics", "device", "breakdown",
            "info", "checks")}), flush=True)
    summary = {}
    ok = [r for r in runs if r["rc"] == 0 and r["trace"] == 0]
    names = sorted({m for r in ok for m in r["metrics"]})
    for name in names:
        per_set = []
        for k in range(args.sets):
            vals = [r["metrics"][name]["value"] for r in ok
                    if r["set"] == k and name in r["metrics"]]
            if len(vals) >= 2:
                per_set.append({"median": statistics.median(vals),
                                "spread": iqr_share(vals), "n": len(vals)})
        summary[name] = per_set
    print(json.dumps({"summary": summary}, indent=1))
    if args.out:
        Path(args.out).write_text(json.dumps({"runs": runs, "summary": summary},
                                             indent=1))


if __name__ == "__main__":
    main()
