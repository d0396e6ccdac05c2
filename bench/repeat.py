#!/usr/bin/env python3
"""Repeat a cell to measure its spread, one process per run as the check
runs it:

    python bench/repeat.py --workload qwen2-1.5b.longdoc --seeds 1001-1006 \\
        --sets 2 --seconds 51 --out runs/longdoc_sets.jsonl

Runs ``bench/run.py`` once per seed in each set (the same seeds in every
set), appends each run's result line to ``--out`` (with its set, seed,
exit code and wall time), and prints for each set and metric the median
and the spread: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, and for
each number compared for ``correct`` its least and largest reading. With
``--control 1`` the runs read the check's control (``run.py --control``):
the largest program gap over seeds is a limit's lower reading, the
smallest control gap its upper one. This process never touches JAX; each
run holds the chips alone."""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def seeds(text):
    """``"1-3,7"`` -> [1, 2, 3, 7]."""
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def spread(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2 if q2 else float("inf")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=51)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    runs = []
    for k in range(args.sets):
        for s in seeds(args.seeds):
            t = time.monotonic()
            p = subprocess.run(
                [sys.executable, os.path.join(ROOT, "bench", "run.py"),
                 "--workload", args.workload, "--seed", str(s),
                 "--seconds", str(args.seconds), "--trace", str(args.trace),
                 "--control", str(args.control)],
                cwd=ROOT, capture_output=True, text=True)
            line = p.stdout.strip().splitlines()[-1] if p.stdout.strip() \
                else "{}"
            try:
                res = json.loads(line)
            except ValueError:
                res = {}
            rec = {"set": k, "seed": s, "rc": p.returncode,
                   "wall_s": time.monotonic() - t, "result": res,
                   "stderr_tail": p.stderr[-1500:]}
            runs.append(rec)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
            print(json.dumps({k2: rec[k2] for k2 in ("set", "seed", "rc",
                                                     "wall_s")}
                             | {"correct": res.get("correct"),
                                "metrics": {m: v["value"] for m, v in
                                            res.get("metrics", {}).items()},
                                "checks": res.get("checks")}), flush=True)
    summary = {}
    for k in range(args.sets):
        ok = [r["result"] for r in runs if r["set"] == k and r["result"]]
        names = sorted({m for r in ok for m in r.get("metrics", {})})
        for m in names:
            v = [r["metrics"][m]["value"] for r in ok if m in r["metrics"]]
            if len(v) >= 2:
                summary.setdefault(m, {})[f"set{k}"] = {
                    "median": statistics.median(v), "spread": spread(v),
                    "n": len(v)}
    checks = {}
    for r in runs:
        for name, c in r["result"].get("checks", {}).items():
            checks.setdefault(name, []).append(c["value"])
    readings = {name: {"least": min(v), "largest": max(v), "n": len(v)}
                for name, v in checks.items()}
    print(json.dumps({"workload": args.workload, "spreads": summary,
                      "checks": readings}))


if __name__ == "__main__":
    main()
