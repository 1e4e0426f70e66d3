#!/usr/bin/env python3
"""Sets of runs of one cell, for setting its bounds.

    python bench/spread.py --workload <cell> --seconds <s> \\
        --seeds 11,12,13,14,15,16 [--sets 2] [--trace 0] [--out runs.jsonl]

Starts ``bench/run.py`` once per seed in each set, one run at a time
(this process never touches JAX, so each run has the chip to itself),
with the same seeds in every set.  Prints one line per run and, for
each metric, each set's median and its spread: the distance between the
first and third quartile (``statistics.quantiles(values, n=4)``) over
the median.  ``--out`` appends one JSON row per run.
"""

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def spread(values: list[float]) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def one_run(cell: str, seed: str, seconds: str, trace: str) -> dict:
    t = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", cell,
         "--seed", seed, "--seconds", seconds, "--trace", trace],
        cwd=ROOT, capture_output=True, text=True, check=False)
    out = proc.stdout.strip().splitlines()
    try:
        result = json.loads(out[-1]) if out else {}
    except ValueError:
        result = {}
    row = {"seed": seed, "rc": proc.returncode,
           "wall_s": time.perf_counter() - t, "result": result,
           "stderr": [line for line in proc.stderr.splitlines()
                      if line.startswith(('{"info"', "check "))][-16:]}
    if proc.returncode or not result:
        row["stderr_tail"] = proc.stderr[-3000:]
    return row


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace", default="0", choices=("0", "1"))
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = args.seeds.split(",")
    rows = []
    for s in range(args.sets):
        for seed in seeds:
            row = {"set": s, **one_run(args.workload, seed, args.seconds,
                                       args.trace)}
            rows.append(row)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(row) + "\n")
            res = row["result"]
            print(s, seed, "rc", row["rc"], "correct", res.get("correct"),
                  "wall %.1f" % row["wall_s"],
                  {k: v["value"] for k, v in res.get("metrics", {}).items()},
                  {k: v["value"] for k, v in res.get("checks", {}).items()},
                  "peak", res.get("device", {}).get("memory_peak_bytes"),
                  flush=True)
            if "stderr_tail" in row:
                print(row["stderr_tail"], flush=True)
    names = sorted({k for r in rows for k in r["result"].get("metrics", {})})
    for name in names:
        for s in range(args.sets):
            v = [r["result"]["metrics"][name]["value"] for r in rows
                 if r["set"] == s and name in r["result"].get("metrics", {})]
            if len(v) >= 2:
                print(name, "set", s, "median", statistics.median(v),
                      "spread", spread(v), v, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
