"""Run one cell several times, each run a new process, and print the spread
of every metric: the sets of runs the bounds in ``BENCHMARK.json`` are set
from.  The parent never touches JAX, so each child has the chip to itself.

    python3 -m benchmarks.measure --workload <cell> --seconds 30 \
        --seeds 2147483659,2147483693,... --sets 2 [--trace 0|1]

Every run's last line goes to ``chiprun_out/<cell>.jsonl`` with its seed, set
and wall time; the summary (median, quartile spread as the driver takes it,
first-run and later ``setup_s``) is printed at the end.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

from benchmarks import stats


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    os.makedirs(args.out, exist_ok=True)
    log = os.path.join(args.out, f"{args.workload}.jsonl")
    rows = []
    for set_no in range(args.sets):
        for seed in seeds:
            t0 = time.perf_counter()
            p = subprocess.run(
                [sys.executable, "-m", "benchmarks.run", "--workload",
                 args.workload, "--seed", str(seed), "--seconds",
                 str(args.seconds), "--trace", str(args.trace)],
                capture_output=True, text=True)
            wall = time.perf_counter() - t0
            lines = p.stdout.strip().splitlines()
            row = {"set": set_no, "seed": seed, "rc": p.returncode,
                   "wall_s": wall, "trace": args.trace}
            if p.returncode == 0 and lines:
                row["result"] = json.loads(lines[-1])
                row["earlier"] = [json.loads(x) for x in lines[:-1]
                                  if x.startswith("{")]
            else:
                row["stderr"] = p.stderr[-3000:]
                row["stdout"] = p.stdout[-2000:]
            rows.append(row)
            with open(log, "a") as f:
                f.write(json.dumps(row) + "\n")
            brief = {k: v["value"] for k, v in
                     row.get("result", {}).get("metrics", {}).items()}
            print(json.dumps({"set": set_no, "seed": seed, "rc": p.returncode,
                              "wall_s": round(wall, 1), **brief,
                              "correct": row.get("result", {}).get("correct"),
                              "failed": row.get("result", {}).get("failed")}),
                  flush=True)
            if p.returncode != 0:
                print(p.stderr[-3000:], flush=True)
    ok = [r for r in rows if "result" in r]
    names = sorted({k for r in ok for k in r["result"]["metrics"]})
    for name in names:
        for set_no in range(args.sets):
            vals = [r["result"]["metrics"][name]["value"] for r in ok
                    if r["set"] == set_no and name in r["result"]["metrics"]]
            if name == "setup_s" and set_no == 0:
                vals = vals[1:]          # the first run compiles
            if len(vals) < 2:
                continue
            print(json.dumps({"metric": name, "set": set_no, "n": len(vals),
                              "median": statistics.median(vals),
                              "min": min(vals), "max": max(vals),
                              "iqr_over_median": stats.spread(vals)}),
                  flush=True)


if __name__ == "__main__":
    main()
