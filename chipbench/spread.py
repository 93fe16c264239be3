"""Run one cell on several seeds, one process each, and print every metric's
median and spread (interquartile distance over the median, by
`statistics.quantiles(values, n=4)`, as the bounds are set from).

    python3 -m chipbench.spread --workload fork-n4.flood --seeds 11,12,13 \
        --seconds 20 --trace 0 [--sets 2] [--fault skip_half]

The result lines go to `chiprun_out/chipbench/results.jsonl`, one per run,
with the cell, seed, set and wall seconds added.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

from . import launch


def spread(values) -> float:
    if len(values) < 2:
        return 0.0
    q, mid = statistics.quantiles(values, n=4), statistics.median(values)
    return (q[2] - q[0]) / mid if mid else 0.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", default="20")
    ap.add_argument("--trace", default="0")
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--fault")
    ap.add_argument("--keep-logs", action="store_true",
                    help="keep each run's nodes' logs, in a directory of its own")
    args = ap.parse_args(argv)
    out_path = os.path.join(launch.ROOT, "chiprun_out", "chipbench", "results.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    rows = []
    for s in range(args.sets):
        for seed in args.seeds.split(","):
            cmd = [sys.executable, "-m", "chipbench.run", "--workload", args.workload,
                   "--seed", seed, "--seconds", args.seconds, "--trace", args.trace]
            if args.fault:
                cmd += ["--fault", args.fault]
            if args.keep_logs:
                cmd += ["--keep-logs", "--out", os.path.join(
                    launch.ROOT, "chiprun_out", "chipbench", f"{args.workload}-{seed}-{s}")]
            t = time.time()
            p = subprocess.run(cmd, cwd=launch.ROOT, capture_output=True, text=True)
            wall = time.time() - t
            lines = p.stdout.strip().splitlines()
            if p.returncode != 0 or not lines:
                print(f"seed {seed} set {s}: rc={p.returncode}\n{p.stderr[-3000:]}", flush=True)
                continue
            row = json.loads(lines[-1])
            row.update(cell=args.workload, seed=int(seed), set=s, wall_s=wall,
                       trace=int(args.trace), fault=args.fault)
            rows.append(row)
            with open(out_path, "a") as f:
                f.write(json.dumps(row) + "\n")
            over = {k: v for k, v in row["compared"].items() if v[0] > v[1]}
            print(
                f"{args.workload} seed {seed} set {s}: correct={row['correct']} "
                f"attempted={row['attempted']} failed={row['failed']} wall={wall:.0f}s "
                + " ".join(f"{k}={v['value']:.6g}" for k, v in row["metrics"].items())
                + (f" OVER {over}" if over else ""),
                flush=True,
            )
    for s in range(args.sets):
        mine = [r for r in rows if r["set"] == s]
        names = sorted({k for r in mine for k in r["metrics"]})
        for k in names:
            vals = [r["metrics"][k]["value"] for r in mine if k in r["metrics"]]
            if vals:
                print(f"set {s} {args.workload} {k}: n={len(vals)} median={statistics.median(vals):.6g} "
                      f"spread={100 * spread(vals):.2f}% min={min(vals):.6g} max={max(vals):.6g}",
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
