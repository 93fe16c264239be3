"""Where a configuration's verified throughput stops following the offer: one
deployment (one set-up), a window at each offered rate in the order given, a
table at the end. Not part of a benchmark run; its result goes into the traffic
files as plain numbers. Give every rate twice (two passes) and 20 s or more:
two 10 s readings of one rate have differed by a factor of two (PERF.md).

    python3 -m chipbench.sweep --config fork-n4 --rates 24000,30000,36000,24000,30000,36000 --seconds 20
    python3 -m chipbench.sweep --config fork-n10 --traffic flood-n10 --rates 18000,21000,24000,27000,18000,21000,24000,27000 --seconds 20

One sweep a chip call: every node stores every payload, so a deployment writes
nodes x rate x tx_size bytes a second (eight 20 s windows with their ramps
wrote 19 GB at n=4 and 26 GB at n=10, PR 28), and a call that has written
45 GiB is ended.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

from . import arith, collect, launch, run
from .traffic import Traffic


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10)
    ap.add_argument("--seed", type=int, default=25)
    ap.add_argument("--traffic", default="flood-n4", help="file that gives tick, ramp, probe")
    ap.add_argument("--pause", type=float, default=3.0)
    args = ap.parse_args(argv)
    cfg = run.load_config(args.config)
    base = Traffic.load(args.traffic)
    work = os.path.join(launch.ROOT, "chiprun_out", "chipbench", "sweep-" + args.config)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    dep = launch.Deployment(work, cfg, args.seed)
    rows = []
    t_begin = time.time()
    try:
        dep.start()
        took = dep.await_ready()
        run.say(f"sweep: cpus {os.cpu_count()} booted {json.dumps(took)}")
        for j, rate in enumerate(float(r) for r in args.rates.split(",")):
            tr = base.with_rate(rate)
            src = run.window_run(dep, cfg, tr, args.seed + j, args.seconds, False, tag=f"client-{j}")
            time.sleep(1.2)  # one more METRICS line after the window
            src.update(collect.gather(work, cfg["nodes"]))
            lat, failed = arith.sample_latencies(src)
            shares = arith.verified_shares(src)
            late = [max(0.0, r[3] - r[2]) for r in arith.window_records(src)]
            dev, sent = arith.backend_delta(src, "tpu_sigs"), arith.remote_sigs(src)
            chunks = arith.backend_delta(src, "dispatched")
            row = {
                "rate": rate,
                "committed_tx_per_s": arith.committed_tx_in_window(src) / args.seconds,
                "verified_tx_per_s": arith.verified_tx_per_s(src),
                "min_verified_share": None if any(s is None for s in shares) else min(shares),
                "verified_share": arith.verified_share(src),
                "samples": len(lat),
                "failed_share": failed / max(1, len(lat)),
                "p50_ms": 1000 * arith.percentile(lat, 0.5),
                "p95_ms": 1000 * arith.percentile(lat, 0.95),
                "late_p95_ms": 1000 * arith.percentile(late, 0.95),
                "device_sigs": dev,
                "sent_sigs": sent,
                "chunks": chunks,
                "probe_unanswered": sum(1 for a in src["probe"]["answers"] if a is None),
            }
            rows.append(row)
            run.say("sweep: " + json.dumps(row))
            time.sleep(args.pause)
    finally:
        dep.stop()
        for i in range(cfg["nodes"]):
            shutil.rmtree(os.path.join(work, f".db-{i}"), ignore_errors=True)
            run.tidy_log(dep.log(f"node-{i}"), keep=False)
    with open(os.path.join(work, "sweep.json"), "w") as f:
        json.dump({"config": args.config, "seconds": args.seconds, "rows": rows,
                   "wall_s": time.time() - t_begin}, f, indent=1)
    print(json.dumps({"config": args.config, "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
