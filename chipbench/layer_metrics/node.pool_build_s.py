"""Seconds the slowest node took to generate its synthetic signature pool at
boot (`mempool.pool_build_s`, one sample a node, by each node's last METRICS
snapshot before the window closes): the part of `setup_s` that the nodes'
boot is made of. A program older than the histogram reads 0; None where a
node's log holds no snapshot by then."""
from chipbench import collect

EMPTY = {"sum": 0.0}


def read(src):
    w = src["window"]
    lasts = [collect.bracket(n["snapshots"], w["t0"], w["t1"])[1] for n in src["nodes"]]
    if not lasts or any(last is None for last in lasts):
        return None
    return max(last["histograms"].get("mempool.pool_build_s", EMPTY)["sum"] for last in lasts)
