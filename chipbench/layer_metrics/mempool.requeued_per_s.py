"""Payload digests put back at the front of a live node's queue a second in
the window (counter `mempool.orphans_requeued`, `mempool/core.py` `_commit`:
digests of blocks that a commit left out, so that they can never commit),
pooled over the live nodes as `consensus.timeouts_per_s` is. With one member
dead, the block proposed just before its round is such a block in every
rotation of the leader. None where a node's snapshots do not bracket the
window or do not hold the counter (a program that drops such digests)."""
from chipbench import collect, spans

NAME = "mempool.orphans_requeued"


def read(src):
    w = src["window"]
    for node in src["nodes"]:
        _first, last = collect.bracket(node["snapshots"], w["t0"], w["t1"])
        if last is None or NAME not in last["counters"]:
            return None
    return spans.counter_rate(src, "nodes", NAME)
