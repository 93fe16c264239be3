"""Signatures in one workload batch as a node's mempool hands it to its
verification service, mean of the window over all nodes (the sum of
`mempool.verify_batch_size` over its count, between the two METRICS snapshots
that bracket the window): one payload's transactions. 976 where payloads are
500,000 B, 29 under `fab local`'s 15,000 B. None where the snapshots do not
bracket the window or no batch was admitted in it."""
from chipbench import collect

EMPTY = {"sum": 0, "count": 0}


def read(src):
    w = src["window"]
    sigs = batches = 0
    for node in src["nodes"]:
        first, last = collect.bracket(node["snapshots"], w["t0"], w["t1"])
        if first is None or last is None:
            return None
        a = first["histograms"].get("mempool.verify_batch_size", EMPTY)
        b = last["histograms"].get("mempool.verify_batch_size", EMPTY)
        sigs += b["sum"] - a["sum"]
        batches += b["count"] - a["count"]
    return sigs / batches if batches else None
