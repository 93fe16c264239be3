"""Signatures in one request a node sent to the sidecar, mean of the window
over all nodes (`crypto.remote_sigs` over `crypto.remote_batches`, between
the two METRICS snapshots that bracket the window): what the node's own
service made of its batches by coalescing them. Only requests of 64
signatures or more go over the wire; the rest the node's CPU checks
(`node.cpu_verified_share`). None where the snapshots do not bracket the
window or no request was sent in it."""
from chipbench import collect


def read(src):
    w = src["window"]
    sigs = requests = 0
    for node in src["nodes"]:
        s = collect.counter_delta(node["snapshots"], w["t0"], w["t1"], "crypto.remote_sigs")
        r = collect.counter_delta(node["snapshots"], w["t0"], w["t1"], "crypto.remote_batches")
        if s is None or r is None:
            return None
        sigs += s
        requests += r
    return sigs / requests if requests else None
