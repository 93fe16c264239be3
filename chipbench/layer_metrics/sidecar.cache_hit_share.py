"""Share of the signatures the sidecar looked up in its verified-signature
cache in the window that the cache answered (`verifier.dedup_hits` over hits
plus `verifier.dedup_misses`, between the two METRICS snapshots that bracket
the window): the factor between what arrives and what the device has to
check. Nodes that hold one shared pool send the same signatures for the same
payload, and the cache answers most of them while they stay in step; nodes
with pools of their own leave it the probe's repeats. None where the
snapshots do not bracket the window or nothing was looked up."""
from chipbench import arith


def read(src):
    hits = arith.sidecar_delta(src, "verifier.dedup_hits")
    misses = arith.sidecar_delta(src, "verifier.dedup_misses")
    if hits is None or not hits + misses:
        return None
    return 100.0 * hits / (hits + misses)
