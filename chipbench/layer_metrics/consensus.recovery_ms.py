"""The part of an outage the program controls: from a live node's last
pacemaker timeout in an outage (`arith.outages`) to its first commit after
it, median pooled over the live nodes. The timeout certificate that ends the
stall forms once a quorum has timed out, and the nodes' timers fire within
milliseconds of each other (one certificate moved them all into the round),
so this is certificate, proposal, two rounds of votes and the commit; the
rest of an outage is the configuration's `timeout_delay`, twice. None where
no outage in the window holds a timeout."""
from chipbench import arith


def read(src):
    v = arith.median([b - max(ts) for _a, b, ts in arith.outages(src) if ts])
    return None if v is None else 1000.0 * v
