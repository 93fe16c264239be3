"""Pacemaker timeouts a second in the window, one live node's mean (counter
`consensus.timeouts`, `consensus/core.py` `_local_timeout_round`, between the
METRICS snapshots that bracket the window, pooled over the live nodes). With
one member dead, two rounds in every rotation of the leader time out; with
none, a timeout is a stall of the host or the program."""
from chipbench import spans


def read(src):
    return spans.counter_rate(src, "nodes", "consensus.timeouts")
