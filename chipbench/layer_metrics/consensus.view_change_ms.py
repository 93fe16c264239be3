"""How long a view change takes, window mean pooled over the live nodes:
histogram `consensus.view_change_s` (`consensus/core.py`: a node's first
local timeout of a stall to its next QC-driven round advance, on the event
loop's clock), its sum's advance over its count's between the METRICS
snapshots that bracket the window. With one member dead that is the
`timeout_delay` of the dead member's round (the first timeout, of the
orphaned round, opens it) and the recovery after it. None where a node's
snapshots do not bracket the window or do not hold the histogram (a program
older than it), and where no view change ended in the window."""
from chipbench import collect, spans

NAME = "consensus.view_change_s"


def read(src):
    w = src["window"]
    count = 0
    for node in src["nodes"]:
        first, last = collect.bracket(node["snapshots"], w["t0"], w["t1"])
        if first is None or last is None or NAME not in last["histograms"]:
            return None
        count += last["histograms"][NAME]["count"] - first["histograms"].get(
            NAME, {"count": 0})["count"]
    return spans.window_mean_ms(src, "nodes", NAME) if count > 0 else None
