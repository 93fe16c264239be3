"""Groups (wire requests) that one critical dispatch of the sidecar's
scheduler carried, mean of the window: `scheduler.critical_groups` over
`scheduler.critical_dispatches` between the two METRICS snapshots that
bracket the window. 1.0 is a device program for every urgent request; over
it, requests that found two critical programs in flight waited in the lane
and shared the next one (`crypto/scheduler.py`, the critical lane's dispatch
window; `scheduler.critical_held` counts them).

A program older than `scheduler.critical_groups` counted the same groups
one by one as samples of `scheduler.queue_consensus_s` (a sample a group
taken off the preemptive lane), so its reading is that histogram's count
over the dispatches: what it did, not a 0 that would mean "absent"
(`run.py` prints no line where a due metric has no reading). None where
the snapshots do not bracket the window, where the program has neither
count, or where no critical dispatch fell in the window: `BENCHMARK.json`
lists the cells whose windows always hold some (tens to thousands), and
leaves out those whose requests are nearly all over 256 signatures."""
from chipbench import collect

GROUPS = "scheduler.critical_groups"
DISPATCHES = "scheduler.critical_dispatches"
QUEUED = "scheduler.queue_consensus_s"


def read(src):
    w = src["window"]
    first, last = collect.bracket(src["sidecar"]["snapshots"], w["t0"], w["t1"])
    if first is None or last is None or DISPATCHES not in last["counters"]:
        return None
    if GROUPS in last["counters"]:
        groups = last["counters"][GROUPS] - first["counters"].get(GROUPS, 0)
    elif QUEUED in last["histograms"]:
        before = first["histograms"].get(QUEUED, {"count": 0})
        groups = last["histograms"][QUEUED]["count"] - before["count"]
    else:
        return None
    dispatches = last["counters"][DISPATCHES] - first["counters"].get(DISPATCHES, 0)
    return groups / dispatches if dispatches else None
