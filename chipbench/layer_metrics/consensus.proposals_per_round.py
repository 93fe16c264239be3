"""Blocks proposed a round: the advance of counter `consensus.proposals`
summed over the live nodes, over the advance of the highest gauge
`consensus.round` among them. One block a round at most; with f of n members
dead, n - f proposals every n rounds (0.9 at ten with one dead), since the
dead members' rounds fall to timeouts. A program whose leader proposes again
for every certificate that reaches it reads more.

Proposals come in bursts between stalls, and the nodes' METRICS snapshots
(one a second each, at phases of their own) would cut a burst at a window's
edge differently for each node. So the counts are taken between the
snapshots that fall inside the window's first and last stalls (`arith.outages`
pooled over the live nodes), 1.5 s after the last commit that opened each:
the orphaned proposal that opens a stall is counted, the first timeout 5 s
into it has not fired, and every node stands in the same round. The counts
then span whole rotations of the leader. None where the window holds fewer
than two stalls (a committee with no fault), or where a node's snapshots do
not reach them."""
from chipbench import arith, collect

AFTER_S = 1.5


def _marks(src):
    """One instant in each stall that overlaps the window: 1.5 s past the
    latest of the live nodes' last commits before it."""
    w = src["window"]
    starts, ends = {}, {}
    for a, b, _t in sorted(arith.outages(src)):
        key = next((k for k in starts if a - k < 2.0), a)
        starts[key] = max(starts.get(key, a), a)
        ends[key] = min(ends.get(key, b), b)
    return [
        starts[k] + AFTER_S for k in sorted(starts)
        if starts[k] + AFTER_S < ends[k] - 0.5 and w["t0"] <= starts[k] + AFTER_S <= w["t1"]
    ]


def read(src):
    marks = _marks(src)
    if len(marks) < 2:
        return None
    proposals, first_round, last_round = 0, 0, 0
    for node in src["nodes"]:
        first, last = collect.bracket(node["snapshots"], marks[0], marks[-1])
        if first is None or last is None:
            return None
        if "consensus.proposals" not in last["counters"] or "consensus.round" not in last["gauges"]:
            return None
        proposals += last["counters"]["consensus.proposals"] - first["counters"].get(
            "consensus.proposals", 0)
        first_round = max(first_round, first["gauges"].get("consensus.round", 0))
        last_round = max(last_round, last["gauges"]["consensus.round"])
    rounds = last_round - first_round
    return proposals / rounds if rounds > 0 else None
