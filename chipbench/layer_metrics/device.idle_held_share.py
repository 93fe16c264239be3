"""Share of the window in which no program was on the device while groups
waited in the sidecar's scheduler and no bucket had closed: the flush policy
held them (a deadline, the grid, a bulk slot whose programs had ended).
Read from the sidecar's idle account (`hotstuff_tpu/ops/timeline.py`
`IdleAccount`): the advance of counter `timeline.idle_held_s` between the
sidecar's snapshots that bracket the window, over the seconds they span.
With the two other `device.idle_*_share` readings it splits the device's
idle by what the host was doing. None where a snapshot lacks the counter (a
program without the account) or the snapshots do not bracket the window."""
from chipbench import collect, spans

NAME = "timeline.idle_held_s"


def read(src):
    w = src["window"]
    first, last = collect.bracket(src["sidecar"]["snapshots"], w["t0"], w["t1"])
    if first is None or NAME not in first["counters"] or NAME not in last["counters"]:
        return None
    rate = spans.counter_rate(src, "sidecar", NAME)
    return None if rate is None else 100.0 * rate
