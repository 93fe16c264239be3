"""Share of the window in which no program was on the device while a bucket
that the sidecar's scheduler had closed had not yet dispatched its first
program: the host path of collect, the thread hop, stage and upload.
Read from the sidecar's idle account (`hotstuff_tpu/ops/timeline.py`
`IdleAccount`): the advance of counter `timeline.idle_host_s` between the
sidecar's snapshots that bracket the window, over the seconds they span.
With the two other `device.idle_*_share` readings it splits the device's
idle by what the host was doing. None where a snapshot lacks the counter (a
program without the account) or the snapshots do not bracket the window."""
from chipbench import collect, spans

NAME = "timeline.idle_host_s"


def read(src):
    w = src["window"]
    first, last = collect.bracket(src["sidecar"]["snapshots"], w["t0"], w["t1"])
    if first is None or NAME not in first["counters"] or NAME not in last["counters"]:
        return None
    rate = spans.counter_rate(src, "sidecar", NAME)
    return None if rate is None else 100.0 * rate
