"""The arithmetic from sources to numbers: the window, percentiles over all
samples, committed-and-verified throughput. One place, so that an end-to-end
metric and the per-layer metric that shadows it cannot drift apart.

The window is [t0, t1) on the wall clock, fixed before the run: t0 is the
schedule's start plus the ramp, t1 = t0 + --seconds. A transaction belongs
to the window by its DUE instant, a commit by the commit line's time stamp.
"""

from __future__ import annotations

import math

from . import collect, traffic


def live_nodes(config) -> int:
    """The nodes that boot: a configuration's `faults` f are the last f
    members of its committee, which never start (upstream's
    `benchmark/local.py`: `range(nodes - faults)`). The committee, and with
    it the quorum, stays that of all `nodes`; f may be at most (n - 1) // 3,
    the most a committee of n survives."""
    n, f = int(config["nodes"]), int(config.get("faults", 0))
    if not 0 <= f <= (n - 1) // 3:
        raise ValueError(f"faults {f} in a committee of {n}: at most {(n - 1) // 3}")
    return n - f


def percentile(values, q: float):
    """Nearest-rank percentile over ALL values given; None for none."""
    if not values:
        return None
    s = sorted(values)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def median(values):
    return percentile(values, 0.5)


def window_records(src) -> list:
    w = src["window"]
    return [r for r in src["records"] if w["t0"] <= r[2] < w["t1"] and r[4] > 0]


def attempted(src) -> int:
    return sum(r[4] for r in window_records(src))


def commit_times(node) -> dict:
    """payload digest -> first commit time in this node's log."""
    out: dict = {}
    for t, _round, digest in node["payload_commits"]:
        if digest not in out:
            out[digest] = t
    return out


def sample_commits(src) -> list[tuple[float, float | None]]:
    """(due, commit time or None) for every sample due in the window; the
    commit is the one in the log of the node the sample's client sent to."""
    from . import reference as ref

    commits = [commit_times(n) for n in src["nodes"]]
    out = []
    for c, k, due, _sent, _n, _seq in window_records(src):
        digest = src["nodes"][c]["samples"].get(ref.sample_id(c, k))
        out.append((due, commits[c].get(digest) if digest is not None else None))
    return out


def sample_latencies(src) -> tuple[list[float], int]:
    """(seconds from DUE to commit for every sample due in the window, how
    many of them never committed). A sample that the node of its client has
    not committed when the run ends has failed; it stays in the list with
    the wait it had had by then, so no percentile forgets it."""
    end = src["window"]["end"]
    pairs = sample_commits(src)
    lat = [max(0.0, (end if t is None else t) - due) for due, t in pairs]
    return lat, sum(1 for _due, t in pairs if t is None)


def committed_latencies(src) -> list[float]:
    return [max(0.0, t - due) for due, t in sample_commits(src) if t is not None]


def committed_tx_in_window(src) -> int:
    """Transactions in payloads that their author's own node committed with
    a commit time inside the window."""
    w, size = src["window"], src["config"]["tx_size"]
    total = 0
    for node in src["nodes"]:
        for digest, t in commit_times(node).items():
            nbytes = node["own_payloads"].get(digest)
            if nbytes is not None and w["t0"] <= t < w["t1"]:
                total += nbytes // size
    return total


def verify_counts(src) -> list:
    """Per node: (workload signatures verified, skipped) between the METRICS
    snapshots that bracket the window; None for a node whose snapshots do
    not bracket it."""
    w = src["window"]
    out = []
    for node in src["nodes"]:
        snaps = node["snapshots"]
        done = collect.hist_sum_delta(snaps, w["t0"], w["t1"], "mempool.verify_batch_size")
        skipped = collect.counter_delta(snaps, w["t0"], w["t1"], "mempool.synthetic_skipped")
        out.append(None if done is None or skipped is None else (done, skipped))
    return out


def verified_shares(src) -> list:
    """Per node: verified over verified plus skipped; None where unread or
    where the node saw no payload at all."""
    return [
        None if c is None or c[0] + c[1] <= 0 else c[0] / (c[0] + c[1])
        for c in verify_counts(src)
    ]


def verified_share(src):
    """Of all the workload verifications the window's payloads made due, on
    every node, the share that was done and not skipped under load."""
    counts = verify_counts(src)
    if not counts or any(c is None for c in counts):
        return None
    done = sum(c[0] for c in counts)
    due = done + sum(c[1] for c in counts)
    return done / due if due > 0 else None


def verified_tx_per_s(src):
    share = verified_share(src)
    if share is None:
        return None
    return committed_tx_in_window(src) / src["window"]["seconds"] * share


def outages(src) -> list[tuple[float, float, list[float]]]:
    """Per live node, every stretch between two consecutive block commits of
    its log that is longer than `timeout_delay` and overlaps the window:
    (last commit before, first commit after, the node's `Timeout reached`
    instants in between). Time without service, as the node's own clock
    saw it; a stretch the log never closed is not one."""
    w = src["window"]
    least = src["config"]["parameters"]["consensus"]["timeout_delay"] / 1000.0
    out = []
    for node in src["nodes"]:
        times = sorted(t for t, _r, _d in node["blocks"])
        for a, b in zip(times, times[1:]):
            if b - a > least and a < w["t1"] and b > w["t0"]:
                out.append((a, b, [t for t, _r in node["timeouts"] if a < t < b]))
    return out


def front_dropped(src) -> list:
    """Per node: transactions its front port evicted (drop-oldest) since
    boot, by its last METRICS snapshot."""
    return [
        n["snapshots"][-1][1]["counters"].get("mempool.front_dropped", 0) if n["snapshots"] else 0
        for n in src["nodes"]
    ]


def window_ticks(src) -> range:
    w, tr = src["window"], src["traffic"]
    return traffic.ticks_between(w["start"], tr["tick_s"], w["t0"], w["t1"])


def sidecar_delta(src, name: str):
    w = src["window"]
    return collect.counter_delta(src["sidecar"]["snapshots"], w["t0"], w["t1"], name)


def backend_delta(src, key: str):
    """Difference of the sidecar's `info.backend` report over the window;
    `dispatched` sums the chunks of every program."""
    w = src["window"]
    a, b = collect.bracket(src["sidecar"]["snapshots"], w["t0"], w["t1"])
    if a is None or b is None:
        return None

    def get(obj):
        rep = (obj.get("info") or {}).get("backend") or {}
        v = rep.get(key)
        return sum(v.values()) if isinstance(v, dict) else v

    va, vb = get(a), get(b)
    if va is None or vb is None:
        return None
    return vb - va


def program_seconds(src):
    """(device seconds of the verify programs dispatched between the
    sidecar's snapshots that bracket the window, the seconds between those
    snapshots): every chunk dispatched is one run of the one program, and a
    run takes the trace's median program time whatever its lanes hold (its
    spread is 0.00 %, PERF.md). The trace itself is a fraction of a second;
    the count is the whole window's. None without a trace or the counts."""
    w, tr = src["window"], src.get("trace") or {}
    chunks = backend_delta(src, "dispatched")
    span = collect.bracket_seconds(src["sidecar"]["snapshots"], w["t0"], w["t1"])
    if not chunks or not span or not tr.get("program_ms"):
        return None
    return chunks * tr["program_ms"]["median"] / 1000.0, span


def remote_sigs(src):
    """Signatures the nodes and the probe sent to the sidecar in the window."""
    w = src["window"]
    total = 0
    for node in src["nodes"]:
        d = collect.counter_delta(node["snapshots"], w["t0"], w["t1"], "crypto.remote_sigs")
        if d is None:
            return None
        total += d
    return total + src.get("probe_sigs_in_window", 0)
