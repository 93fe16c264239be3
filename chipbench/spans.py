"""Window readings of the program's own spans and counters (PERF.md §3): the
mean of a span histogram, or the advance of a counter, between the two
METRICS snapshots that `collect.bracket` picks around the window, of the
sidecar or pooled over the nodes. The histograms run from boot, warm-up
included; the difference of two snapshots does not.

A reading is None only where a process's snapshots do not bracket the
window. A name that a snapshot does not hold (a program older than the
span) counts as empty and reads 0: a cell has to report every metric.
"""

from __future__ import annotations

from . import collect


def _brackets(src, where: str):
    """[(first, last, seconds between them)] for the sidecar's log
    (`where` "sidecar") or every node's ("nodes"); None if one is unread."""
    w = src["window"]
    out = []
    for log in [src["sidecar"]] if where == "sidecar" else src["nodes"]:
        first, last = collect.bracket(log["snapshots"], w["t0"], w["t1"])
        seconds = collect.bracket_seconds(log["snapshots"], w["t0"], w["t1"])
        if first is None or last is None or not seconds:
            return None
        out.append((first, last, seconds))
    return out


def _hist_delta(first, last, name: str) -> tuple[float, int]:
    empty = {"sum": 0.0, "count": 0}
    a = first["histograms"].get(name, empty)
    b = last["histograms"].get(name, empty)
    return b["sum"] - a["sum"], b["count"] - a["count"]


def _counter_delta(first, last, name: str) -> float:
    return last["counters"].get(name, 0) - first["counters"].get(name, 0)


def window_mean_ms(src, where: str, name: str):
    """Milliseconds a sample of histogram `name` took on average in the
    window: the sum's advance over the count's, pooled over `where`."""
    pairs = _brackets(src, where)
    if pairs is None:
        return None
    total = count = 0
    for first, last, _seconds in pairs:
        s, c = _hist_delta(first, last, name)
        total += s
        count += c
    return 1000.0 * total / count if count else 0.0


def loop_us_per_sig(src, names):
    """Microseconds of the sidecar's event loop, in the synchronous
    sections `names` time, per signature that arrived in the window."""
    pairs = _brackets(src, "sidecar")
    if pairs is None:
        return None
    first, last, _seconds = pairs[0]
    sigs = _counter_delta(first, last, "sidecar.request_sigs")
    spent = sum(_hist_delta(first, last, name)[0] for name in names)
    return 1e6 * spent / sigs if sigs else 0.0


def loop_cpu_share(src, where: str):
    """Per cent of one core that the event loop's thread used in the
    window (`runtime.loop_cpu_s` over the seconds between the snapshots);
    of the nodes, the busiest."""
    pairs = _brackets(src, where)
    if pairs is None:
        return None
    return max(
        100.0 * _counter_delta(first, last, "runtime.loop_cpu_s") / seconds
        for first, last, seconds in pairs
    )


def counter_rate(src, where: str, name: str):
    """Advances of counter `name` a second in the window, pooled over
    `where`: the sum of every process's advance over the sum of the seconds
    its snapshots span (of the nodes, one node's mean rate)."""
    pairs = _brackets(src, where)
    if pairs is None:
        return None
    advance = sum(_counter_delta(first, last, name) for first, last, _s in pairs)
    return advance / sum(seconds for _f, _l, seconds in pairs)
