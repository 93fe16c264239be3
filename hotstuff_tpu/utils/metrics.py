"""In-process metrics registry + stage tracing for the consensus/TPU hot paths.

Every performance claim so far came from ad-hoc timers (`tools/profile_e2e.py`
exists because a 2.1x device-vs-e2e gap was asserted before it was measured);
this module makes per-stage breakdowns a permanent, machine-readable artifact:

  * `counter(name)` / `gauge(name)` / `histogram(name)` — get-or-create
    metrics in a process-global registry. Counters are monotonic; histograms
    use FIXED bucket bounds (no per-sample storage) and derive p50/p95/p99
    by interpolation inside the owning bucket, so recording is O(log buckets)
    and memory is O(buckets) no matter how hot the path.
  * `span(hist)` context manager and `@timed(name)` decorator — stage
    tracing; a span records wall seconds into its histogram on exit.
  * `snapshot_json()` — one compact JSON object (no raw buckets) for the
    periodic `METRICS {json}` log line that `benchmark.logs.LogParser`
    scrapes; `dump()` / `write_json(path)` — the full structured artifact
    (`node run --metrics-out`).
  * `start_periodic_emitter(interval_s)` — a daemon thread logging the
    snapshot line on `hotstuff.metrics` at INFO.

Thread-safety: every metric guards its state with its own lock — the
verifier's upload/dispatch threads, the BatchVerificationService worker
threads, and the asyncio actor loops all record concurrently.

Overhead: recording is gated on a module-level flag (`HOTSTUFF_METRICS=0`
disables it); when disabled, `inc`/`record`/`span` are a single global read
and an early return — no lock, no clock read.

The canonical metric namespace is registered eagerly at import
(`_DEFAULT_NAMESPACE` below, documented in COMPONENTS.md), so a `dump()`
always carries the full schema — zeros included — even in processes that
never exercise (or cannot import) a given layer. Layer modules re-request
the same names via get-or-create, which keeps handles and schema in sync.

Dependency-free by design: stdlib only, no jax, no package-internal imports.
"""

from __future__ import annotations

import functools
import json
import logging
import math
import os
import threading
import time
from bisect import bisect_left
from typing import Callable, Sequence

log = logging.getLogger("hotstuff.metrics")

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "TIME_BUCKETS_S",
    "SIZE_BUCKETS",
    "counter",
    "gauge",
    "histogram",
    "span",
    "before_dump",
    "timed",
    "enabled",
    "enable",
    "dump",
    "percentile",
    "bucket_percentile",
    "snapshot_json",
    "emit_snapshot",
    "write_json",
    "reset",
    "start_periodic_emitter",
    "meter_loop_cpu",
]

# Wall-seconds buckets (1-2-5 series, 10 us .. 60 s): spans from sub-ms
# kernel dispatches up to multi-second cold compiles land in distinct rows.
TIME_BUCKETS_S: tuple[float, ...] = (
    1e-5, 2e-5, 5e-5, 1e-4, 2e-4, 5e-4, 1e-3, 2e-3, 5e-3,
    1e-2, 2e-2, 5e-2, 0.1, 0.2, 0.5, 1.0, 2.0, 5.0, 10.0, 30.0, 60.0,
)

# Power-of-two buckets for batch/queue sizes (1 .. 128k — the verifier's
# bucket widths are powers of two, so each width is its own row).
SIZE_BUCKETS: tuple[float, ...] = tuple(float(2**i) for i in range(18))


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile over raw samples, 0.0 on empty input.

    The ONE list-percentile definition (ceil nearest-rank): ingress
    loadgen, the scheduler's LaneStats, and tools/trace_report.py's
    local mirror all report a "p99" computed the same way, so the same
    samples never yield different percentiles in different reports.
    (Histogram.percentile interpolates over buckets — a different
    estimator for pre-binned data, not a duplicate of this.)"""
    if not values:
        return 0.0
    ordered = sorted(values)
    idx = max(0, min(len(ordered) - 1, math.ceil(q * len(ordered)) - 1))
    return ordered[idx]


def bucket_percentile(
    bounds: Sequence[float],
    counts: Sequence[int],
    total: int,
    lo: float,
    hi: float,
    q: float,
) -> float:
    """Interpolated percentile over bucket counts (the ONE bucket
    estimator: Histogram.percentile feeds its observed min/max as the
    edge clamps; the telemetry plane's windowed delta percentiles have no
    observed range, so they pass [0, last finite bound]). `counts` has
    one extra overflow entry past `bounds`; `total` is sum(counts),
    passed in because Histogram reads it under its snapshot lock."""
    target = q * total
    cum = 0
    for i, c in enumerate(counts):
        if c and cum + c >= target:
            b_lo = float(bounds[i - 1]) if i > 0 else lo
            b_hi = float(bounds[i]) if i < len(bounds) else hi
            b_lo = max(b_lo, lo)  # clamp edges to the caller's range
            b_hi = max(min(b_hi, hi), b_lo)
            return b_lo + (b_hi - b_lo) * ((target - cum) / c)
        cum += c
    return hi

_enabled = os.environ.get("HOTSTUFF_METRICS", "1") != "0"

# Metric locks are RE-ENTRANT: the node's SIGTERM handler flushes a dump()
# on the interrupted main thread, which may be parked inside a record()'s
# critical section — a plain Lock would deadlock the exit path (a torn read
# of one in-flight sample is acceptable for a final snapshot; a hang is not).
_new_lock = threading.RLock


def enabled() -> bool:
    return _enabled


def enable(on: bool = True) -> None:
    """Flip recording globally (registration is always allowed)."""
    global _enabled
    _enabled = on


class Counter:
    """Monotonic counter."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str) -> None:
        self.name = name
        self._value = 0
        self._lock = _new_lock()

    def inc(self, n: float = 1) -> None:
        if not _enabled:
            return
        with self._lock:
            self._value += n

    @property
    def value(self) -> float:
        return self._value

    def _reset(self) -> None:
        with self._lock:
            self._value = 0


class Gauge:
    """Last-write-wins scalar (e.g. the current consensus round)."""

    __slots__ = ("name", "_value", "_lock")

    def __init__(self, name: str) -> None:
        self.name = name
        self._value = 0.0
        self._lock = _new_lock()

    def set(self, v: float) -> None:
        if not _enabled:
            return
        with self._lock:
            self._value = v

    def add(self, v: float) -> None:
        if not _enabled:
            return
        with self._lock:
            self._value += v

    @property
    def value(self) -> float:
        return self._value

    def _reset(self) -> None:
        with self._lock:
            self._value = 0.0


class Histogram:
    """Fixed-bucket histogram with interpolated percentiles.

    `bounds` are the inclusive upper edges of the finite buckets; one
    overflow bucket catches everything above the last bound. Percentiles
    interpolate linearly inside the owning bucket (clamped to the observed
    min/max at the edges), so their error is bounded by the bucket width —
    the resolution contract callers pick via `buckets`.
    """

    __slots__ = ("name", "bounds", "_counts", "_count", "_sum", "_min", "_max", "_lock")

    def __init__(self, name: str, buckets: Sequence[float] = TIME_BUCKETS_S) -> None:
        if not buckets or list(buckets) != sorted(buckets):
            raise ValueError(f"histogram {name}: buckets must be sorted and non-empty")
        self.name = name
        self.bounds = tuple(float(b) for b in buckets)
        self._counts = [0] * (len(self.bounds) + 1)  # +1 overflow
        self._count = 0
        self._sum = 0.0
        self._min = float("inf")
        self._max = float("-inf")
        self._lock = _new_lock()

    def record(self, v: float) -> None:
        if not _enabled:
            return
        i = bisect_left(self.bounds, v)
        with self._lock:
            self._counts[i] += 1
            self._count += 1
            self._sum += v
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    def _snapshot(self) -> tuple[list[int], int, float, float, float]:
        """One locked copy of (bucket counts, count, sum, min, max)."""
        with self._lock:
            return (
                list(self._counts), self._count, self._sum, self._min, self._max
            )

    def _percentile_from(
        self, counts: list[int], total: int, lo_obs: float, hi_obs: float,
        q: float,
    ) -> float:
        return bucket_percentile(self.bounds, counts, total, lo_obs, hi_obs, q)

    def percentile(self, q: float) -> float:
        """q in [0, 1] -> interpolated value; 0.0 on an empty histogram."""
        counts, total, _s, lo_obs, hi_obs = self._snapshot()
        if total == 0:
            return 0.0
        return self._percentile_from(counts, total, lo_obs, hi_obs, q)

    def summary(self) -> dict:
        """All fields derive from ONE locked snapshot, so concurrent
        recording cannot yield an internally inconsistent summary (e.g.
        p95 < p50, or a count matching none of the percentile bases)."""
        counts, total, s, lo, hi = self._snapshot()
        if total == 0:
            return {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                    "mean": 0.0, "p50": 0.0, "p95": 0.0, "p99": 0.0}
        pct = lambda q: self._percentile_from(counts, total, lo, hi, q)
        return {
            "count": total,
            "sum": s,
            "min": lo,
            "max": hi,
            "mean": s / total,
            "p50": pct(0.50),
            "p95": pct(0.95),
            "p99": pct(0.99),
        }

    def buckets_dict(self) -> dict:
        with self._lock:
            counts = list(self._counts)
        return {"le": list(self.bounds) + ["+inf"], "counts": counts}

    def _reset(self) -> None:
        with self._lock:
            self._counts = [0] * (len(self.bounds) + 1)
            self._count = 0
            self._sum = 0.0
            self._min = float("inf")
            self._max = float("-inf")


class _Span:
    """Context manager timing one stage into a histogram (see `span`)."""

    __slots__ = ("_hist", "_t0")

    def __init__(self, hist: Histogram) -> None:
        self._hist = hist
        self._t0 = None

    def __enter__(self) -> "_Span":
        if _enabled:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._t0 is not None and _enabled:
            self._hist.record(time.perf_counter() - self._t0)
        self._t0 = None


class Registry:
    """Named metrics, get-or-create. One process-global default below."""

    def __init__(self) -> None:
        self._metrics: dict[str, Counter | Gauge | Histogram] = {}
        self._info: dict[str, object] = {}
        self._before_dump: list[Callable[[], None]] = []
        self._lock = _new_lock()

    def _get_or_create(self, name: str, kind, factory):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = factory()
            elif not isinstance(m, kind):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, requested {kind.__name__}"
                )
            return m

    def counter(self, name: str) -> Counter:
        return self._get_or_create(name, Counter, lambda: Counter(name))

    def gauge(self, name: str) -> Gauge:
        return self._get_or_create(name, Gauge, lambda: Gauge(name))

    def histogram(
        self, name: str, buckets: Sequence[float] = TIME_BUCKETS_S
    ) -> Histogram:
        return self._get_or_create(name, Histogram, lambda: Histogram(name, buckets))

    def before_dump(self, fn: Callable[[], None]) -> None:
        """Run `fn()` at the start of every dump: a flush of counters that
        are charged lazily (ops/timeline.py's idle account)."""
        with self._lock:
            self._before_dump.append(fn)

    def dump(self, include_buckets: bool = True) -> dict:
        """Full structured artifact (the `--metrics-out` JSON)."""
        counters, gauges, hists = {}, {}, {}
        with self._lock:
            flushes = list(self._before_dump)
        for fn in flushes:
            fn()
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            if isinstance(m, Counter):
                counters[m.name] = m.value
            elif isinstance(m, Gauge):
                gauges[m.name] = m.value
            else:
                summary = m.summary()
                if include_buckets:
                    summary["buckets"] = m.buckets_dict()
                hists[m.name] = summary
        out = {
            "v": 1,
            "enabled": _enabled,
            "counters": counters,
            "gauges": gauges,
            "histograms": hists,
        }
        with self._lock:
            info = dict(self._info)
        if info:
            # Non-numeric facts about the process (which device, which
            # kernel): callables are evaluated at dump time so the section
            # is as live as the counters beside it.
            out["info"] = {
                k: v() if callable(v) else v for k, v in info.items()
            }
        return out

    def set_info(self, key: str, value) -> None:
        """Attach a JSON-able value (or a zero-argument callable returning
        one) to every dump under `info[key]`."""
        with self._lock:
            self._info[key] = value

    def snapshot_json(self) -> str:
        """Compact one-line JSON (summaries only) for the METRICS log line."""
        return json.dumps(
            self.dump(include_buckets=False), separators=(",", ":"), sort_keys=True
        )

    def write_json(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.dump(), f, indent=2, sort_keys=True)
            f.write("\n")

    def reset(self) -> None:
        """Zero every metric; registrations are kept (test isolation)."""
        with self._lock:
            metrics = list(self._metrics.values())
        for m in metrics:
            m._reset()


REGISTRY = Registry()


def counter(name: str) -> Counter:
    return REGISTRY.counter(name)


def gauge(name: str) -> Gauge:
    return REGISTRY.gauge(name)


def histogram(name: str, buckets: Sequence[float] = TIME_BUCKETS_S) -> Histogram:
    return REGISTRY.histogram(name, buckets)


def before_dump(fn: Callable[[], None]) -> None:
    REGISTRY.before_dump(fn)


def span(hist: Histogram | str) -> _Span:
    """`with metrics.span(h): ...` — time the block into histogram `h`.
    Hot paths should pass a pre-created Histogram handle (a string does a
    registry lookup per call)."""
    if isinstance(hist, str):
        hist = REGISTRY.histogram(hist)
    return _Span(hist)


def timed(name: str, buckets: Sequence[float] = TIME_BUCKETS_S) -> Callable:
    """Decorator form of `span`: records each call's wall seconds."""
    h = REGISTRY.histogram(name, buckets)

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not _enabled:
                return fn(*args, **kwargs)
            t0 = time.perf_counter()
            try:
                return fn(*args, **kwargs)
            finally:
                h.record(time.perf_counter() - t0)

        return wrapper

    return deco


def dump(include_buckets: bool = True) -> dict:
    return REGISTRY.dump(include_buckets)


def snapshot_json() -> str:
    return REGISTRY.snapshot_json()


def write_json(path: str) -> None:
    REGISTRY.write_json(path)


def reset() -> None:
    REGISTRY.reset()


def set_info(key: str, value) -> None:
    REGISTRY.set_info(key, value)


def emit_snapshot() -> None:
    """Log one `METRICS {json}` line (the LogParser scraping contract)."""
    log.info("METRICS %s", snapshot_json())


_emitter_stop: threading.Event | None = None
_emitter_lock = threading.Lock()


def start_periodic_emitter(interval_s: float = 5.0) -> threading.Event | None:
    """Emit a snapshot line every `interval_s` from a daemon thread; returns
    the stop event (set() to halt), or None when interval <= 0 or an emitter
    is already running."""
    global _emitter_stop
    if interval_s <= 0:
        return None
    with _emitter_lock:
        if _emitter_stop is not None and not _emitter_stop.is_set():
            return None
        stop = _emitter_stop = threading.Event()

    def _loop() -> None:
        while not stop.wait(interval_s):
            if _enabled:
                emit_snapshot()

    threading.Thread(target=_loop, name="metrics-emitter", daemon=True).start()
    return stop


async def meter_loop_cpu(interval_s: float = 0.05) -> None:
    """Add the CPU seconds of the calling event loop's thread to
    `runtime.loop_cpu_s`, every `interval_s`: over a window its advance,
    divided by the window, is how much of one core the loop's thread
    used (1.0 = that thread is the bottleneck). Started by a process's
    entry point alone (`crypto/remote.py:serve`, `node/main.py`), never
    by a library class: the chaos runner's virtual clock must not see it."""
    import asyncio

    cpu = counter("runtime.loop_cpu_s")
    last = time.thread_time()
    while True:
        await asyncio.sleep(interval_s)
        now = time.thread_time()
        cpu.inc(now - last)
        last = now


def start_periodic_emitter_from_env(default_s: float = 5.0):
    """`start_periodic_emitter` at `HOTSTUFF_METRICS_INTERVAL` seconds
    (<= 0 disables) — what `node run` and the crypto sidecar both do."""
    try:
        interval = float(os.environ.get("HOTSTUFF_METRICS_INTERVAL", default_s))
    except ValueError:
        log.warning("ignoring malformed HOTSTUFF_METRICS_INTERVAL")
        interval = default_s
    return start_periodic_emitter(interval)


# --- canonical namespace ----------------------------------------------------
#
# (name, kind, buckets) — the schema of record, documented as the metric
# naming table in COMPONENTS.md. Registered eagerly so every dump carries
# the full schema with zeros for layers the process never exercised.

_DEFAULT_NAMESPACE: tuple[tuple[str, str, tuple[float, ...] | None], ...] = (
    # ops/ed25519.py + crypto/tpu_backend.py — verifier hot path
    ("verifier.stage_s", "histogram", None),
    ("verifier.upload_s", "histogram", None),
    ("verifier.dispatch_s", "histogram", None),
    ("verifier.readback_s", "histogram", None),
    ("verifier.e2e_s", "histogram", None),
    ("verifier.batch_size", "histogram", SIZE_BUCKETS),
    ("verifier.sigs", "counter", None),
    ("verifier.batches", "counter", None),
    ("verifier.chunks", "counter", None),
    # committee-resident key precompute + verified-signature dedup
    ("verifier.decompressions", "counter", None),
    ("verifier.table_builds", "counter", None),
    ("verifier.pad_lanes", "counter", None),
    ("verifier.committee_batches", "counter", None),
    ("verifier.committee_sigs", "counter", None),
    ("verifier.committee_registrations", "counter", None),
    ("verifier.committee_misses", "counter", None),
    ("verifier.committee_size", "gauge", None),
    ("verifier.crossover_fallbacks", "counter", None),
    ("verifier.dedup_hits", "counter", None),
    ("verifier.dedup_misses", "counter", None),
    ("verifier.dedup_inserts", "counter", None),
    ("verifier.dedup_evictions", "counter", None),
    ("verifier.rejected_sigs", "counter", None),
    ("verifier.committee_rejected_sigs", "counter", None),
    # ops/bls.py — batched G1 public-key aggregation kernel (§5.5o).
    # host_fallbacks counts CommitteeTable aggregations that ran the exact
    # pure-python fold because jax was unavailable on the host.
    ("bls.table_builds", "counter", None),
    ("bls.aggregations", "counter", None),
    ("bls.points_aggregated", "counter", None),
    ("bls.host_fallbacks", "counter", None),
    ("crypto.tpu_batches", "counter", None),
    ("crypto.tpu_sigs", "counter", None),
    ("crypto.cpu_batches", "counter", None),
    ("crypto.cpu_sigs", "counter", None),
    ("crypto.batch_size", "histogram", SIZE_BUCKETS),
    # crypto/remote.py — a node's RemoteBackend (sidecar client)
    ("crypto.remote_batches", "counter", None),
    ("crypto.remote_sigs", "counter", None),
    ("crypto.remote_fallback_batches", "counter", None),
    ("crypto.remote_cpu_batches", "counter", None),
    ("crypto.remote_cpu_sigs", "counter", None),
    ("crypto.remote_rtt_s", "histogram", None),
    # crypto/remote.py — the sidecar's side of a request: counts at parse,
    # the two synchronous event-loop sections, the whole request
    ("sidecar.requests", "counter", None),
    ("sidecar.request_sigs", "counter", None),
    ("sidecar.columnar_sigs", "counter", None),
    ("sidecar.parse_s", "histogram", None),
    ("sidecar.reply_s", "histogram", None),
    ("sidecar.request_s", "histogram", None),
    # crypto/batch_service.py — one dispatch: flatten + dedup scan on the
    # event loop, the thread hop and the backend call, then the mask's
    # scatter, the cache inserts and the futures on the loop again
    ("service.collect_s", "histogram", None),
    ("service.backend_s", "histogram", None),
    ("service.scatter_s", "histogram", None),
    # crypto/scheduler.py — continuous-batching device scheduler. One
    # queue-delay histogram PER REGISTERED SOURCE CLASS: the starvation
    # lint (the graftlint `scheduler` pass) fails if a class in
    # scheduler.SOURCE_CLASSES has no row here.
    ("scheduler.submitted", "counter", None),
    ("scheduler.dispatched_groups", "counter", None),
    ("scheduler.buckets", "counter", None),
    ("scheduler.critical_dispatches", "counter", None),
    ("scheduler.critical_groups", "counter", None),
    ("scheduler.critical_held", "counter", None),
    ("scheduler.size_flushes", "counter", None),
    ("scheduler.grid_flushes", "counter", None),
    ("scheduler.deadline_flushes", "counter", None),
    ("scheduler.preempt_closes", "counter", None),
    ("scheduler.depth", "gauge", None),
    ("scheduler.bucket_size", "histogram", SIZE_BUCKETS),
    ("scheduler.queue_consensus_s", "histogram", None),
    ("scheduler.queue_aggregate_s", "histogram", None),
    ("scheduler.queue_sync_s", "histogram", None),
    ("scheduler.queue_ingress_s", "histogram", None),
    ("scheduler.queue_mempool_s", "histogram", None),
    # ops/pipeline.py — double-buffered async dispatch pipeline (§5.5i).
    # `pipeline.steals` is incremented by crypto/scheduler.py's cross-chip
    # work-stealing bulk dispatch; the rest by DispatchPipeline itself.
    ("pipeline.chunks", "counter", None),
    ("pipeline.depth", "gauge", None),
    ("pipeline.stalls", "counter", None),
    ("pipeline.stall_s", "histogram", None),
    ("pipeline.buffer_reuse", "counter", None),
    ("pipeline.buffer_allocs", "counter", None),
    ("pipeline.steals", "counter", None),
    # consensus/core.py + aggregator.py + synchronizer.py
    ("consensus.proposals", "counter", None),
    ("consensus.votes", "counter", None),
    ("consensus.commits", "counter", None),
    ("consensus.timeouts", "counter", None),
    ("consensus.proposals_suppressed", "counter", None),
    ("consensus.qcs", "counter", None),
    ("consensus.tcs", "counter", None),
    ("consensus.sync_requests", "counter", None),
    ("consensus.sync_retries", "counter", None),
    ("consensus.sync_requests_served", "counter", None),
    ("consensus.sync_abandoned", "counter", None),
    ("consensus.sync_escalations", "counter", None),
    # consensus/synchronizer.py + core.py — batched catch-up range sync
    ("sync.range_requests", "counter", None),
    ("sync.range_served", "counter", None),
    ("sync.range_replies", "counter", None),
    ("sync.range_blocks", "counter", None),
    ("sync.parked_blocks", "counter", None),
    # consensus/reconfig.py — dynamic validator reconfiguration
    ("reconfig.epoch_switches", "counter", None),
    ("reconfig.proposed", "counter", None),
    ("reconfig.rejected", "counter", None),
    ("reconfig.late_applies", "counter", None),
    ("reconfig.epoch", "gauge", None),
    # consensus/reconfig.py + core.py — the epoch-final handoff (§5.5j):
    # wall-withheld certification acts, dead-fork pending drops, the
    # boundary-edge QC commit unlock, and the per-handoff lag histogram
    # (rounds the commit trigger landed past activation-1 — 0 on every
    # healthy handoff, >=1 exactly on a contract violation, which is
    # what the reconfig.handoff telemetry SLO keys on).
    ("reconfig.handoff_holds", "counter", None),
    ("reconfig.handoff_abandoned", "counter", None),
    ("reconfig.handoff_commits", "counter", None),
    ("reconfig.handoff_lag_rounds", "histogram", (0.5, 2.0, 8.0, 32.0)),
    # consensus/overlay.py — region-aware aggregation overlay (§5.5l).
    # vote_frames/timeout_frames count plane frames in BOTH modes (bundle
    # and legacy), so the timeout_storm matrix cells' frames-per-timeout
    # ratio is mode-comparable.
    ("agg.bundles_sent", "counter", None),
    ("agg.bundles_received", "counter", None),
    ("agg.entries_merged", "counter", None),
    ("agg.invalid_entries", "counter", None),
    ("agg.fallbacks", "counter", None),
    ("agg.vote_frames", "counter", None),
    ("agg.timeout_frames", "counter", None),
    # consensus/aggregator.py + core.py — constant-size certificate plane
    # (§5.5o). cert_bytes_committed counts wire bytes of EVERY committed
    # QC/TC (aggregate or entry-list, any crypto mode) so the fleet_rollup
    # bytes_per_committed_round column is mode-comparable across cells.
    ("agg.qcs_formed", "counter", None),
    ("agg.tcs_formed", "counter", None),
    ("agg.partials_merged", "counter", None),
    ("agg.partial_rejects", "counter", None),
    ("agg.cert_bytes_committed", "counter", None),
    # consensus/leader.py + core.py — region-aware election (§5.5p).
    # Counted per committed round whenever a region map is wired, in
    # EVERY elector mode; cross_region_hops_blind is the round-robin
    # counterfactual priced on the same rounds (in-artifact A/B).
    ("elect.rounds", "counter", None),
    ("elect.leader_region_matches", "counter", None),
    ("elect.cross_region_hops", "counter", None),
    ("elect.cross_region_hops_blind", "counter", None),
    ("consensus.round", "gauge", None),
    ("consensus.proposal_to_vote_s", "histogram", None),
    ("consensus.qc_form_s", "histogram", None),
    ("consensus.tc_form_s", "histogram", None),
    ("consensus.commit_latency_s", "histogram", None),
    ("consensus.view_change_s", "histogram", None),
    # mempool/core.py
    ("mempool.payloads_own", "counter", None),
    ("mempool.payloads_other", "counter", None),
    ("mempool.payloads_duplicate", "counter", None),
    ("mempool.payload_bytes", "counter", None),
    ("mempool.payload_requests_served", "counter", None),
    ("mempool.gossip_dropped", "counter", None),
    ("mempool.synthetic_skipped", "counter", None),
    ("mempool.synthetic_skipped_batches", "counter", None),
    ("mempool.requests_clamped", "counter", None),
    ("mempool.front_dropped", "counter", None),
    ("mempool.ingress_lane_txs", "counter", None),
    ("mempool.verify_batch_size", "histogram", SIZE_BUCKETS),
    ("mempool.verify_rtt_s", "histogram", None),
    ("mempool.pool_build_s", "histogram", None),
    ("mempool.pool_triples", "counter", None),
    ("mempool.orphans_requeued", "counter", None),
    # ingress/ — authenticated client plane with admission control
    ("ingress.received", "counter", None),
    ("ingress.admitted", "counter", None),
    ("ingress.shed", "counter", None),
    ("ingress.replays", "counter", None),
    ("ingress.malformed", "counter", None),
    ("ingress.verified_sigs", "counter", None),
    ("ingress.rejected_sigs", "counter", None),
    ("ingress.forwarded", "counter", None),
    ("ingress.lane_depth", "gauge", None),
    ("ingress.retry_after_ms", "histogram", SIZE_BUCKETS),
    ("ingress.verify_batch_size", "histogram", SIZE_BUCKETS),
    ("ingress.latency_s", "histogram", None),
    # proofs/ — commit-proof serving plane (registry + service)
    ("proofs.indexed", "counter", None),
    ("proofs.resolved", "counter", None),
    ("proofs.evicted", "counter", None),
    ("proofs.cert_mismatch", "counter", None),
    ("proofs.queries", "counter", None),
    ("proofs.served", "counter", None),
    ("proofs.unknown", "counter", None),
    ("proofs.subs_shed", "counter", None),
    ("proofs.malformed", "counter", None),
    ("proofs.registry_size", "gauge", None),
    ("proofs.serve_s", "histogram", None),
    ("proofs.proof_bytes", "histogram", SIZE_BUCKETS),
    # network/net.py
    ("net.bytes_sent", "counter", None),
    ("net.frames_sent", "counter", None),
    ("net.bytes_received", "counter", None),
    ("net.frames_received", "counter", None),
    ("net.send_failures", "counter", None),
    ("net.reconnects", "counter", None),
    ("net.dropped_full", "counter", None),
    ("net.decode_errors", "counter", None),
    ("net.backoff_seconds", "counter", None),
    ("net.backoff_drops", "counter", None),
    # network/net.py — per-peer link observatory roll-ups (the per-link
    # detail lives in the PeerLink ledger, not the registry)
    ("net.peer.links", "counter", None),
    ("net.peer.probes_sent", "counter", None),
    ("net.peer.pings_received", "counter", None),
    ("net.peer.pongs_received", "counter", None),
    ("net.peer.rtt_samples", "counter", None),
    # chaos/ — deterministic fault injection & invariant checking
    ("chaos.drops", "counter", None),
    ("chaos.delays", "counter", None),
    ("chaos.duplicates", "counter", None),
    ("chaos.reorders", "counter", None),
    ("chaos.partition_drops", "counter", None),
    ("chaos.unrouted", "counter", None),
    ("chaos.frames", "counter", None),
    ("chaos.forged_votes", "counter", None),
    ("chaos.forged_timeouts", "counter", None),
    ("chaos.equivocations", "counter", None),
    ("chaos.stale_replays", "counter", None),
    ("chaos.withheld_votes", "counter", None),
    ("chaos.crashes", "counter", None),
    ("chaos.restarts", "counter", None),
    ("chaos.late_boots", "counter", None),
    ("chaos.invariant_checks", "counter", None),
    ("chaos.invariant_violations", "counter", None),
    ("chaos.fault_trace_dropped", "counter", None),
    # chaos/trusted_crypto.py — keyed-hash stub signature scheme
    ("chaos.stub_signs", "counter", None),
    ("chaos.stub_verifies", "counter", None),
    ("chaos.stub_rejects", "counter", None),
    # chaos/trusted_crypto.py — aggregate analogue of the stub scheme
    # (TrustedAggScheme): XOR-combine partials, byte-exact recompute verify
    ("chaos.stub_agg_signs", "counter", None),
    ("chaos.stub_agg_verifies", "counter", None),
    ("chaos.stub_agg_rejects", "counter", None),
    # chaos/plan.py WanMatrix via chaos/transport.py — per-region RTT classes
    ("wan.frames", "counter", None),
    ("wan.cross_region_frames", "counter", None),
    # tools/chaos_run.py --matrix — scenario-matrix regression harness
    ("matrix.cells", "counter", None),
    ("matrix.cells_green", "counter", None),
    ("matrix.cells_red", "counter", None),
    ("matrix.regressions", "counter", None),
    # utils/tracing.py — causal tracing + flight recorder
    ("trace.events", "counter", None),
    ("trace.dropped", "counter", None),
    ("trace.dumps", "counter", None),
    ("trace.watchdog_triggers", "counter", None),
    ("trace.frames_tagged", "counter", None),
    ("trace.frames_stripped", "counter", None),
    # utils/telemetry.py — live telemetry plane (delta snapshots, SLO
    # burn-rate alerts, scrape endpoint)
    ("telemetry.snapshots", "counter", None),
    ("telemetry.slo_burn_fired", "counter", None),
    ("telemetry.slo_burn_cleared", "counter", None),
    ("telemetry.scrapes", "counter", None),
    ("telemetry.peer_views", "counter", None),
    # utils/incidents.py — run-level incident ledger (fault→alert→
    # recovery attribution, fleet MTTR accounting, burn budgets)
    ("incident.opened", "counter", None),
    ("incident.attributed", "counter", None),
    ("incident.unattributed", "counter", None),
    ("incident.mttd_s", "histogram", None),
    ("incident.mttr_s", "histogram", None),
    ("incident.budget_burn_s", "histogram", None),
    # ops/timeline.py — the idle account: the device's busy seconds and
    # its idle seconds by cause (float counts, fed at every dump)
    ("timeline.device_busy_s", "counter", None),
    ("timeline.idle_host_s", "counter", None),
    ("timeline.idle_held_s", "counter", None),
    ("timeline.idle_no_request_s", "counter", None),
    # utils/metrics.py meter_loop_cpu — CPU seconds of the event loop's
    # thread (a float count), started by the sidecar's and the node's mains
    ("runtime.loop_cpu_s", "counter", None),
)


def register_defaults(registry: Registry | None = None) -> None:
    r = registry or REGISTRY
    for name, kind, buckets in _DEFAULT_NAMESPACE:
        if kind == "counter":
            r.counter(name)
        elif kind == "gauge":
            r.gauge(name)
        else:
            r.histogram(name, buckets or TIME_BUCKETS_S)


register_defaults()
