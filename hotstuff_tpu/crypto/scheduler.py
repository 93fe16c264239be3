"""Continuous-batching device scheduler with preemptive priority lanes.

Every device-bound verification — QC/TC-critical consensus checks, mempool
bulk, sync/payload re-verification, and client ingress — goes through this
one flush policy. A single queue with a single deadline and one `urgent`
bit has no vocabulary for "ingress is latency-sensitive but not
commit-critical" and no way to size buckets against the device's alignment
grid, so a bulk or ingress flood and a quorum-sized QC check would be
fate-shared into the same coalesced flushes.

This module is the LLM-serving continuous-batching pattern applied to the
verify plane (ROADMAP item 4): typed **sources**, each with a priority
class and latency SLO, feed one admission → bucket → dispatch loop:

  * **Preemptive critical lane.** Consensus-critical groups never wait out
    a lower-class flush timer: any pending critical work is drained and
    dispatched FIRST on every loop pass, bypassing the bulk dispatch bound
    and the pace model entirely. A critical arrival also CLOSES the
    forming bulk bucket early — the formed groups ship right behind it
    instead of restarting their deadline, so preemption never re-delays
    bulk.
  * **The critical lane's dispatch window.** On a gridless backend (the
    nodes' RemoteBackend, CpuBackend, the chaos services) a critical
    dispatch is a sub-millisecond host call and any number may be in
    flight. On a backend with a device grid (`alignment_fn() > 0`: the
    sidecar's TpuBackend) a dispatch is a whole device program that costs
    the same full or empty, and the device runs them one after another: a
    third program in flight only queues behind the other two. So there
    the lane keeps `bulk_concurrency` critical dispatches in flight (the
    DispatchPipeline window: one program on the device, one staged behind
    it) on an account of its OWN, which bulk work never touches. Groups
    that arrive while it is full stay in the lane and ride the next
    dispatch TOGETHER, started the moment the older in-flight one ends:
    no timer, nothing held that could have run, and the wait is bounded
    by one dispatch. An in-process TpuBackend node has a grid too: its
    quorum-sized checks take the backend's CPU fast path in well under a
    millisecond, and a third simultaneous critical group there waits for
    the first of two to end and joins the next.
  * **Alignment-grid bucket sizing.** Bulk buckets are sized dynamically
    against the backend's bucket alignment (`TpuBackend.bucket_alignment`:
    `lane × ndev` on a mesh — parallel/mesh.py's `mesh_alignment` — or the
    single-chip `min_bucket`): once a full grid row of work is pending the
    bucket closes, so the device pays its padded lanes with real work in
    them. Backends with no grid (CPU, pure-python) fall back to
    deadline/size flushing alone.
  * **Continuous refill.** Bucket formation runs concurrently with the
    bounded in-flight dispatches: as one bucket dispatches, the next forms
    from whatever sources have work, so the device never idles between
    heterogeneous batches. Buckets are lane-ordered (sync before ingress
    before mempool) but may mix classes — per-group queueing delay is
    attributed to each group's own lane regardless.
  * **Cross-chip work stealing** (`n_backends > 1`). The owning service
    may register sibling shard backends (one TpuBackend per chip/mesh
    leg): each backend gets its own `bulk_concurrency` in-flight account
    mirroring its DispatchPipeline window (ops/pipeline.py), and a bulk
    bucket dispatches to the FIRST backend with a free slot, home (0)
    preferred — one service no longer feeds one backend while sibling
    pipelines idle. A non-home dispatch counts into `pipeline.steals`.
    Critical work always rides home (the committee-registered backend).
    Chaos/virtual-time services run `inline=True`, which forces
    n_backends=1 — bit-identical to the pre-stealing loop.

The scheduler owns admission, per-lane queueing, and bucket formation;
the owning BatchVerificationService stays the dispatch executor (dedup
cache, committee tagging, backend call, future resolution) — its public
`verify_group` API is a thin source-registration façade over `submit()`.

Observability: per-lane queueing-delay histograms (`scheduler.queue_<lane>_s`)
plus bucket/flush counters in the `scheduler.*` namespace, a per-service
`LaneStats` reservoir (the chaos expectations read p50/p99 from it), and `lane=`/`queue_s=` fields on every traced group's
`verify.batch` event so `tools/trace_report.py` attributes queueing delay
per class.

Deterministic by construction: no wall-clock reads (event-loop time only),
no threads of its own — under the chaos VirtualTimeLoop with `inline=True`
dispatch, a scheduled run replays bit-for-bit. `pace_s_per_sig` models
finite device occupancy in VIRTUAL time (a bucket of n signatures holds
the bulk pipeline for n×pace seconds), which is what makes queueing — and
therefore preemption — observable under a clock where Python work costs
zero virtual seconds.

Dependency-free: stdlib + utils.metrics/tracing only (no jax, no crypto).
"""

from __future__ import annotations

import asyncio
import logging
from collections import deque
from dataclasses import dataclass
from typing import Callable

from ..utils import metrics

log = logging.getLogger("hotstuff.crypto")

__all__ = [
    "SourceClass",
    "SOURCE_CLASSES",
    "CONSENSUS",
    "AGGREGATE",
    "SYNC",
    "INGRESS",
    "MEMPOOL",
    "SchedulerConfig",
    "LaneStats",
    "DeviceScheduler",
    "resolve_source",
    "note_queue_delay",
    "drain_order",
]


@dataclass(frozen=True, slots=True)
class SourceClass:
    """One typed verification source: a priority class + latency SLO.

    `priority` orders lane draining (lower drains first); `slo_s` is the
    published queueing-delay target the per-lane histograms are judged
    against (advisory — reported, never enforced); `max_delay_s` bounds
    how long a forming bucket may wait for more work once this class has
    a group pending; `preemptive` marks the critical lane (immediate
    dispatch, bypasses the bulk bound, closes forming buckets early)."""

    name: str
    priority: int
    slo_s: float
    max_delay_s: float
    preemptive: bool = False


# The five registered sources (ISSUE 7 / ROADMAP item 4; ISSUE 13 filled
# the slot PR 7 left open). QC/TC/vote/proposal checks gate round
# advancement — preemptive, no flush timer. AGGREGATE is the overlay's
# partial-bundle verification (consensus/overlay.py): quorum-forming but
# mergeable-in-batches, so it rides the batched device path at a priority
# strictly between consensus and sync (a stalled round's bundles must not
# queue behind catch-up or ingress floods). Sync/payload re-verification
# un-stalls consensus availability — tight deadline. Ingress is client-
# latency-sensitive bulk; mempool is pure measurement load and starves
# first under pressure (the lane contract, mirroring ingress admission).
CONSENSUS = SourceClass("consensus", 0, slo_s=0.002, max_delay_s=0.0, preemptive=True)
AGGREGATE = SourceClass("aggregate", 1, slo_s=0.010, max_delay_s=0.0005)
SYNC = SourceClass("sync", 2, slo_s=0.020, max_delay_s=0.001)
INGRESS = SourceClass("ingress", 3, slo_s=0.100, max_delay_s=0.002)
MEMPOOL = SourceClass("mempool", 4, slo_s=0.500, max_delay_s=0.004)

SOURCE_CLASSES: dict[str, SourceClass] = {
    c.name: c for c in (CONSENSUS, AGGREGATE, SYNC, INGRESS, MEMPOOL)
}


def resolve_source(source: str | None, urgent: bool) -> SourceClass:
    """Map a verify_group call to its SourceClass. Explicit `source` wins;
    the legacy `urgent` bit keeps un-migrated callers working (urgent ==
    consensus-critical, everything else is mempool bulk)."""
    if source is not None:
        try:
            return SOURCE_CLASSES[source]
        except KeyError:
            raise ValueError(
                f"unknown verification source {source!r}; registered: "
                f"{sorted(SOURCE_CLASSES)}"
            ) from None
    return CONSENSUS if urgent else MEMPOOL


# Sub-resolution deadline guard (the utils/actors.py Timer.RESOLUTION_S
# class of livelock, observed live on the chaos virtual-time loop once
# the overlay's `aggregate` lane made batched deadlines common in
# consensus scenarios): when a pending deadline lands WITHIN the event
# loop clock's resolution of `now` (vtime jumps overshoot by 1e-9), the
# armed wait_for timer fires without the clock advancing, form_bucket
# still judges the deadline "strictly in the future", and the run loop
# re-arms forever at a frozen virtual instant. Deadlines within this
# bound count as DUE — in form_bucket and the run loop alike (the two
# must agree, or the loop waits for a deadline the bucket logic already
# considers expired). One microsecond is far below any max_delay_s.
RESOLUTION_S = 1e-6

_M_SUBMITTED = metrics.counter("scheduler.submitted")
# Cross-chip work stealing (ISSUE 9 / ROADMAP items 1+4): a bulk bucket
# dispatched to any backend other than the home backend 0 counts here —
# the pipeline.* namespace because the free-slot model mirrors each
# backend's DispatchPipeline window (ops/pipeline.py).
_M_STEALS = metrics.counter("pipeline.steals")
_M_DISPATCHED = metrics.counter("scheduler.dispatched_groups")
_M_BUCKETS = metrics.counter("scheduler.buckets")
_M_CRITICAL = metrics.counter("scheduler.critical_dispatches")
# Groups shipped on critical dispatches, and those of them that found the
# critical lane's dispatch window full and waited for a slot (gridded
# backends only): groups over dispatches is how far the window coalesces.
_M_CRITICAL_GROUPS = metrics.counter("scheduler.critical_groups")
_M_CRITICAL_HELD = metrics.counter("scheduler.critical_held")
_M_SIZE_FLUSHES = metrics.counter("scheduler.size_flushes")
_M_GRID_FLUSHES = metrics.counter("scheduler.grid_flushes")
_M_DEADLINE_FLUSHES = metrics.counter("scheduler.deadline_flushes")
_M_PREEMPT_CLOSES = metrics.counter("scheduler.preempt_closes")
_M_DEPTH = metrics.gauge("scheduler.depth")
_M_BUCKET_SIZE = metrics.histogram("scheduler.bucket_size", metrics.SIZE_BUCKETS)
# Per-lane queueing delay (submit -> dequeue-into-a-bucket). The f-string
# keeps lane names and histogram rows in lockstep; the graftlint
# `scheduler` pass (python -m tools.graftlint)
# separately asserts every registered class has its row in the canonical
# namespace (the starvation lint's schema half).
_QUEUE_HIST = {
    name: metrics.histogram(f"scheduler.queue_{name}_s")
    for name in SOURCE_CLASSES
}


def note_queue_delay(lane_stats: "LaneStats", source: str, queue_s: float) -> None:
    """Record one group's queueing delay into the lane's global histogram
    and the service-local reservoir."""
    hist = _QUEUE_HIST.get(source)
    if hist is not None:
        hist.record(queue_s)
    lane_stats.note(source, queue_s)


class LaneStats:
    """Per-service per-lane queueing-delay reservoir.

    The global `scheduler.queue_<lane>_s` histograms aggregate across every
    service in the process; chaos scenarios need PER-SERVICE percentiles
    (one node's critical lane), so each BatchVerificationService keeps its
    own bounded sample ring here, fed by the scheduler's dequeue.

    The ring ROTATES at CAP (oldest evicted) rather than saturating: the
    telemetry plane (utils/telemetry.py) windows per-snapshot deltas off
    `total()`'s monotonic count, and a saturating list would freeze its
    live lane SLOs for the rest of the process once a long-running node
    crossed CAP. `summary()` therefore describes the most recent CAP
    samples — every chaos scenario stays well under that."""

    CAP = 65_536  # samples retained per lane (rotating window)

    def __init__(self) -> None:
        self._samples: dict[str, deque] = {
            name: deque(maxlen=self.CAP) for name in SOURCE_CLASSES
        }
        self._total: dict[str, int] = {name: 0 for name in SOURCE_CLASSES}

    def note(self, lane: str, queue_s: float) -> None:
        ring = self._samples.get(lane)
        if ring is None:
            ring = self._samples.setdefault(lane, deque(maxlen=self.CAP))
        ring.append(queue_s)
        self._total[lane] = self._total.get(lane, 0) + 1

    def lanes(self) -> list[str]:
        return list(self._samples)

    def total(self, lane: str) -> int:
        """Monotonic count of samples EVER noted for the lane — the
        telemetry plane's cursor basis, immune to ring rotation."""
        return self._total.get(lane, 0)

    def samples(self, lane: str) -> list[float]:
        """A copy of the lane's retained samples, oldest first (the last
        `total() - cursor` entries are the ones a telemetry window has
        not seen yet)."""
        return list(self._samples.get(lane, ()))

    def tail(self, lane: str, n: int) -> list[float]:
        """The most recent min(n, retained) samples, oldest first —
        O(n), so a telemetry window never pays a full-ring copy just to
        read a few fresh entries."""
        ring = self._samples.get(lane)
        if not ring or n <= 0:
            return []
        if n >= len(ring):
            return list(ring)
        out = [x for _, x in zip(range(n), reversed(ring))]
        out.reverse()
        return out

    def summary(self) -> dict[str, dict]:
        """{lane: {count, p50_ms, p99_ms, max_ms}} for lanes that saw work."""
        out = {}
        for lane, samples in self._samples.items():
            if not samples:
                continue
            ordered = sorted(samples)
            out[lane] = {
                "count": len(ordered),
                "p50_ms": round(metrics.percentile(ordered, 0.50) * 1e3, 3),
                "p99_ms": round(metrics.percentile(ordered, 0.99) * 1e3, 3),
                "max_ms": round(ordered[-1] * 1e3, 3),
            }
        return out


@dataclass(slots=True)
class SchedulerConfig:
    """Knobs beyond what the owning service already carries.

    `bulk_concurrency` bounds in-flight NON-critical buckets (2 = double
    buffering: stage the next bucket while one is on the device; more
    slots only add host-thread contention against the critical lane) and,
    on a backend with a device grid, in-flight critical dispatches on
    their own account (the same window: a program costs the same there
    whichever lane filled it).
    `pace_s_per_sig` is the virtual device-occupancy model for chaos runs
    (0 = backend-bound, production)."""

    bulk_concurrency: int = 2
    pace_s_per_sig: float = 0.0


class _Lane:
    __slots__ = ("cls", "queue", "enqueued", "dispatched")

    def __init__(self, cls: SourceClass) -> None:
        self.cls = cls
        self.queue: deque = deque()
        self.enqueued = 0
        self.dispatched = 0


class DeviceScheduler:
    """The admission → bucket → dispatch loop.

    `dispatch(groups, total, critical)` is the owning service's executor
    hook (BatchVerificationService._spawn_dispatch): it must return the
    spawned task, whose completion frees a bulk slot (or, on a gridded
    backend, a slot of the critical lane's window). Groups only need
    `.source`, `.t_submit`, `.t_dequeue` and `__len__` — the scheduler
    never looks at messages or futures, which is what keeps the lint's
    drain-order simulation (and unit tests) dependency-free."""

    def __init__(
        self,
        dispatch: Callable[[list, int, bool], "asyncio.Task"],
        *,
        max_batch: int = 8192,
        alignment_fn: Callable[[], int] | None = None,
        config: SchedulerConfig | None = None,
        lane_stats: LaneStats | None = None,
        classes: tuple[SourceClass, ...] | None = None,
        n_backends: int = 1,
    ) -> None:
        self._dispatch = dispatch
        self.max_batch = max_batch
        self._alignment_fn = alignment_fn or (lambda: 0)
        self.config = config or SchedulerConfig()
        self.lane_stats = lane_stats or LaneStats()
        classes = classes or tuple(SOURCE_CLASSES.values())
        ordered = sorted(classes, key=lambda c: c.priority)
        self._critical = [c.name for c in ordered if c.preemptive]
        self._batched = [c.name for c in ordered if not c.preemptive]
        self.lanes: dict[str, _Lane] = {c.name: _Lane(c) for c in ordered}
        # Cross-chip work stealing: one bulk in-flight account per
        # dispatch target. Backend 0 is HOME (the committee-registered
        # primary every critical dispatch rides); targets 1..n-1 are the
        # steal shards — a bulk bucket goes to the first backend with a
        # free slot, home preferred, so one service no longer feeds one
        # backend while sibling pipelines idle. `bulk_concurrency` slots
        # per backend mirror each backend's DispatchPipeline window.
        # With n_backends == 1 the accounting and the dispatch-hook
        # arity are EXACTLY the pre-stealing behavior (the chaos
        # inline/virtual-time determinism contract, §5.5i).
        self.n_backends = max(1, n_backends)
        self._inflight = [0] * self.n_backends
        # The critical lane's own window (module docstring): its
        # dispatches in flight, counted on gridded backends only.
        self._inflight_critical = 0
        self._wake: asyncio.Event | None = None  # bound lazily to the loop
        self.stats = {
            "submitted": 0,
            "buckets": 0,
            "critical_dispatches": 0,
            "preempt_closes": 0,
            "steals": 0,
        }

    @property
    def _inflight_bulk(self) -> int:
        """Total bulk dispatches in flight across every backend."""
        return sum(self._inflight)

    def _pick_backend(self) -> int | None:
        """First backend with a free bulk slot, home (0) preferred; None
        while every pipeline window is full (the loop then waits)."""
        for idx in range(self.n_backends):
            if self._inflight[idx] < self.config.bulk_concurrency:
                return idx
        return None

    # -- admission -----------------------------------------------------------

    def submit(self, group) -> None:
        """Admit one group into its lane (synchronous — lanes are
        unbounded; backpressure stays with the callers, e.g.
        ingress admission and the mempool's verify semaphores)."""
        lane = self.lanes[group.source]
        lane.queue.append(group)
        lane.enqueued += 1
        self.stats["submitted"] += 1
        _M_SUBMITTED.inc()
        if lane.cls.preemptive and self._critical_window_full():
            # It waits for a slot. (A critical group that finds one free
            # ships on the loop's next pass: the window fills only by a
            # dispatch, and a dispatch takes everything the lane holds.)
            _M_CRITICAL_HELD.inc()
        _M_DEPTH.set(self.depth())
        if self._wake is not None:
            self._wake.set()

    def depth(self) -> int:
        return sum(len(lane.queue) for lane in self.lanes.values())

    # -- bucket formation (pure: unit-testable, reused by the lint) ----------

    def _take(self, group, now: float, bucket: list) -> None:
        group.t_dequeue = now
        lane = self.lanes[group.source]
        lane.dispatched += 1
        note_queue_delay(self.lane_stats, group.source, max(0.0, now - group.t_submit))
        bucket.append(group)

    def drain_critical(self, now: float) -> list:
        """Pop EVERY pending preemptive-lane group (they coalesce into one
        hot bucket — simultaneous QC + vote checks still flush together)."""
        out: list = []
        for name in self._critical:
            queue = self.lanes[name].queue
            while queue:
                self._take(queue.popleft(), now, out)
        return out

    def form_bucket(self, now: float, force: bool = False) -> tuple[list, str] | None:
        """Close and return one batched-lane bucket, or None if the loop
        should keep waiting. Close conditions, in order:

          * `force`   — a critical dispatch just preempted the forming
                        bucket: ship what has accumulated (preempt close).
          * size      — pending work fills max_batch.
          * grid      — a full device alignment row is pending (zero pad
                        waste; alignment 0 disables this trigger).
          * deadline  — the oldest pending group aged past its class's
                        max_delay_s (bounds p99 at low rates, and bounds
                        starvation of the lowest lane: its deadline forces
                        a flush that drains lanes in priority order).

        Groups are indivisible (one future per group), so the last group
        taken may overshoot the grid target; it never overshoots max_batch
        unless it is single-handedly larger than max_batch."""
        pending = sum(
            len(g) for name in self._batched for g in self.lanes[name].queue
        )
        if pending == 0:
            return None
        reason = None
        target = self.max_batch
        if force:
            reason = "preempt"
        elif pending >= self.max_batch:
            reason = "size"
        else:
            align = self._alignment_fn()
            if align > 0 and pending >= align:
                # Close at the largest full grid multiple and leave the
                # remainder forming: the dispatched bucket pads zero lanes,
                # and the residue's own deadline still bounds its wait.
                reason = "grid"
                target = (pending // align) * align
            else:
                deadline = self._next_deadline()
                if deadline is not None and now >= deadline - RESOLUTION_S:
                    reason = "deadline"
        if reason is None:
            return None
        bucket: list = []
        total = 0
        for name in self._batched:
            queue = self.lanes[name].queue
            while queue and (total < target or not bucket):
                g = queue.popleft()
                self._take(g, now, bucket)
                total += len(g)
            if total >= target:
                break
        return bucket, reason

    def _next_deadline(self) -> float | None:
        """Earliest (t_submit + class max_delay) across pending batched
        groups — FIFO lanes mean only each lane's head matters."""
        deadline = None
        for name in self._batched:
            lane = self.lanes[name]
            if lane.queue:
                d = lane.queue[0].t_submit + lane.cls.max_delay_s
                if deadline is None or d < deadline:
                    deadline = d
        return deadline

    # -- dispatch loop -------------------------------------------------------

    def note_bulk_done(self, _task=None, backend: int = 0) -> None:
        """Done-callback for non-critical dispatch tasks: frees the
        backend's bulk slot and wakes the loop so the next bucket can
        ship (continuous refill)."""
        self._inflight[backend] -= 1
        if self._wake is not None:
            self._wake.set()

    def note_critical_done(self, _task=None) -> None:
        """Done-callback for a gridded backend's critical dispatch tasks
        (however they ended): frees the slot of the critical lane's window
        and wakes the loop, which ships everything the lane holds."""
        self._inflight_critical -= 1
        if self._wake is not None:
            self._wake.set()

    def _critical_window_full(self) -> bool:
        """True while a backend with a device grid has `bulk_concurrency`
        critical dispatches in flight (the account stays 0 without a grid)."""
        return (
            self._inflight_critical >= self.config.bulk_concurrency
            and self._alignment_fn() > 0
        )

    def _ship_critical(self, now: float) -> bool:
        # Bypasses the bulk bound AND the pace model: critical work is
        # never delayed by a lower-class flush timer or a busy bulk
        # pipeline. Where a dispatch is a device program (a backend with a
        # grid) the lane has a window of its own: with it full the groups
        # stay in the lane and share the dispatch that starts when the
        # older one in flight ends (module docstring).
        if self._critical_window_full():
            return False
        hot = self.drain_critical(now)
        if not hot:
            return False
        self.stats["critical_dispatches"] += 1
        _M_CRITICAL.inc()
        _M_CRITICAL_GROUPS.inc(len(hot))
        _M_DISPATCHED.inc(len(hot))
        _M_DEPTH.set(self.depth())
        task = self._dispatch(hot, sum(len(g) for g in hot), True)
        if self._alignment_fn() > 0:
            self._inflight_critical += 1
            task.add_done_callback(self.note_critical_done)
        return True

    async def _pace_busy(self, dur: float, loop) -> None:
        """Hold the bulk pipeline busy for `dur` seconds of loop time
        (virtual under chaos) without ever delaying the critical lane:
        wake-ups inside the window ship any pending critical work, then
        the remaining occupancy elapses."""
        end = loop.time() + dur
        while True:
            remaining = end - loop.time()
            if remaining <= RESOLUTION_S:
                return  # sub-resolution remainder: same livelock class
            self._wake.clear()
            try:
                await asyncio.wait_for(self._wake.wait(), remaining)
            except asyncio.TimeoutError:
                return
            self._ship_critical(loop.time())

    async def run(self) -> None:
        """The single admission → bucket → dispatch loop. Spawned by the
        owning service (actors.spawn, so a chaos crash-restart of a node
        tears it down with the rest of the node's task tree)."""
        loop = asyncio.get_running_loop()
        if self._wake is None:
            self._wake = asyncio.Event()
        pace = self.config.pace_s_per_sig
        while True:
            now = loop.time()
            # 1. Critical lane first, always; remember whether it preempted
            #    a forming (non-empty, not-yet-closed) batched backlog.
            preempted = self._ship_critical(now)
            # 2. One batched bucket, if any backend has a free slot and a
            #    close condition holds (a preempt close ships the formed
            #    groups immediately so the critical jump never re-delays
            #    them). Home backend preferred; a bucket shipped to a
            #    sibling shard while home's pipeline window is full is a
            #    STEAL (pipeline.steals).
            target = self._pick_backend()
            if target is not None:
                formed = self.form_bucket(now, force=preempted)
                if formed is not None:
                    bucket, reason = formed
                    total = sum(len(g) for g in bucket)
                    self.stats["buckets"] += 1
                    _M_BUCKETS.inc()
                    _M_DISPATCHED.inc(len(bucket))
                    _M_BUCKET_SIZE.record(total)
                    _M_DEPTH.set(self.depth())
                    if reason == "preempt":
                        self.stats["preempt_closes"] += 1
                        _M_PREEMPT_CLOSES.inc()
                    elif reason == "size":
                        _M_SIZE_FLUSHES.inc()
                    elif reason == "grid":
                        _M_GRID_FLUSHES.inc()
                    else:
                        _M_DEADLINE_FLUSHES.inc()
                    self._inflight[target] += 1
                    if target != 0:
                        self.stats["steals"] += 1
                        _M_STEALS.inc()
                    if self.n_backends == 1:
                        # Pre-stealing arity: single-backend dispatch
                        # hooks (and the lint's drain-order stub) never
                        # see a target index.
                        task = self._dispatch(bucket, total, False)
                        task.add_done_callback(self.note_bulk_done)
                    else:
                        task = self._dispatch(bucket, total, False, target)
                        task.add_done_callback(
                            lambda t, b=target: self.note_bulk_done(t, b)
                        )
                    if pace > 0.0:
                        # Virtual device-occupancy model (chaos): the bulk
                        # pipeline is busy for total*pace seconds — but the
                        # sleep is PREEMPTIBLE: a critical arrival ships
                        # mid-occupancy, then the remainder elapses.
                        await self._pace_busy(total * pace, loop)
                    continue
            # 3. Nothing dispatchable: wait for new work, a freed bulk
            #    slot, or the earliest pending deadline. form_bucket only
            #    returns None while every pending deadline is more than
            #    RESOLUTION_S in the future, so the armed timeout always
            #    exceeds the loop clock's resolution (no sub-resolution
            #    re-arm livelock under the virtual clock — see
            #    RESOLUTION_S above).
            self._wake.clear()
            if self.depth() > 0 and self._ship_critical(loop.time()):
                continue  # raced a critical submit against the clear
            deadline = self._next_deadline()
            waitable = self._pick_backend() is not None
            timeout = None
            if deadline is not None and waitable:
                # form_bucket returned None, so the deadline is more than
                # RESOLUTION_S away; the floor keeps the armed timer past
                # the loop clock's resolution regardless (see RESOLUTION_S).
                timeout = max(deadline - loop.time(), RESOLUTION_S)
            try:
                await asyncio.wait_for(self._wake.wait(), timeout)
            except asyncio.TimeoutError:
                pass

    def summary(self) -> dict:
        """Structured per-lane snapshot (chaos reports embed one per node)."""
        return {
            "backends": self.n_backends,
            "inflight": list(self._inflight),
            "lanes": {
                name: {
                    "priority": lane.cls.priority,
                    "slo_ms": round(lane.cls.slo_s * 1e3, 3),
                    "enqueued": lane.enqueued,
                    "dispatched": lane.dispatched,
                    "depth": len(lane.queue),
                }
                for name, lane in self.lanes.items()
            },
            "queue_delay": self.lane_stats.summary(),
            **self.stats,
        }


# ---------------------------------------------------------------------------
# Starvation lint support (the graftlint `scheduler` pass)


class _StubGroup:
    """Minimal group shape for the drain-order simulation: the scheduler's
    formation logic only reads source/t_submit/len()."""

    __slots__ = ("source", "t_submit", "t_dequeue", "n")

    def __init__(self, source: str, t_submit: float, n: int = 1) -> None:
        self.source = source
        self.t_submit = t_submit
        self.t_dequeue = 0.0
        self.n = n

    def __len__(self) -> int:
        return self.n


def drain_order(classes: tuple[SourceClass, ...] | None = None) -> list[str]:
    """Simulate the loop's selection over one group per registered class
    with NO further arrivals, advancing a synthetic clock past each pending
    deadline, and return the lane names in the order their groups were
    dequeued. A registered class missing from the result can be enqueued
    but never selected — the starvation condition the graftlint
    `scheduler` pass
    fails the build on (rc 1)."""
    sched = DeviceScheduler(lambda groups, total, critical: None)
    classes = classes or tuple(SOURCE_CLASSES.values())
    now = 0.0
    for cls in classes:
        sched.submit(_StubGroup(cls.name, now))
    order: list[str] = []
    for _ in range(4 * len(classes) + 4):  # bounded: no arrivals, must drain
        for g in sched.drain_critical(now):
            order.append(g.source)
        formed = sched.form_bucket(now)
        if formed is not None:
            order.extend(g.source for g in formed[0])
        if sched.depth() == 0:
            break
        deadline = sched._next_deadline()
        now = (deadline if deadline is not None else now) + 1e-6
    return order
