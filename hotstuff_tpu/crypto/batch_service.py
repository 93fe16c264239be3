"""BatchVerificationService: the verification façade over the device
scheduler.

The north-star constraint (BASELINE.json): TPU batch verification must not
regress consensus latency — QC formation blocks round advancement, so
per-vote verification cannot wait for a large batch to fill. This service
generalises the reference's SignatureService request/oneshot seam
(crypto/src/lib.rs:226-252) to verification: callers submit GROUPS of
(message, key, signature) triples (a QC's votes, one synthetic payload
batch, or a single vote), DECLARE their source class (`source=`:
consensus-critical / sync / ingress / mempool-bulk — crypto/scheduler.py),
and await a per-item validity mask.

Batching policy lives in the continuous-batching DeviceScheduler
(crypto/scheduler.py): typed priority lanes, a preemptive critical lane,
alignment-grid bucket sizing, continuous refill. This class remains the
DISPATCH EXECUTOR — dedup cache, committee tagging, the backend call,
future resolution — and the thin source-registration façade callers see.
The pre-scheduler single-queue flush heuristics survive as
`use_scheduler=False` (`_run_legacy`), kept as the measured baseline for
`bench.py --scheduler-ab`.

The backend call runs in a worker thread so the TPU dispatch never blocks
the event loop (the mempool/consensus cores keep processing while a batch
is in flight — the same pipelining the reference gets from tokio). Groups
are enqueued whole (one lane entry, one future per group), so per-item
asyncio overhead is O(1) per group, not O(n) — at 100k+ sigs/s the Python
queue would otherwise dominate the TPU kernel.
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Sequence

from ..ops import timeline
from ..utils import metrics, tracing
from .backend import CryptoBackend, get_backend
from .primitives import PublicKey, Signature
from .scheduler import (
    DeviceScheduler,
    LaneStats,
    SchedulerConfig,
    note_queue_delay,
    resolve_source,
)

log = logging.getLogger("hotstuff.crypto")

_M_DEDUP_HITS = metrics.counter("verifier.dedup_hits")
_M_DEDUP_MISSES = metrics.counter("verifier.dedup_misses")
_M_DEDUP_INSERTS = metrics.counter("verifier.dedup_inserts")
_M_DEDUP_EVICTIONS = metrics.counter("verifier.dedup_evictions")
_M_COLLECT = metrics.histogram("service.collect_s")
_M_BACKEND = metrics.histogram("service.backend_s")


class VerifiedSigCache:
    """Bounded LRU of (message, pk, sig) triples that VERIFIED.

    Every vote signature is checked 2-3x over its lifetime: once on vote
    arrival, again inside every QC that carries it (`QC.verify`), and again
    when that QC rides a Block/Timeout. A hit here short-circuits the
    backend call entirely. Only successes are cached (a miss proves
    nothing), and the triple is the full (message, key, signature) — a
    forged signature over the same digest can never alias a cached entry.

    Thread-safe: the consensus event loop seeds it while backend dispatch
    worker threads look entries up.
    """

    __slots__ = ("maxsize", "_entries", "_lock")

    def __init__(self, maxsize: int = 65536) -> None:
        if maxsize <= 0:
            raise ValueError("dedup cache needs maxsize >= 1")
        self.maxsize = maxsize
        self._entries: OrderedDict[tuple[bytes, bytes, bytes], None] = (
            OrderedDict()
        )
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._entries)

    def hit(self, message: bytes, key: PublicKey, sig: Signature) -> bool:
        """True iff this exact triple previously verified (refreshes LRU
        recency); counts into verifier.dedup_hits/misses."""
        k = (message, key.data, sig.data)
        with self._lock:
            if k in self._entries:
                self._entries.move_to_end(k)
                _M_DEDUP_HITS.inc()
                return True
        _M_DEDUP_MISSES.inc()
        return False

    def add(self, message: bytes, key: PublicKey, sig: Signature) -> None:
        """Record a VERIFIED triple; evicts least-recently-used past
        maxsize (memory stays bounded at ~128 B/entry)."""
        k = (message, key.data, sig.data)
        with self._lock:
            if k in self._entries:
                self._entries.move_to_end(k)
                return
            self._entries[k] = None
            _M_DEDUP_INSERTS.inc()
            while len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
                _M_DEDUP_EVICTIONS.inc()


@dataclass
class _Group:
    messages: list[bytes]
    keys: list[PublicKey]
    signatures: list[Signature]
    urgent: bool
    committee: bool = False
    # dedup=False opts the group out of the verified-signature cache: the
    # mempool's SYNTHETIC workload draws cyclically from a fixed pool of
    # pre-signed triples, and caching those would make the benchmark
    # measure the cache instead of the backend.
    dedup: bool = True
    # Causal trace id (utils/tracing.py): set for consensus groups so the
    # flight recorder can attribute this batch's verification cost to the
    # block whose QC/vote/proposal it checks.
    trace: str | None = None
    # Source class (crypto/scheduler.py) + queueing timestamps: t_submit is
    # stamped at admission, t_dequeue when a bucket (or legacy flush) takes
    # the group — their difference is the per-lane queueing delay the
    # scheduler metrics and verify.batch trace events attribute.
    source: str = "mempool"
    t_submit: float = 0.0
    t_dequeue: float = 0.0
    # The sidecar's per-process request number (crypto/remote.py), 0 for
    # a group that is no wire request: `service.collect` names the
    # requests it merged by it.
    rid: int = 0
    future: asyncio.Future = field(default_factory=lambda: asyncio.get_running_loop().create_future())

    def __len__(self) -> int:
        return len(self.messages)


class BatchVerificationService:
    def __init__(
        self,
        backend: CryptoBackend | None = None,
        max_batch: int = 8192,
        max_delay: float = 0.002,
        max_concurrent_dispatches: int = 4,
        dedup_cache_size: int = 65536,
        inline: bool = False,
        use_scheduler: bool = True,
        scheduler_config: SchedulerConfig | None = None,
        steal_backends: Sequence[CryptoBackend] | None = None,
    ) -> None:
        self._backend = backend
        self.max_batch = max_batch
        self.max_delay = max_delay
        # Cross-chip work stealing (crypto/scheduler.py): sibling shard
        # backends bulk buckets may be stolen to when the home backend's
        # pipeline window is full. Backend 0 (the `backend` arg) stays
        # home for every critical dispatch and all legacy-loop flushes.
        # inline=True (the chaos virtual-time mode) FORCES stealing off:
        # which backend a bucket lands on must not depend on wall-clock
        # thread timing when a scenario replays bit-for-bit (§5.5i).
        self._steal_backends: list[CryptoBackend] = (
            [] if inline else list(steal_backends or ())
        )
        # inline=True runs the backend call ON the event loop instead of a
        # worker thread. Production keeps the thread (a TPU dispatch must
        # not block consensus timers); the chaos runner opts in because its
        # pure-python backend is millisecond-cheap and thread scheduling is
        # the one nondeterminism its virtual-time replay cannot control.
        self.inline = inline
        # Verified-signature dedup: set dedup_cache_size=0 to disable
        # (the bench A/B switch and the uncached-baseline tests).
        self.dedup: VerifiedSigCache | None = (
            VerifiedSigCache(dedup_cache_size) if dedup_cache_size else None
        )
        self._queue: asyncio.Queue[_Group] = asyncio.Queue()
        self._task: asyncio.Task | None = None
        # Per-lane queueing-delay reservoir, fed by BOTH flush paths (the
        # scheduler's dequeue and the legacy loop) — the bench A/B and the
        # chaos scheduler expectations read per-service p50/p99 from here.
        self.lane_stats = LaneStats()
        # The continuous-batching device scheduler (crypto/scheduler.py) is
        # the default flush policy; use_scheduler=False keeps the legacy
        # single-queue heuristics as the measured A/B baseline.
        self.scheduler: DeviceScheduler | None = (
            DeviceScheduler(
                self._spawn_dispatch,
                max_batch=max_batch,
                alignment_fn=self._bucket_alignment,
                config=scheduler_config,
                lane_stats=self.lane_stats,
                n_backends=1 + len(self._steal_backends),
            )
            if use_scheduler
            else None
        )
        # Flushes dispatch CONCURRENTLY (bounded): an urgent 3-signature QC
        # check must not wait out a multi-thousand-signature workload batch
        # already in flight on the device (backends route small batches to
        # the CPU fast path, so the urgent flush completes in microseconds
        # while the big dispatch is still on the wire; urgent dispatches
        # never acquire this semaphore). With steal backends configured
        # the bound must cover every backend window the scheduler can
        # legitimately fill (bulk_concurrency per backend) — otherwise
        # the service-global semaphore silently caps stealing below the
        # per-backend accounting that admitted it. Without steal
        # backends the caller's max_concurrent_dispatches stands as-is.
        dispatch_bound = max_concurrent_dispatches
        if self.scheduler is not None and self._steal_backends:
            dispatch_bound = max(
                dispatch_bound,
                self.scheduler.config.bulk_concurrency
                * (1 + len(self._steal_backends)),
            )
        self._dispatch_sem = asyncio.Semaphore(dispatch_bound)
        self._dispatches: set[asyncio.Task] = set()
        self.stats = {
            "flushes": 0,
            "size_flushes": 0,
            "urgent_flushes": 0,
            "verified": 0,
        }

    def _ensure_task(self) -> None:
        if self._task is None or self._task.done():
            # actors.spawn (not bare create_task): the service task then
            # joins the caller's SpawnScope, so a chaos crash-restart of a
            # node tears down its verification flush loop too.
            from ..utils.actors import spawn

            loop = (
                self.scheduler.run()
                if self.scheduler is not None
                else self._run_legacy()
            )
            self._task = spawn(loop, name="batch-verification-service")

    @property
    def backend(self) -> CryptoBackend:
        return self._backend or get_backend()

    def _bucket_alignment(self) -> int:
        """The device bucket grid the scheduler sizes bulk buckets against
        (TpuBackend.bucket_alignment; 0 for gridless backends)."""
        return getattr(self.backend, "bucket_alignment", 0)

    # -- submission API ------------------------------------------------------

    async def verify_group(
        self,
        messages: Sequence[bytes],
        pairs: Sequence[tuple[PublicKey, Signature]],
        urgent: bool = False,
        committee: bool = False,
        dedup: bool = True,
        trace: str | None = None,
        source: str | None = None,
        rid: int = 0,
    ) -> list[bool]:
        """Submit a correlated group (e.g. one QC's votes or one synthetic
        payload batch); resolves to the per-item validity mask once the
        group's flush completes. `source` declares the group's scheduler
        class ("consensus" | "sync" | "ingress" | "mempool" —
        crypto/scheduler.py); when omitted, the legacy `urgent` bit maps to
        consensus-critical vs mempool bulk. `committee=True` tags the group
        as signed by registered validator keys, routing it to the backend's
        committee-resident kernel when available; `dedup=False` bypasses
        the verified-signature cache (synthetic benchmark load, where
        repeats are intentional and must pay full verification); `trace`
        tags the group with a causal trace id so the flight recorder can
        attribute the batch's cost to the block it checks; `rid` is the
        sidecar's number of the wire request the group came in."""
        if not messages:
            return []
        self._ensure_task()
        cls = resolve_source(source, urgent)
        group = _Group(
            list(messages),
            [pk for pk, _ in pairs],
            [sig for _, sig in pairs],
            cls.preemptive,
            committee,
            dedup,
            trace,
            cls.name,
            asyncio.get_running_loop().time(),
            rid=rid,
        )
        if self.scheduler is not None:
            self.scheduler.submit(group)
        else:
            await self._queue.put(group)
        return await group.future

    async def verify(
        self,
        message: bytes,
        key: PublicKey,
        signature: Signature,
        urgent: bool = True,
        committee: bool = False,
        trace: str | None = None,
        source: str | None = None,
    ) -> bool:
        """Await a single verification (batched under the hood)."""
        mask = await self.verify_group(
            [message], [(key, signature)], urgent, committee, trace=trace,
            source=source,
        )
        return mask[0]

    def seed_verified(
        self, message: bytes, key: PublicKey, signature: Signature
    ) -> None:
        """Record an ALREADY-VERIFIED triple into the dedup cache (the
        aggregator seeds vote/timeout signatures on arrival, so the QC/TC
        assembled from them re-verifies zero signatures here)."""
        if self.dedup is not None:
            self.dedup.add(message, key, signature)

    # -- flush loops ---------------------------------------------------------
    #
    # Production rides DeviceScheduler.run() (crypto/scheduler.py). The
    # legacy single-queue heuristics below are retained as the measured
    # baseline for `bench.py --scheduler-ab` (use_scheduler=False): size /
    # deadline / urgent flushing with no lanes, no alignment sizing, no
    # continuous refill.

    async def _run_legacy(self) -> None:
        loop = asyncio.get_running_loop()
        while True:
            first = await self._queue.get()
            groups = [first]
            total = len(first)
            urgent = first.urgent
            deadline = loop.time() + self.max_delay
            while total < self.max_batch:
                # Opportunistic drain of whatever is already enqueued.
                while not self._queue.empty() and total < self.max_batch:
                    g = self._queue.get_nowait()
                    groups.append(g)
                    total += len(g)
                    urgent |= g.urgent
                if urgent or total >= self.max_batch:
                    break
                timeout = deadline - loop.time()
                if timeout <= 0:
                    break
                try:
                    g = await asyncio.wait_for(self._queue.get(), timeout)
                except asyncio.TimeoutError:
                    break
                groups.append(g)
                total += len(g)
                urgent |= g.urgent

            # The legacy path stamps dequeue time at flush decision, so the
            # per-lane queue-delay attribution is directly comparable with
            # the scheduler's (same submit -> dequeue definition).
            now = loop.time()
            for g in groups:
                g.t_dequeue = now
                note_queue_delay(self.lane_stats, g.source, max(0.0, now - g.t_submit))

            # Urgent groups dispatch in their OWN flush, immediately: a
            # 3-signature QC check must neither ride a multi-thousand-
            # signature workload batch down the device path nor wait for a
            # dispatch slot held by one (backends send small batches down
            # the CPU fast path, so unbounded urgent dispatches are bounded
            # in practice by the consensus message rate). Workload groups
            # coalesced in the same pass flush separately, gated by the
            # dispatch bound — acquired inside _dispatch so this loop keeps
            # draining the queue while every slot is in flight.
            if urgent:
                hot = [g for g in groups if g.urgent]
                cold = [g for g in groups if not g.urgent]
                self._spawn_dispatch(hot, sum(len(g) for g in hot), True)
                if cold:
                    self._spawn_dispatch(cold, sum(len(g) for g in cold), False)
            else:
                self._spawn_dispatch(groups, total, False)

    def _spawn_dispatch(
        self, groups: list[_Group], total: int, urgent: bool,
        backend_idx: int = 0,
    ) -> asyncio.Task:
        from ..utils.actors import spawn

        task = spawn(
            self._dispatch(groups, total, urgent, backend_idx),
            name="verify-dispatch",
        )
        self._dispatches.add(task)
        task.add_done_callback(self._dispatches.discard)
        return task

    async def _dispatch(
        self, groups: list[_Group], total: int, urgent: bool,
        backend_idx: int = 0,
    ) -> None:
        if not urgent:
            await self._dispatch_sem.acquire()
        try:
            # One synchronous section of the event loop (no await inside:
            # ops/timeline.py's asyncio rule): the flatten and the dedup
            # scan, item by item, up to the backend call. The verifier's
            # chunk spans under the call share its batch number.
            rids = [g.rid for g in groups if g.rid]
            collect = timeline.span(
                "collect", timeline.open_batch(), 0, total, hist=_M_COLLECT,
                groups=len(groups),
                rid_first=min(rids, default=0), rid_last=max(rids, default=0),
            )
            with collect:
                msgs = [m for g in groups for m in g.messages]
                keys = [k for g in groups for k in g.keys]
                sigs = [s for g in groups for s in g.signatures]
                # backend_idx > 0 is a scheduler steal: the bucket rides a
                # sibling shard's pipeline. Committee routing still resolves
                # per backend (an unregistered steal target just takes the
                # generic kernel — correctness never depends on the tag).
                backend = (
                    self.backend
                    if backend_idx == 0
                    else self._steal_backends[backend_idx - 1]
                )

                # Verified-signature dedup: triples the aggregator (or an
                # earlier flush) already validated resolve True without
                # touching the backend; only misses dispatch. Per-item
                # eligibility: a flush may mix dedup-opted-out synthetic
                # groups with consensus traffic. The scan (and the index-
                # gather re-copy) is skipped entirely when no group opted in
                # or nothing hit — the synthetic throughput path pays zero.
                cache = self.dedup if any(g.dedup for g in groups) else None
                mask = [False] * len(msgs)
                miss = range(len(msgs))
                dedupable = None
                if cache is not None:
                    dedupable = [g.dedup for g in groups for _ in range(len(g))]
                    miss = []
                    for i, (m, k, s) in enumerate(zip(msgs, keys, sigs)):
                        if dedupable[i] and cache.hit(m, k, s):
                            mask[i] = True
                        else:
                            miss.append(i)
                collect.set(miss=len(miss))
                if miss:
                    full = len(miss) == len(msgs)
                    kwargs = {}
                    if all(g.committee for g in groups) and getattr(
                        backend, "supports_committee_routing", False
                    ):
                        kwargs["committee"] = True
                    m = msgs if full else [msgs[i] for i in miss]
                    k = keys if full else [keys[i] for i in miss]
                    s = sigs if full else [sigs[i] for i in miss]
            if miss:
                t0 = time.perf_counter()
                try:
                    if self.inline:
                        sub = backend.verify_batch_mask(m, k, s, **kwargs)
                    else:
                        sub = await asyncio.to_thread(
                            backend.verify_batch_mask, m, k, s, **kwargs
                        )
                except Exception as exc:  # backend failure must not hang callers
                    for g in groups:
                        if not g.future.done():
                            g.future.set_exception(exc)
                    return
                dur = time.perf_counter() - t0
                _M_BACKEND.record(dur)
                if tracing.enabled():
                    # One verify.batch event per traced group in the flush
                    # (batch tags + the group's scheduler lane and queueing
                    # delay, the per-class attribution trace_report.py's
                    # verify-lane table aggregates), plus a watchdog sample
                    # of the flush's per-signature cost.
                    for g in groups:
                        if g.trace is not None:
                            tracing.event(
                                "verify.batch", g.trace, dur,
                                n=len(g), flush=len(miss), lane=g.source,
                                queue_s=round(
                                    max(0.0, g.t_dequeue - g.t_submit), 6
                                ),
                            )
                    tracing.WATCHDOG.note_verify(dur, len(miss))
                for i, ok in zip(miss, sub):
                    mask[i] = bool(ok)
                    if ok and cache is not None and dedupable[i]:
                        cache.add(msgs[i], keys[i], sigs[i])
            self.stats["flushes"] += 1
            self.stats["size_flushes"] += total >= self.max_batch
            self.stats["urgent_flushes"] += urgent
            self.stats["verified"] += total
            lo = 0
            for g in groups:
                hi = lo + len(g)
                if not g.future.cancelled():
                    g.future.set_result([bool(b) for b in mask[lo:hi]])
                lo = hi
        finally:
            if not urgent:
                self._dispatch_sem.release()
