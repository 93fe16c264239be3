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

The backend call runs in a worker thread so the TPU dispatch never blocks
the event loop (the mempool/consensus cores keep processing while a batch
is in flight — the same pipelining the reference gets from tokio). Groups
are enqueued whole (one lane entry, one future per group), so per-item
asyncio overhead is O(1) per group, not O(n) — at 100k+ sigs/s the Python
queue would otherwise dominate the TPU kernel.

A group is either three lists (every caller inside a node) or, from the
sidecar's wire (crypto/remote.py), one (n, 128) uint8 array of rows msg |
pk | sig (`verify_rows`). A bucket of columnar groups stays one array from
the flatten through the cache scan to the backend's three column views, and
its mask is one bool array; a bucket that mixes the kinds takes the list
path. One cache serves both, keyed by the bytes msg + pk + sig.
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Sequence

import numpy as np

from ..ops import timeline
from ..utils import metrics, tracing
from .backend import (
    ROW,
    CryptoBackend,
    columns_to_lists,
    get_backend,
    row_columns,
)
from .primitives import PublicKey, Signature
from .scheduler import (
    DeviceScheduler,
    LaneStats,
    SchedulerConfig,
    resolve_source,
)

log = logging.getLogger("hotstuff.crypto")

_M_DEDUP_HITS = metrics.counter("verifier.dedup_hits")
_M_DEDUP_MISSES = metrics.counter("verifier.dedup_misses")
_M_DEDUP_INSERTS = metrics.counter("verifier.dedup_inserts")
_M_DEDUP_EVICTIONS = metrics.counter("verifier.dedup_evictions")
_M_COLLECT = metrics.histogram("service.collect_s")
_M_BACKEND = metrics.histogram("service.backend_s")
_M_SCATTER = metrics.histogram("service.scatter_s")
_ROW_BYTES = np.dtype((np.void, ROW))


class VerifiedSigCache:
    """Bounded LRU of (message, pk, sig) triples that VERIFIED.

    Every vote signature is checked 2-3x over its lifetime: once on vote
    arrival, again inside every QC that carries it (`QC.verify`), and again
    when that QC rides a Block/Timeout. A hit here short-circuits the
    backend call entirely. Only successes are cached (a miss proves
    nothing), and the triple is the full (message, key, signature) — a
    forged signature over the same digest can never alias a cached entry.

    One key format: the bytes message + pk + sig. pk and sig are
    fixed-width suffixes, so it is unambiguous for any message length, and
    for a columnar request (crypto/remote.py) it is a row as it came off
    the wire. A bucket is scanned, and its verified misses inserted, under
    one lock acquisition each (`scan`, `add_many`); the LRU order and the
    four counters are those of the same items taken one by one.

    Thread-safe: the consensus event loop seeds it while backend dispatch
    worker threads look entries up.
    """

    __slots__ = ("maxsize", "_entries", "_lock")

    def __init__(self, maxsize: int = 65536) -> None:
        if maxsize <= 0:
            raise ValueError("dedup cache needs maxsize >= 1")
        self.maxsize = maxsize
        self._entries: OrderedDict[bytes, None] = OrderedDict()
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._entries)

    @staticmethod
    def key(message: bytes, key: PublicKey, sig: Signature) -> bytes:
        return message + key.data + sig.data

    def holds(self, message: bytes, key: PublicKey, sig: Signature) -> bool:
        """Membership alone: no recency refresh, no counter (audits)."""
        return self.key(message, key, sig) in self._entries

    def scan(self, keys: Sequence[bytes | None]) -> list[int]:
        """Indices, in order, of the keys that did NOT previously verify;
        each hit refreshes LRU recency. A None (an item opted out of the
        cache) is a miss and counts neither way; the others count into
        verifier.dedup_hits/misses."""
        entries = self._entries
        miss: list[int] = []
        with self._lock:
            for i, k in enumerate(keys):
                if k in entries:
                    entries.move_to_end(k)
                else:
                    miss.append(i)
        _M_DEDUP_HITS.inc(len(keys) - len(miss))
        _M_DEDUP_MISSES.inc(len(miss) - keys.count(None))
        return miss

    def add_many(self, keys: Sequence[bytes]) -> None:
        """Record VERIFIED triples in order; evicts least-recently-used
        past maxsize (memory stays bounded at ~128 B/entry)."""
        entries = self._entries
        inserts = evictions = 0
        with self._lock:
            for k in keys:
                if k in entries:
                    entries.move_to_end(k)
                    continue
                entries[k] = None
                inserts += 1
                while len(entries) > self.maxsize:
                    entries.popitem(last=False)
                    evictions += 1
        _M_DEDUP_INSERTS.inc(inserts)
        _M_DEDUP_EVICTIONS.inc(evictions)

    def hit(self, message: bytes, key: PublicKey, sig: Signature) -> bool:
        """True iff this exact triple previously verified (refreshes LRU
        recency); counts into verifier.dedup_hits/misses."""
        return not self.scan((self.key(message, key, sig),))

    def add(self, message: bytes, key: PublicKey, sig: Signature) -> None:
        """Record one VERIFIED triple."""
        self.add_many((self.key(message, key, sig),))


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
    # stamped at admission, t_dequeue when a bucket takes
    # the group — their difference is the per-lane queueing delay the
    # scheduler metrics and verify.batch trace events attribute.
    source: str = "mempool"
    t_submit: float = 0.0
    t_dequeue: float = 0.0
    # The sidecar's per-process request number (crypto/remote.py), 0 for
    # a group that is no wire request: `service.collect` names the
    # requests it merged by it.
    rid: int = 0
    # A columnar group (`verify_rows`) carries its (n, 128) uint8 rows
    # msg | pk | sig here and leaves the three lists empty; its future
    # resolves to a bool array, a list group's to list[bool].
    rows: np.ndarray | None = None
    future: asyncio.Future = field(default_factory=lambda: asyncio.get_running_loop().create_future())

    def __len__(self) -> int:
        return len(self.messages) if self.rows is None else len(self.rows)


class BatchVerificationService:
    def __init__(
        self,
        backend: CryptoBackend | None = None,
        max_batch: int = 8192,
        max_concurrent_dispatches: int = 4,
        dedup_cache_size: int = 65536,
        inline: bool = False,
        scheduler_config: SchedulerConfig | None = None,
        steal_backends: Sequence[CryptoBackend] | None = None,
    ) -> None:
        self._backend = backend
        self.max_batch = max_batch
        # Cross-chip work stealing (crypto/scheduler.py): sibling shard
        # backends bulk buckets may be stolen to when the home backend's
        # pipeline window is full. Backend 0 (the `backend` arg) stays
        # home for every critical dispatch.
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
        # (the uncached-baseline tests).
        self.dedup: VerifiedSigCache | None = (
            VerifiedSigCache(dedup_cache_size) if dedup_cache_size else None
        )
        self._task: asyncio.Task | None = None
        # Per-lane queueing-delay reservoir, fed by the scheduler's
        # dequeue — the chaos scheduler expectations read per-service
        # p50/p99 from here.
        self.lane_stats = LaneStats()
        # The continuous-batching device scheduler (crypto/scheduler.py):
        # the flush policy.
        self.scheduler = DeviceScheduler(
            self._spawn_dispatch,
            max_batch=max_batch,
            alignment_fn=self._bucket_alignment,
            config=scheduler_config,
            lane_stats=self.lane_stats,
            n_backends=1 + len(self._steal_backends),
        )
        # Flushes dispatch CONCURRENTLY (bounded): an urgent 3-signature QC
        # check must not wait out a multi-thousand-signature workload batch
        # already in flight on the device, so urgent dispatches never
        # acquire this semaphore. In a node the backend routes such a batch
        # to its CPU fast path and the urgent flush completes in
        # microseconds; in the sidecar every urgent flush is a device
        # program of its own, and what bounds those is the scheduler's
        # critical window (crypto/scheduler.py: `bulk_concurrency` in
        # flight on a gridded backend, later arrivals share the next
        # one), never this semaphore. With steal backends configured
        # the bound must cover every backend window the scheduler can
        # legitimately fill (bulk_concurrency per backend) — otherwise
        # the service-global semaphore silently caps stealing below the
        # per-backend accounting that admitted it. Without steal
        # backends the caller's max_concurrent_dispatches stands as-is.
        dispatch_bound = max_concurrent_dispatches
        if self._steal_backends:
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

            self._task = spawn(
                self.scheduler.run(), name="batch-verification-service"
            )

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
        cls = resolve_source(source, urgent)
        return await self._submit(
            _Group(
                list(messages),
                [pk for pk, _ in pairs],
                [sig for _, sig in pairs],
                cls.preemptive,
                committee,
                dedup,
                trace,
                cls.name,
                rid=rid,
            )
        )

    async def verify_rows(
        self, rows: np.ndarray, urgent: bool = False, rid: int = 0
    ) -> np.ndarray:
        """`verify_group` for a request that stayed columnar
        (crypto/remote.py): `rows` is its (n, 128) uint8 array msg | pk |
        sig, n >= 1, and the mask comes back as one bool array. Same lanes,
        same cache, same backend call; the rows are never taken apart into
        objects unless the bucket they land in holds a list group too."""
        cls = resolve_source(None, urgent)
        return await self._submit(
            _Group([], [], [], cls.preemptive, source=cls.name, rid=rid, rows=rows)
        )

    async def _submit(self, group: _Group):
        self._ensure_task()
        group.t_submit = asyncio.get_running_loop().time()
        self.scheduler.submit(group)
        timeline.ACCOUNT.submitted()
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

    # -- dispatch (the flush loop is DeviceScheduler.run) ---------------------

    def _spawn_dispatch(
        self, groups: list[_Group], total: int, urgent: bool,
        backend_idx: int = 0,
    ) -> asyncio.Task:
        from ..utils.actors import spawn

        # The scheduler closed this bucket: the device's idle account
        # charges it until its first program, or its end (ops/timeline.py).
        bucket = timeline.ACCOUNT.closed(len(groups))
        task = spawn(
            self._dispatch(groups, total, urgent, backend_idx, bucket),
            name="verify-dispatch",
        )
        self._dispatches.add(task)
        task.add_done_callback(self._dispatches.discard)
        task.add_done_callback(bucket.end)
        return task

    async def _dispatch(
        self, groups: list[_Group], total: int, urgent: bool,
        backend_idx: int = 0, bucket=None,
    ) -> None:
        # the verifier's pipeline, under `to_thread`'s copy of this context,
        # takes the bucket out of the idle account's closed state
        timeline.BUCKET.set(bucket)
        if not urgent:
            await self._dispatch_sem.acquire()
        try:
            # Two synchronous sections of the event loop (no await inside:
            # ops/timeline.py's asyncio rule), `collect` up to the backend
            # call and `scatter` after it. The verifier's chunk spans under
            # the call share their batch number.
            batch = timeline.open_batch()
            rids = [g.rid for g in groups if g.rid]
            collect = timeline.span(
                "collect", batch, 0, total, hist=_M_COLLECT,
                groups=len(groups),
                rid_first=min(rids, default=0), rid_last=max(rids, default=0),
            )
            with collect:
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
                # touching the backend; only misses dispatch. The scan (and
                # the index-gather re-copy) is skipped entirely when no
                # group opted in or nothing hit — the synthetic throughput
                # path pays zero.
                cache = self.dedup if any(g.dedup for g in groups) else None
                # A bucket of columnar groups alone stays one array; one
                # that mixes the two kinds takes the list path.
                columnar = all(g.rows is not None for g in groups)
                if columnar:
                    mask, miss, keys, args = _collect_rows(
                        groups, cache,
                        getattr(backend, "accepts_columns", False),
                    )
                else:
                    mask, miss, keys, args = _collect_lists(groups, cache)
                collect.set(miss=len(miss))
                kwargs = {}
                if all(g.committee for g in groups) and getattr(
                    backend, "supports_committee_routing", False
                ):
                    kwargs["committee"] = True
            if len(miss):
                t0 = time.perf_counter()
                try:
                    if self.inline:
                        sub = backend.verify_batch_mask(*args, **kwargs)
                    else:
                        sub = await asyncio.to_thread(
                            backend.verify_batch_mask, *args, **kwargs
                        )
                except Exception as exc:  # backend failure must not hang callers
                    for g in groups:
                        if not g.future.done():
                            g.future.set_exception(exc)
                    return
                dur = time.perf_counter() - t0
                _M_BACKEND.record(dur)
            with timeline.span("scatter", batch, 0, total, hist=_M_SCATTER):
                if len(miss):
                    if tracing.enabled():
                        # One verify.batch event per traced group in the
                        # flush (batch tags + the group's scheduler lane and
                        # queueing delay, the per-class attribution
                        # trace_report.py's verify-lane table aggregates),
                        # plus a watchdog sample of the flush's
                        # per-signature cost.
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
                    # Only what verified now is inserted, in lane order.
                    if columnar:
                        sub = np.asarray(sub, bool)
                        mask[miss] = sub
                        verified = miss[sub].tolist()
                    else:
                        verified = []
                        for i, ok in zip(miss, sub):
                            if ok:
                                mask[i] = True
                                verified.append(i)
                    if keys is not None:
                        cache.add_many(
                            [keys[i] for i in verified if keys[i] is not None]
                        )
                self.stats["flushes"] += 1
                self.stats["size_flushes"] += total >= self.max_batch
                self.stats["urgent_flushes"] += urgent
                self.stats["verified"] += total
                lo = 0
                for g in groups:
                    hi = lo + len(g)
                    if not g.future.cancelled():
                        part = mask[lo:hi]
                        g.future.set_result(
                            np.asarray(part, bool)
                            if g.rows is not None
                            else [bool(b) for b in part]
                        )
                    lo = hi
        finally:
            if not urgent:
                self._dispatch_sem.release()


def _collect_lists(groups: list[_Group], cache: VerifiedSigCache | None):
    """Flatten a bucket item by item: (mask with the cache's hits set, the
    lanes still to verify, the lanes' cache keys or None when no group uses
    the cache, the backend call's three arguments). A columnar group that
    shares the bucket is taken apart here."""
    parts = [
        (g.messages, g.keys, g.signatures)
        if g.rows is None
        else columns_to_lists(*row_columns(g.rows))
        for g in groups
    ]
    msgs = [m for p in parts for m in p[0]]
    pks = [k for p in parts for k in p[1]]
    sigs = [s for p in parts for s in p[2]]
    mask = [False] * len(msgs)
    if cache is None:
        return mask, range(len(msgs)), None, (msgs, pks, sigs)
    # Per-item eligibility: a flush may mix dedup-opted-out synthetic
    # groups with consensus traffic.
    dedupable = [g.dedup for g in groups for _ in range(len(g))]
    keys = [
        cache.key(m, k, s) if d else None
        for m, k, s, d in zip(msgs, pks, sigs, dedupable)
    ]
    miss = cache.scan(keys)
    if len(miss) == len(msgs):
        return mask, miss, keys, (msgs, pks, sigs)
    mask = [True] * len(msgs)
    for i in miss:
        mask[i] = False
    return mask, miss, keys, (
        [msgs[i] for i in miss], [pks[i] for i in miss], [sigs[i] for i in miss]
    )


def _collect_rows(
    groups: list[_Group], cache: VerifiedSigCache | None, as_columns: bool
):
    """`_collect_lists` for a bucket of columnar groups: one array from the
    flatten to the backend's arguments, its three column views, or
    `columns_to_lists` of them for a backend that takes no columns; the mask
    and the miss lanes are arrays, a cache key is a row's bytes."""
    rows = (
        groups[0].rows
        if len(groups) == 1
        else np.concatenate([g.rows for g in groups])
    )
    mask = np.zeros(len(rows), bool)
    miss = np.arange(len(rows))
    keys = None
    if cache is not None:
        # one bytes object a row, made in numpy (void items: nothing stripped)
        keys = np.ascontiguousarray(rows).view(_ROW_BYTES).ravel().tolist()
        miss = np.array(cache.scan(keys), np.intp)
        if len(miss) < len(rows):
            mask[:] = True
            mask[miss] = False
            rows = rows[miss]
    args = row_columns(rows)
    return mask, miss, keys, args if as_columns else columns_to_lists(*args)
