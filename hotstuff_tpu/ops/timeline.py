"""Device-occupancy timeline: per-chunk (stage, upload, dispatch, readback)
intervals for host<->device gap attribution.

ROADMAP item 1 claims the committed 9.7x-device vs 4.5x-e2e gap is
host<->device staging, not kernel math — but the stage histograms in
utils/metrics.py are AGGREGATES: they can say "upload cost X ms total",
not "how much of chunk N+1's upload could have hidden under chunk N's
dispatch". This module records every pipeline phase of the
Ed25519TpuVerifier chunk loop as an INTERVAL on one monotonic timeline,
so the three numbers the next perf session needs are measured, not
asserted:

  * **occupancy** — the fraction of the recorded span in which the
    device-facing pipeline (upload / dispatch / readback) was busy; the
    complement is host-only time the device sat idle.
  * **idle-gap distribution** — the gaps between consecutive busy
    segments (count / total / p50 / max): how the idle time is shaped
    (many small bubbles pipeline away; one big bubble is a serialization
    point).
  * **overlap headroom** — for consecutive chunks of one batch, the
    fraction of chunk-N+1 upload time that fits under chunk-N dispatch:
    sum(min(upload_dur(N+1), dispatch_dur(N))) / sum(upload_dur). This
    is the number ROADMAP item 1's async double-buffering claim must be
    judged against — a headroom near 1.0 means a double-buffered
    dispatch path can hide nearly the whole transfer cost; near 0.0
    means the transfer is not hideable and the win must come from
    shrinking it. (Conservative by construction: dispatch intervals time
    the async issue, so queued device compute behind the issue only adds
    hideable room this metric does not count.)

Recording is a ring-bounded deque append (oldest evicted), gated on
`HOTSTUFF_TIMELINE=0` exactly like the metrics/tracing flags; timestamps
are `time.monotonic()` and dumps carry the flight recorder's (mono, wall)
anchor pair so `tools/trace_report.py` can align device-timeline rows
beside the six-stage block rows.

`span` is the ONE way a host phase of the verify plane is timed. One
`with` block, one clock read per edge, three sinks:

  1. the **ring** above (off under `HOTSTUFF_TIMELINE=0`);
  2. the phase's **histogram** (`hist=`: `verifier.stage_s`, ...), so no
     site wraps one interval in `metrics.span` as well;
  3. an **annotation** named `PHASES[phase]` on the profiler's clock.
     This module stays jax-free: `set_annotator(factory)` is called by
     the code that already imports jax (`Ed25519TpuVerifier`) with
     `jax.profiler.TraceAnnotation`, a TraceMe that costs nothing unless
     a profiler session is open, and then puts the interval into the
     `/host:CPU` plane of the very trace that holds the device's plane.
     A process that never builds a verifier (a node) never installs one.

Two properties of the third sink:

  * **Annotations are per thread and stack-like.** On an asyncio thread
    wrap only SYNCHRONOUS sections (no `await` inside), or coroutines
    interleave and nest falsely. A wait that crosses an `await` (the
    scheduler's queue, the dispatch semaphore, the `to_thread` hop) is no
    annotation: in the trace it looks like "no request came". The idle
    account below attributes those waits instead.
  * **A backdated span (`start=`) is backdated in the ring alone.** The
    pipeline opens `readback` at dispatch completion for the occupancy
    math; the histogram and the annotation run from the real enter, so on
    the profiler's clock `verifier.readback` is the time the worker
    really blocked on the result, which is what gap attribution wants.

Request-level phases (`parse`, `collect`, `scatter`, `reply`) share the ring
with the chunk phases; `summary()` reads the chunk phases alone. Spans of one
bucket share `batch` (`open_batch` in the service's dispatch, `batch_id`
in the verifier: `asyncio.to_thread` carries the context over); a
request's `parse` and `reply` carry its `rid` as their `batch`, and
`collect` names the rids it merged (`scatter`, the section after the
backend call, shares its batch), so a request can be followed from parse
to chunk to reply.

**The idle account** (`IdleAccount`, one per `DeviceTimeline`; the
process's is `ACCOUNT`) says why the device idled, over the whole run and
not the ring's stretch. The device is BUSY while at least one program is
between its dispatch returning and its mask reaching the host (the two
edges of the backdated `readback` span, `ops/pipeline.py`), taken over
every pipeline of the process: its lanes share one device. Every idle
instant goes to exactly one cause, the first that holds:

  * `host` — a bucket has closed (the scheduler handed it to the service,
    `BatchVerificationService._spawn_dispatch`) and has neither dispatched
    its first program nor ended: collect, the `to_thread` hop, stage and
    upload. A bucket the cache answers whole ends without a program.
  * `held` — groups are queued in a scheduler and no bucket is closed:
    the flush policy holds them (deadline, grid, a bulk slot whose
    dispatch's programs already ended).
  * `no_request` — nothing queued, nothing closed: the nodes and the
    offer set the pace.

At each change of that state (a group submitted, a bucket closed, a
bucket's first program or end, a program dispatched, a mask on the host)
the seconds since the last change go to the state that held, on
`time.monotonic()` (never the loop's clock, which chaos makes virtual).
The account starts at the first program dispatch, so a process that never
dispatches one (a node) charges nothing. An edge is one lock and one clock
read, gated on `HOTSTUFF_METRICS` like every metric; the totals reach the
counters `timeline.device_busy_s` and `timeline.idle_<cause>_s` at every
metrics dump, which first charges the open interval, so a snapshot holds
everything up to its own instant.

Dependency-free by design: stdlib + utils.metrics/tracing only — no jax
(the graftlint tool and the chaos/telemetry planes import this
module on hosts with no accelerator stack at all).
"""

from __future__ import annotations

import contextvars
import json
import os
import threading
import time
from collections import deque

from ..utils import metrics, tracing

__all__ = [
    "PHASES",
    "CHUNK_PHASES",
    "DEVICE_PHASES",
    "DeviceTimeline",
    "TIMELINE",
    "IdleAccount",
    "ACCOUNT",
    "IDLE_CAUSES",
    "BUCKET",
    "enabled",
    "enable",
    "span",
    "set_annotator",
    "open_batch",
    "batch_id",
    "NULL",
    "summary",
    "dump",
    "write_json",
    "reset",
]

# Ring phase -> the span's name on the profiler's clock (its histogram
# adds `_s`). First the four pipeline phases of one verifier chunk, in
# pipeline order: `stage` is host CPU (numpy/C++ wire-format staging); the
# other three face the device and define occupancy. Then the four
# synchronous event-loop sections of one sidecar request. The names are
# matched by the benchmark's trace reduction: final.
PHASES: dict[str, str] = {
    "stage": "verifier.stage",
    "upload": "verifier.upload",
    "dispatch": "verifier.dispatch",
    "readback": "verifier.readback",
    "parse": "sidecar.parse",
    "collect": "service.collect",
    "scatter": "service.scatter",
    "reply": "sidecar.reply",
}
CHUNK_PHASES: tuple[str, ...] = ("stage", "upload", "dispatch", "readback")
DEVICE_PHASES: frozenset[str] = frozenset({"upload", "dispatch", "readback"})

# 4,096 intervals wrapped in ~8 s at 145 requests a second (three request
# phases each, four a chunk); four times that keeps half a minute.
RING_CAPACITY = 16384

# The idle account's causes, in the order it tests them after "busy"
# (module docstring); each has the counter `timeline.idle_<cause>_s`.
IDLE_CAUSES: tuple[str, ...] = ("host", "held", "no_request")
_BUSY, _HOST, _HELD, _NO_REQUEST = range(4)

_enabled = os.environ.get("HOTSTUFF_TIMELINE", "1") != "0"


def enabled() -> bool:
    return _enabled


def enable(on: bool = True) -> None:
    global _enabled
    _enabled = on


class _Bucket:
    """A closed bucket's place in an `IdleAccount`: `open` while it counts
    as closed (neither its first program dispatched nor its task ended).
    `epoch` is the account's at the close (None: not counted), so that a
    bucket closed before a `reset` never takes a later one's place."""

    __slots__ = ("account", "epoch", "open")

    def __init__(self, account: "IdleAccount", epoch: object | None) -> None:
        self.account = account
        self.epoch = epoch
        self.open = epoch is not None

    def end(self, _task=None) -> None:
        """The bucket's dispatch ended, however (a done-callback)."""
        self.account.ended(self)


class IdleAccount:
    """The device's busy seconds and its idle seconds by cause (module
    docstring). Edges come from the event loop and the pipeline's workers
    alike; `clock` is `time.monotonic` but in tests. `counters` are the
    four metric counters (busy, then `IDLE_CAUSES`) that `flush` feeds;
    None keeps the totals here alone."""

    def __init__(self, clock=time.monotonic, counters: tuple | None = None) -> None:
        self._clock = clock
        self._counters = counters
        # Re-entrant, as utils/metrics.py's locks are: a SIGTERM handler's
        # last dump may land on the thread parked inside an edge.
        self._lock = threading.RLock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._epoch = object()  # the buckets of this reset
            self._t: float | None = None  # the last change; None: not started
            self._programs = 0  # dispatch returned, mask not yet on the host
            self._closed = 0  # buckets closed, no program yet, not ended
            self._queued = 0  # groups in a scheduler's lanes
            self._totals = [0.0] * 4  # busy, then IDLE_CAUSES
            self._flushed = [0.0] * 4  # what the counters have had

    def _charge(self) -> None:
        """Give the seconds since the last change to the state that held
        through them; nothing before the first program. Under the lock."""
        if self._t is None:
            return
        now = self._clock()
        if self._programs:
            i = _BUSY
        elif self._closed:
            i = _HOST
        elif self._queued:
            i = _HELD
        else:
            i = _NO_REQUEST
        self._totals[i] += now - self._t
        self._t = now

    # -- the edges -----------------------------------------------------------

    def submitted(self) -> None:
        """A group joined a scheduler's lane."""
        if not metrics.enabled():
            return
        with self._lock:
            self._charge()
            self._queued += 1

    def closed(self, groups: int) -> _Bucket:
        """A scheduler closed a bucket of `groups` queued groups and handed
        it to its service; end the bucket returned when its dispatch ends."""
        if not metrics.enabled():
            return _Bucket(self, None)
        with self._lock:
            self._charge()
            self._queued = max(0, self._queued - groups)
            self._closed += 1
            return _Bucket(self, self._epoch)

    def ended(self, bucket: _Bucket) -> None:
        """`bucket`'s dispatch ended; it leaves the closed state if its
        first program has not already taken it out."""
        if not bucket.open:  # it only ever closes: no lock to see that
            return
        with self._lock:
            if not bucket.open:
                return
            bucket.open = False
            if bucket.epoch is not self._epoch:
                return
            self._charge()
            self._closed -= 1

    def dispatched(self, bucket: _Bucket | None = None) -> None:
        """A program's dispatch returned: the device has it. `bucket` is the
        closed bucket the program serves (`BUCKET`), None outside one. The
        process's first program starts the account."""
        if not metrics.enabled():
            return
        with self._lock:
            if self._t is None:
                self._t = self._clock()
            else:
                self._charge()
            self._programs += 1
            if bucket is not None and bucket.open and bucket.epoch is self._epoch:
                bucket.open = False
                self._closed -= 1

    def read(self) -> None:
        """A program's mask reached the host."""
        if not metrics.enabled():
            return
        with self._lock:
            self._charge()
            self._programs = max(0, self._programs - 1)

    # -- the totals ----------------------------------------------------------

    def totals(self) -> dict:
        """{"busy_s", "idle_s": {cause: s}} up to now, the open interval
        charged; all zero until the first program."""
        with self._lock:
            self._charge()
            t = list(self._totals)
        return {
            "busy_s": round(t[_BUSY], 6),
            "idle_s": {c: round(t[1 + i], 6) for i, c in enumerate(IDLE_CAUSES)},
        }

    def flush(self) -> None:
        """Charge the open interval and add to each counter what it has not
        had yet. Every metrics dump calls it first (`metrics.before_dump`)."""
        with self._lock:
            self._charge()
            adds = [t - f for t, f in zip(self._totals, self._flushed)]
            self._flushed = list(self._totals)
        for counter, add in zip(self._counters or (), adds):
            if add:
                counter.inc(add)


class DeviceTimeline:
    """Ring of (batch, chunk, phase, t0, t1, n) intervals.

    `batch` numbers one verify_batch_mask[_committee] call; `chunk` is the
    chunk's index within its batch (the uploader is a 1-worker FIFO, so
    chunk order IS dispatch order). Appends are deque-atomic under the
    GIL — the staging thread and the uploader thread both record.
    `account` is the idle account the pipelines that record here feed (a
    fresh one, counters off, unless given)."""

    def __init__(
        self, capacity: int | None = None, account: IdleAccount | None = None
    ) -> None:
        self.account = account if account is not None else IdleAccount()
        if capacity is None:
            try:
                capacity = int(
                    os.environ.get("HOTSTUFF_TIMELINE_RING", RING_CAPACITY)
                )
            except ValueError:
                capacity = RING_CAPACITY
        self.capacity = max(16, capacity)
        self._ring: deque = deque(maxlen=self.capacity)
        self._count = 0
        self._batch_seq = 0
        self._lock = threading.Lock()

    def next_batch(self) -> int:
        with self._lock:
            self._batch_seq += 1
            return self._batch_seq

    def note(
        self, batch: int, chunk: int, phase: str, t0: float, t1: float, n: int = 0
    ) -> None:
        if not _enabled:
            return
        with self._lock:
            self._count += 1
        self._ring.append((batch, chunk, phase, t0, t1, n))

    def __len__(self) -> int:
        return len(self._ring)

    @property
    def dropped(self) -> int:
        return max(0, self._count - self.capacity)

    def intervals(self) -> list[dict]:
        return [
            {
                "batch": b,
                "chunk": c,
                "phase": p,
                "t0": round(t0, 6),
                "t1": round(t1, 6),
                "n": n,
            }
            for b, c, p, t0, t1, n in list(self._ring)
        ]

    # -- derived numbers -----------------------------------------------------

    def summary(self) -> dict:
        """Occupancy / idle-gap / overlap-headroom over the chunk phases
        of the whole ring (request phases are no part of a chunk), and
        beside them the idle account's totals since it started (`account`:
        the device's one idle, by cause; the ring's gaps are the same edges
        over the ring's stretch alone, their cause unknown).

        The ring's fields derive from ONE ring snapshot. Empty ring -> zeros
        (the shape is stable so BENCH json and dashboards never KeyError)."""
        iv = [i for i in list(self._ring) if i[2] in CHUNK_PHASES]
        out = {
            "account": self.account.totals(),
            "batches": 0,
            "chunks": 0,
            "span_s": 0.0,
            "occupancy": 0.0,
            "overlap_headroom": 0.0,
            "phase_s": {p: 0.0 for p in CHUNK_PHASES},
            "idle": {"count": 0, "total_s": 0.0, "p50_s": 0.0, "max_s": 0.0},
        }
        if not iv:
            return out
        t_lo = min(t0 for _b, _c, _p, t0, _t1, _n in iv)
        t_hi = max(t1 for _b, _c, _p, _t0, t1, _n in iv)
        phase_s = {p: 0.0 for p in CHUNK_PHASES}
        busy: list[tuple[float, float]] = []
        chunks = set()
        batches = set()
        upload_dur: dict[tuple[int, int], float] = {}
        dispatch_dur: dict[tuple[int, int], float] = {}
        for b, c, p, t0, t1, n in iv:
            dur = max(0.0, t1 - t0)
            phase_s[p] += dur
            chunks.add((b, c))
            batches.add(b)
            if p in DEVICE_PHASES:
                busy.append((t0, t1))
            if p == "upload":
                upload_dur[(b, c)] = upload_dur.get((b, c), 0.0) + dur
            elif p == "dispatch":
                dispatch_dur[(b, c)] = dispatch_dur.get((b, c), 0.0) + dur
        # merge the device-busy segments into a union, then read occupancy
        # and the idle gaps off the merged cover
        busy.sort()
        merged: list[list[float]] = []
        for t0, t1 in busy:
            if merged and t0 <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], t1)
            else:
                merged.append([t0, t1])
        busy_s = sum(t1 - t0 for t0, t1 in merged)
        span_s = max(t_hi - t_lo, 1e-12)
        gaps = [
            merged[i + 1][0] - merged[i][1]
            for i in range(len(merged) - 1)
            if merged[i + 1][0] > merged[i][1]
        ]
        # overlap headroom: chunk N+1's upload vs chunk N's dispatch,
        # paired within one batch (see module docstring)
        total_upload = sum(upload_dur.values())
        hideable = sum(
            min(dur, dispatch_dur.get((b, c - 1), 0.0))
            for (b, c), dur in upload_dur.items()
            if c > 0
        )
        out.update(
            {
                "batches": len(batches),
                "chunks": len(chunks),
                # 6 decimals: serial vs pipelined occupancy compare
                # STRICTLY, and on fast hosts the gap can live below 1e-4
                # (4-digit rounding would tie).
                "span_s": round(span_s, 6),
                "occupancy": round(busy_s / span_s, 6),
                "overlap_headroom": round(
                    hideable / total_upload if total_upload > 0 else 0.0, 6
                ),
                "phase_s": {p: round(s, 6) for p, s in phase_s.items()},
                "idle": {
                    "count": len(gaps),
                    "total_s": round(sum(gaps), 6),
                    "p50_s": round(metrics.percentile(gaps, 0.50), 6),
                    "max_s": round(max(gaps), 6) if gaps else 0.0,
                },
            }
        )
        return out

    def dump(self) -> dict:
        """Structured artifact; (mono, wall) anchor pair matches the flight
        recorder's convention so trace_report.py aligns both on one wall
        timeline."""
        return {
            "v": 1,
            "kind": "device_timeline",
            "node": tracing.NODE_LABEL.get(),
            "capacity": self.capacity,
            "recorded": self._count,
            "dropped": self.dropped,
            # graftlint: allow[determinism] dump-alignment stamp, mirrors the flight recorder's (mono, wall) anchor
            "anchor": {"mono": time.monotonic(), "wall": time.time()},
            "intervals": self.intervals(),
            "summary": self.summary(),
        }

    def write_json(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.dump(), f, indent=2, sort_keys=True)
            f.write("\n")

    def reset(self) -> None:
        self._ring.clear()
        self._count = 0
        self.account.reset()


ACCOUNT = IdleAccount(
    counters=(
        metrics.counter("timeline.device_busy_s"),
        metrics.counter("timeline.idle_host_s"),
        metrics.counter("timeline.idle_held_s"),
        metrics.counter("timeline.idle_no_request_s"),
    )
)
metrics.before_dump(ACCOUNT.flush)
TIMELINE = DeviceTimeline(account=ACCOUNT)

# The closed bucket (`IdleAccount.closed`) the service's dispatch runs
# under: the verifier's pipeline, in a copy of that context (`to_thread`),
# charges the bucket's first program to it.
BUCKET: contextvars.ContextVar[_Bucket | None] = contextvars.ContextVar(
    "timeline_bucket", default=None
)


# The third sink. None until code that imports jax installs
# `jax.profiler.TraceAnnotation` (module docstring); a test installs a fake.
_annotate = None


def set_annotator(factory):
    """Install `factory(name, **stats)` -> context manager (with a
    `set_metadata(**stats)`) as the annotation sink of every later span;
    None removes it. Returns the one that was installed before."""
    global _annotate
    prev, _annotate = _annotate, factory
    return prev


# The batch a `service.collect` opened, for the verifier under it: both
# asyncio tasks and `asyncio.to_thread` run in a copy of the context.
_BATCH: contextvars.ContextVar[int] = contextvars.ContextVar(
    "timeline_batch", default=0
)


def open_batch() -> int:
    """A fresh batch number, set as this context's batch: the spans of a
    verifier called under it share it (`batch_id`)."""
    batch = TIMELINE.next_batch()
    _BATCH.set(batch)
    return batch


def batch_id() -> int:
    """The batch the caller's context opened, else a fresh one."""
    return _BATCH.get() or TIMELINE.next_batch()


class _Span:
    """Context manager feeding one interval to the three sinks (module
    docstring); one monotonic read per edge, the annotation outermost.

    `start` backdates the RING interval's opening edge to a moment the
    caller already observed (clamped to never sit in the future): the
    dispatch pipeline opens each `readback` span at dispatch completion,
    because the device has been computing since then even if the
    readback worker dequeued the chunk late.
    """

    __slots__ = ("_tl", "_batch", "_chunk", "_phase", "_n", "_t0", "_start",
                 "_hist", "_ann")

    def __init__(self, tl, phase, batch, chunk, n, start, hist, stats):
        self._tl = tl
        self._phase = phase
        self._batch = batch
        self._chunk = chunk
        self._n = n
        self._t0 = 0.0
        self._start = start
        self._hist = hist
        self._ann = (
            None
            if _annotate is None
            else _annotate(PHASES[phase], batch=batch, chunk=chunk, n=n, **stats)
        )

    def set(self, **stats) -> None:
        """Stats known only inside the block (a dedup scan's misses); they
        ride the annotation, the ring's tuple is fixed."""
        if self._ann is not None:
            self._ann.set_metadata(**stats)

    def __enter__(self) -> "_Span":
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.monotonic()
        if self._hist is not None:
            self._hist.record(t1 - self._t0)
        t0 = self._t0 if self._start is None else min(self._start, self._t0)
        self._tl.note(self._batch, self._chunk, self._phase, t0, t1, self._n)
        if self._ann is not None:
            self._ann.__exit__(*exc)


class _NullSpan:
    __slots__ = ()

    def set(self, **stats) -> None:
        pass

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


NULL = _NullSpan()


def span(
    phase: str,
    batch: int,
    chunk: int,
    n: int = 0,
    timeline: DeviceTimeline | None = None,
    start: float | None = None,
    hist: "metrics.Histogram | None" = None,
    **stats,
):
    """`with timeline.span("upload", b, c, n, hist=_M_UPLOAD): ...` —
    ring interval, histogram sample and annotation of one interval.
    `stats` ride the annotation beside batch/chunk/n. A no-op when every
    sink is off (ring disabled, no histogram, no annotator)."""
    if not _enabled and hist is None and _annotate is None:
        return NULL
    # `is None`, not truthiness: an EMPTY DeviceTimeline is falsy (__len__).
    return _Span(
        TIMELINE if timeline is None else timeline,
        phase, batch, chunk, n, start, hist, stats,
    )


def summary() -> dict:
    return TIMELINE.summary()


def dump() -> dict:
    return TIMELINE.dump()


def write_json(path: str) -> None:
    TIMELINE.write_json(path)


def reset() -> None:
    TIMELINE.reset()
