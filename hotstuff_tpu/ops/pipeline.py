"""Double-buffered async dispatch pipeline for the verifier chunk loops.

ROADMAP item 1's committed gap (9.7x device vs 4.5x e2e) is host<->device
staging, and PR 8's DeviceTimeline measures exactly how much of it is
hideable: `overlap_headroom` = the fraction of chunk-N+1 upload time that
fits under chunk-N dispatch. This module is the machinery that actually
hides it. The previous shape — one module-global single-worker uploader
thread shared by every verifier, plus a one-shot end-of-batch readback —
overlapped staging with upload but (a) serialized ALL mask fetches after
the LAST dispatch, (b) allocated a fresh padded staging buffer per chunk,
and (c) leaked its executor for the life of the process.

`DispatchPipeline` replaces it with a bounded-depth in-flight window:

  * **depth** (default 2 = double buffering) bounds how many chunks may
    be between staging-start and readback-complete. Staging chunk k+depth
    blocks until chunk k's mask is on the host — backpressure, counted as
    `pipeline.stalls` / `pipeline.stall_s`.
  * **Staging-buffer pool.** Padded wire buffers are taken from a
    per-shape free list and returned once the chunk's READBACK settles
    (device_put's transfer is async — PJRT may read, or on CPU alias,
    the host bytes until the kernel's results are back), so packing
    chunk k+2 never allocates in steady state (`pipeline.buffer_reuse`
    vs `pipeline.buffer_allocs`).
  * **Streamed readback.** Each chunk's mask is fetched on a dedicated
    readback worker as soon as its dispatch handle exists, so the
    device->host fetch of chunk k overlaps the dispatch of chunk k+1
    instead of serializing after the last dispatch.
  * **FIFO order.** Both workers are single-threaded FIFO executors, so
    chunk upload order IS dispatch order IS readback order — the
    DeviceTimeline's `chunk` index stays meaningful and result order is
    task order.
  * **Owned, closeable workers.** Each pipeline owns its executors
    (created lazily on the first depth>1 run), `close()` shuts them
    down, a `weakref.finalize` reaps them when the owner is collected,
    and one atexit hook drains every live pipeline — repeated verifier
    construction in tests leaks nothing.
  * **depth=1 is the serial/inline mode**: stage, upload, dispatch and
    readback run synchronously on the caller thread with NO worker
    threads at all — the deterministic degeneration the chaos
    virtual-time plane requires (COMPONENTS.md §5.5i).

The pipeline times the `stage` and `readback` phases of each task
(`timeline.span`: the ring under the task's key, the task's histogram of
that phase, the profiler annotation); the task's `submit` callable owns
the `upload` and `dispatch` phases (the `submit` of the verifier's chunk
loop, `Ed25519TpuVerifier._run`). The same two edges the backdated
`readback` span has, a dispatch returned and a mask on the host, are the
timeline's idle account's program edges (ops/timeline.py `IdleAccount`);
the first program of a run charges the service's closed bucket the run
serves (`timeline.BUCKET`, read on the caller's thread).
`TIMELINE_STAGES` is the full vocabulary — the graftlint `pipeline`
pass asserts
it stays inside `timeline.PHASES` so trace_report.py's device rows keep
rendering.

Dependency-free by design: stdlib + numpy + utils.metrics + ops.timeline
only — no jax (tests/test_pipeline.py drives it with a paced fake
backend on jax-less hosts, like DeviceScheduler).
"""

from __future__ import annotations

import atexit
import os
import threading
import time
import weakref
from concurrent.futures import Future, ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable

import numpy as np

from ..utils import metrics
from . import timeline

__all__ = [
    "TIMELINE_STAGES",
    "ChunkTask",
    "StagingBufferPool",
    "DispatchPipeline",
    "default_depth",
    "close_all",
]

# Every DeviceTimeline phase a DispatchPipeline run can stamp (directly —
# stage/readback — or through its tasks' submit callables — upload/
# dispatch). The graftlint `pipeline` pass fails the build if this ever
# leaves timeline.PHASES: a renamed stage would silently fall out of the
# occupancy/headroom math and the trace_report device rows.
TIMELINE_STAGES: tuple[str, ...] = ("stage", "upload", "dispatch", "readback")

_M_CHUNKS = metrics.counter("pipeline.chunks")
_M_DEPTH = metrics.gauge("pipeline.depth")
_M_STALLS = metrics.counter("pipeline.stalls")
_M_STALL_S = metrics.histogram("pipeline.stall_s")
_M_BUF_REUSE = metrics.counter("pipeline.buffer_reuse")
_M_BUF_ALLOC = metrics.counter("pipeline.buffer_allocs")


def default_depth() -> int:
    """Pipeline depth when the caller passes none: HOTSTUFF_PIPELINE_DEPTH
    (>=1), default 2 — stage the next chunk while one is on the device;
    deeper windows only add host-memory pressure for transfers the device
    cannot consume faster."""
    try:
        return max(1, int(os.environ.get("HOTSTUFF_PIPELINE_DEPTH", "2")))
    except ValueError:
        return 2


@dataclass(slots=True)
class ChunkTask:
    """One chunk's three pipeline legs.

    `stage`    — pack the chunk's wire bytes (caller thread; CPU-only).
    `submit`   — ship the staged payload and dispatch the kernel, returning
                 the async device handle (upload worker; must stamp the
                 `upload`/`dispatch` timeline phases itself, as
                 `Ed25519TpuVerifier._run` does).
    `readback` — resolve the handle to a host result (readback worker).
    `tlkey`    — the chunk's (batch, chunk, n) DeviceTimeline key; the
                 pipeline times `stage` and `readback` under it. A task
                 without one is not timed.
    `hists`    — phase -> the histogram that phase's span also feeds
                 (`verifier.stage_s`, `verifier.readback_s`); a phase
                 left out feeds none.
    `release`  — pooled staging buffers to return once the chunk has
                 fully settled (filled by `stage`, drained by the
                 pipeline after `readback` completes — not at
                 submit-return: the upload is asynchronous and may
                 still be reading the host bytes).
    """

    stage: Callable[[], Any]
    submit: Callable[[Any], Any]
    readback: Callable[[Any], Any]
    tlkey: tuple | None = None
    release: list = field(default_factory=list)
    hists: dict = field(default_factory=dict)


class StagingBufferPool:
    """Reusable host staging buffers, one free list per (shape, dtype).

    Every chunk of a batch pads to the same bucket width, so the padded
    wire arrays are identically shaped and a tiny per-shape free list
    gives steady-state zero-allocation staging (the "pinned buffer pool":
    numpy cannot page-pin, but reuse keeps the pages hot and the
    allocator out of the loop).
    Thread-safe: the caller thread takes, the readback worker gives back.
    """

    def __init__(self, max_per_shape: int = 4) -> None:
        self.max_per_shape = max(1, max_per_shape)
        self._free: dict[tuple, list[np.ndarray]] = {}
        self._lock = threading.Lock()

    def take(self, shape: tuple, dtype) -> np.ndarray:
        key = (tuple(shape), np.dtype(dtype).str)
        with self._lock:
            free = self._free.get(key)
            if free:
                _M_BUF_REUSE.inc()
                return free.pop()
        _M_BUF_ALLOC.inc()
        return np.empty(shape, dtype)

    def give(self, arr: np.ndarray) -> None:
        key = (arr.shape, arr.dtype.str)
        with self._lock:
            free = self._free.setdefault(key, [])
            if len(free) < self.max_per_shape:
                free.append(arr)

    def pad(self, arr: np.ndarray, width: int) -> np.ndarray:
        """`ed25519._pad` into a pooled buffer: the last axis grows to
        `width` with zeroed padding, no allocation on reuse. Always copies
        (even at zero pad) — the staged array is about to be handed to an
        async upload, and only pooled buffers have a defined give-back
        point."""
        shape = (*arr.shape[:-1], width)
        out = self.take(shape, arr.dtype)
        n = arr.shape[-1]
        out[..., :n] = arr
        if n < width:
            out[..., n:] = 0
        return out

    def sizes(self) -> dict[tuple, int]:
        """Free-list occupancy per shape (test/diagnostic hook)."""
        with self._lock:
            return {k: len(v) for k, v in self._free.items()}


# Live pipelines, reaped at interpreter exit: worker threads must never
# outlive the process teardown (a verifier constructed in a test and
# dropped without close() is also reaped per-instance by weakref.finalize
# as soon as it is collected).
_LIVE: "weakref.WeakSet[DispatchPipeline]" = weakref.WeakSet()


def close_all() -> None:
    """Drain every live pipeline's workers (atexit hook; also callable
    from SIGTERM paths — `node run` and bench exit through atexit)."""
    for p in list(_LIVE):
        p.close(wait=False)


atexit.register(close_all)


def _drain(execs: dict) -> None:
    """Finalizer body: owns only the executor dict, never the pipeline
    (a bound method would keep the pipeline alive forever)."""
    for ex in list(execs.values()):
        ex.shutdown(wait=False, cancel_futures=True)
    execs.clear()


class DispatchPipeline:
    """Bounded-depth upload/dispatch/readback window over FIFO workers.

    `run(tasks)` executes each `ChunkTask`'s stage on the calling thread,
    its submit on the single upload worker, and its readback on the
    single readback worker, holding at most `depth` chunks between
    staging-start and readback-complete. Results return in task order.
    Exceptions propagate to the caller after every submitted leg has
    settled (no orphaned jobs keep pooled buffers or device handles).
    """

    def __init__(
        self,
        depth: int | None = None,
        name: str = "verify",
        pool: StagingBufferPool | None = None,
        tl: "timeline.DeviceTimeline | None" = None,
    ) -> None:
        self.depth = max(1, depth if depth is not None else default_depth())
        self.name = name
        # depth+1 buffers per shape: `depth` chunks in flight (each holds
        # its buffers until readback settles) plus the one being packed.
        self.pool = pool or StagingBufferPool(max_per_shape=self.depth + 1)
        self._tl = tl  # None -> the process-global timeline
        self._account = (tl if tl is not None else timeline.TIMELINE).account
        self._execs: dict[str, ThreadPoolExecutor] = {}
        self._lock = threading.Lock()
        self._closed = False
        self._inflight = 0
        self.stats = {"chunks": 0, "stalls": 0}
        self._finalizer = weakref.finalize(self, _drain, self._execs)
        _LIVE.add(self)

    # -- lifecycle -----------------------------------------------------------

    @property
    def inflight(self) -> int:
        """Chunks currently between staging-start and readback-complete."""
        return self._inflight

    def set_depth(self, depth: int) -> None:
        """Clamp the in-flight window after construction (the
        multi-process mesh forces 1 — parallel/mesh.py)."""
        self.depth = max(1, int(depth))

    def close(self, wait: bool = True) -> None:
        """Shut the owned workers down. Idempotent; a closed pipeline
        still runs — every subsequent run degrades to the serial inline
        path, so late callers never touch dead executors."""
        with self._lock:
            self._closed = True
            execs, to_stop = self._execs, list(self._execs.values())
            execs.clear()
        for ex in to_stop:
            ex.shutdown(wait=wait, cancel_futures=not wait)

    def _executor(self, kind: str) -> ThreadPoolExecutor:
        ex = self._execs.get(kind)
        if ex is None:
            with self._lock:
                ex = self._execs.get(kind)
                if ex is None:
                    ex = ThreadPoolExecutor(
                        1, thread_name_prefix=f"pipe-{kind}-{self.name}"
                    )
                    self._execs[kind] = ex
        return ex

    # -- timeline spans ------------------------------------------------------

    def _span(self, phase: str, task: ChunkTask, start: float | None = None):
        if task.tlkey is None:
            return timeline.NULL
        return timeline.span(
            phase, *task.tlkey, timeline=self._tl, start=start,
            hist=task.hists.get(phase),
        )

    # -- execution -----------------------------------------------------------

    def _staged(self, task: ChunkTask):
        self.stats["chunks"] += 1
        _M_CHUNKS.inc()
        with self._span("stage", task):
            return task.stage()

    def _submitted(self, task: ChunkTask, payload, bucket):
        handle = task.submit(payload)
        self._account.dispatched(bucket)
        return handle, time.monotonic()

    def _release_buffers(self, task: ChunkTask) -> None:
        """Hand the chunk's pooled staging buffers back — only once the
        chunk's READBACK has settled. jax.device_put does NOT promise a
        synchronous copy (PJRT may keep reading the host bytes until the
        transfer lands, and the CPU backend can zero-copy alias an
        aligned array outright), so releasing at submit-return would let
        the next chunk's packing overwrite wire bytes still in flight.
        A mask on the host proves the inputs were consumed."""
        while task.release:
            self.pool.give(task.release.pop())

    def _read(self, task: ChunkTask, handle_fut: "Future") -> Any:
        try:
            handle, dispatched_t = handle_fut.result()
            # The readback span opens at dispatch completion: the device
            # has been computing since the dispatch returned its async
            # handle, so the readback worker's dequeue latency
            # (GIL/scheduler) is not device idle — without the backdate,
            # every worker handoff shows up as an idle gap that cancels
            # exactly the occupancy the overlap bought.
            return self._readback(task, handle, dispatched_t)
        finally:
            self._release_buffers(task)

    def _readback(self, task: ChunkTask, handle, dispatched_t: float) -> Any:
        """The readback span, opened at `dispatched_t`; the mask on the host
        ends the program's busy interval in the idle account."""
        try:
            with self._span("readback", task, start=dispatched_t):
                return task.readback(handle)
        finally:
            self._account.read()

    def run(self, tasks) -> list:
        """Run every task through the window; returns readbacks in task
        order. depth=1 (or a closed pipeline) runs fully inline."""
        tasks = list(tasks)
        if not tasks:
            return []
        # Gauge semantics: the depth of the pipeline that ran MOST
        # RECENTLY (the gauge is process-global; several live pipelines
        # would otherwise report whichever was constructed last, active
        # or not).
        _M_DEPTH.set(self.depth)
        bucket = timeline.BUCKET.get()
        if self.depth <= 1 or self._closed:
            return [self._run_serial(t, bucket) for t in tasks]
        return self._run_windowed(tasks, bucket)

    def _run_serial(self, task: ChunkTask, bucket) -> Any:
        """The inline/serial leg: caller-thread stage -> submit ->
        readback, nothing overlapped — deterministic under the chaos
        virtual-time loop."""
        try:
            payload = self._staged(task)
            handle, dispatched_t = self._submitted(task, payload, bucket)
            # Same backdate rule as the windowed path (fair A/B): the span
            # opens at dispatch completion — on this thread that is only
            # microseconds ago, so serial semantics are unchanged.
            return self._readback(task, handle, dispatched_t)
        finally:
            self._release_buffers(task)

    def _run_windowed(self, tasks: list[ChunkTask], bucket) -> list:
        up = self._executor("upload")
        rb = self._executor("readback")
        window = threading.Semaphore(self.depth)
        results: list[Future] = []

        def _release(_fut: Future) -> None:
            with self._lock:
                self._inflight -= 1
            window.release()

        try:
            for task in tasks:
                if not window.acquire(blocking=False):
                    # Window full: the device is `depth` chunks behind the
                    # host. The stall is the backpressure working — count
                    # it so occupancy regressions have a host-side signal.
                    self.stats["stalls"] += 1
                    _M_STALLS.inc()
                    t0 = time.monotonic()
                    window.acquire()
                    _M_STALL_S.record(time.monotonic() - t0)
                with self._lock:
                    self._inflight += 1
                # The slot just taken has no future yet: until _release is
                # attached, a failing stage must free it (and the staged
                # buffers) itself.
                attached = False
                handle_fut = None
                try:
                    payload = self._staged(task)
                    handle_fut = up.submit(self._submitted, task, payload, bucket)
                    res_fut = rb.submit(self._read, task, handle_fut)
                    res_fut.add_done_callback(_release)
                    attached = True
                finally:
                    if not attached:
                        if handle_fut is not None:
                            # An upload may already be consuming the
                            # buffers — settle it before pooling them.
                            try:
                                handle_fut.result()
                            except BaseException:
                                pass
                        self._release_buffers(task)
                        _release(None)
                results.append(res_fut)
        except BaseException:
            # A failed stage must not strand earlier chunks: settle every
            # submitted future (their own errors surface via the first
            # .result() below or are superseded by this raise).
            for f in results:
                try:
                    f.result()
                except BaseException:
                    pass
            raise
        # Settle EVERY chunk before surfacing the first failure: a raise
        # mid-gather would leave later readbacks running against pooled
        # buffers the caller thinks are free.
        out, first_exc = [], None
        for f in results:
            try:
                out.append(f.result())
            except BaseException as e:  # noqa: BLE001 — re-raised below
                if first_exc is None:
                    first_exc = e
                out.append(None)
        if first_exc is not None:
            raise first_exc
        return out
