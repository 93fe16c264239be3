"""DeviceScheduler: typed source lanes, preemptive critical dispatch,
alignment-grid bucket sizing, continuous refill.

Dependency-free by design (stub backend, no `cryptography`, no jax): the
scheduler never looks at message bytes, so these tests exercise the real
admission → bucket → dispatch loop with junk triples.
"""

import asyncio

import pytest

from hotstuff_tpu.crypto import scheduler as sched
from hotstuff_tpu.crypto.backend import CryptoBackend
from hotstuff_tpu.crypto.batch_service import BatchVerificationService
from hotstuff_tpu.crypto.primitives import PublicKey, Signature

PK = PublicKey(b"\x01" * 32)
SIG = Signature(b"\x02" * 64)


def _group(n: int, tag: bytes = b"m"):
    msgs = [tag + bytes([i % 256, i // 256]) for i in range(n)]
    return msgs, [(PK, SIG)] * n


class StubBackend(CryptoBackend):
    """Accept-everything backend that records each dispatch's size; an
    optional bucket_alignment mimics TpuBackend's device grid."""

    name = "stub"

    def __init__(self, alignment: int = 0):
        self.calls: list[int] = []
        if alignment:
            self.bucket_alignment = alignment

    def verify_batch_mask(self, messages, keys, signatures, **_kw):
        self.calls.append(len(messages))
        return [True] * len(messages)


def test_resolve_source_mapping():
    assert sched.resolve_source(None, urgent=True) is sched.CONSENSUS
    assert sched.resolve_source(None, urgent=False) is sched.MEMPOOL
    assert sched.resolve_source("ingress", urgent=True) is sched.INGRESS
    with pytest.raises(ValueError, match="unknown verification source"):
        sched.resolve_source("nonsense", urgent=False)


def test_drain_order_covers_every_registered_class():
    """The starvation invariant the lint enforces: one group per class,
    no further arrivals — every class must be selected by the loop."""
    order = sched.drain_order()
    assert set(order) == set(sched.SOURCE_CLASSES)
    # Critical first, then the batched lanes in priority order.
    assert order[0] == "consensus"
    assert order.index("sync") < order.index("mempool")


def test_critical_groups_coalesce_into_one_flush(run_async):
    """Simultaneous consensus-critical submissions flush together (the
    legacy single-queue property the critical lane must keep)."""

    async def body():
        backend = StubBackend()
        svc = BatchVerificationService(backend, inline=True)
        msgs, pairs = _group(1)
        results = await asyncio.gather(
            *[
                svc.verify(msgs[0], PK, SIG, source="consensus")
                for _ in range(4)
            ]
        )
        assert results == [True] * 4
        assert svc.stats["flushes"] == 1 and svc.stats["verified"] == 4
        assert svc.scheduler.stats["critical_dispatches"] == 1

    run_async(body())


def test_critical_preempts_forming_bulk_bucket(run_async):
    """A critical arrival jumps the queue AND closes the forming bulk
    bucket early: critical dispatches first, the formed bulk ships right
    behind it instead of waiting out its deadline."""

    async def body():
        backend = StubBackend()
        svc = BatchVerificationService(backend, inline=True)
        bm, bp = _group(100, b"w")
        w = asyncio.ensure_future(
            svc.verify_group(bm, bp, source="mempool", dedup=False)
        )
        await asyncio.sleep(0.001)  # bulk forming (mempool deadline is 4 ms)
        cm, cp = _group(3, b"q")
        u = asyncio.ensure_future(
            svc.verify_group(cm, cp, source="consensus", dedup=False)
        )
        assert all(await u) and all(await w)
        assert backend.calls == [3, 100], backend.calls
        assert svc.scheduler.stats["preempt_closes"] == 1
        # Queue-delay attribution landed on each group's own lane.
        summary = svc.lane_stats.summary()
        assert summary["consensus"]["count"] == 1
        assert summary["mempool"]["count"] == 1

    run_async(body())


def test_alignment_grid_bucket_sizing(run_async):
    """With a device grid of 64, 5×16 pending signatures close a 64-wide
    bucket (zero pad lanes) and leave the 16-residue to its own deadline
    flush — the continuous-refill shape."""

    async def body():
        backend = StubBackend(alignment=64)
        svc = BatchVerificationService(backend, inline=True)
        futs = []
        for i in range(5):
            m, p = _group(16, b"g%d" % i)
            futs.append(
                asyncio.ensure_future(
                    svc.verify_group(m, p, source="ingress", dedup=False)
                )
            )
        masks = await asyncio.gather(*futs)
        assert all(all(m) for m in masks)
        assert backend.calls == [64, 16], backend.calls
        assert svc.scheduler.stats["buckets"] == 2

    run_async(body())


def test_urgent_bit_maps_to_critical_lane(run_async):
    """Un-migrated callers (urgent=True, no source=) keep riding the
    preemptive lane — resolve_source's compatibility contract, through
    the real service."""

    async def body():
        backend = StubBackend()
        svc = BatchVerificationService(backend, inline=True)
        m, p = _group(2)
        assert await svc.verify_group(m, p, urgent=True, dedup=False) == [True] * 2
        assert svc.scheduler.lanes["consensus"].dispatched == 1
        assert svc.scheduler.lanes["mempool"].dispatched == 0

    run_async(body())


def test_sync_lane_flushes_before_mempool_deadline(run_async):
    """A sync group's 1 ms deadline closes the bucket long before the
    mempool class's 4 ms — and the flush drains lanes in priority order,
    so the pending mempool group rides along instead of waiting."""

    async def body():
        backend = StubBackend()
        svc = BatchVerificationService(backend, inline=True)
        loop = asyncio.get_running_loop()
        mm, mp = _group(10, b"b")
        w = asyncio.ensure_future(
            svc.verify_group(mm, mp, source="mempool", dedup=False)
        )
        sm, sp = _group(1, b"s")
        t0 = loop.time()
        ok = await svc.verify(sm[0], PK, SIG, source="sync")
        took = loop.time() - t0
        assert ok is True
        assert took < 0.05, f"sync flush waited {took:.3f}s"
        assert all(await w)
        assert backend.calls == [11], backend.calls  # one mixed bucket

    run_async(body())


def test_scheduler_summary_shape(run_async):
    async def body():
        svc = BatchVerificationService(StubBackend(), inline=True)
        m, p = _group(2)
        await svc.verify_group(m, p, source="ingress", dedup=False)
        s = svc.scheduler.summary()
        assert set(s["lanes"]) == set(sched.SOURCE_CLASSES)
        lane = s["lanes"]["ingress"]
        assert lane["enqueued"] == 1 and lane["dispatched"] == 1
        assert lane["depth"] == 0
        assert "ingress" in s["queue_delay"]
        assert s["submitted"] == 1

    run_async(body())


def test_lane_stats_percentiles():
    stats = sched.LaneStats()
    for i in range(100):
        stats.note("mempool", i / 1000.0)
    s = stats.summary()["mempool"]
    assert s["count"] == 100
    assert 45.0 <= s["p50_ms"] <= 55.0
    assert 95.0 <= s["p99_ms"] <= 99.0
    assert s["max_ms"] == 99.0


# ---------------------------------------------------------------------------
# Cross-chip work stealing (ISSUE 9): bulk buckets dispatch to whichever
# backend has a free pipeline slot; the home backend keeps every critical
# dispatch; inline (chaos) mode forces stealing off.


class BlockingBackend(CryptoBackend):
    """Home backend whose bulk verifications park on a gate — the 'device
    busy' half of the steal scenario (two fake backends, no jax)."""

    name = "blocking"

    def __init__(self, gate):
        self.calls: list[int] = []
        self._gate = gate

    def verify_batch_mask(self, messages, keys, signatures, **_kw):
        self.calls.append(len(messages))
        self._gate.wait(timeout=5)
        return [True] * len(messages)


def test_bulk_bucket_steals_to_free_sibling_backend(run_async):
    """With the home backend's single bulk slot held by an in-flight
    dispatch, the next bulk bucket ships to the sibling shard instead of
    queueing behind it — and the steal is counted."""

    async def body():
        import threading

        gate = threading.Event()
        home = BlockingBackend(gate)
        sibling = StubBackend()
        svc = BatchVerificationService(
            home,
            scheduler_config=sched.SchedulerConfig(bulk_concurrency=1),
            steal_backends=[sibling],
        )
        assert svc.scheduler.n_backends == 2
        m1, p1 = _group(8, b"a")
        f1 = asyncio.ensure_future(
            svc.verify_group(m1, p1, source="mempool", dedup=False)
        )
        for _ in range(400):  # wait until home's dispatch is in flight
            if home.calls:
                break
            await asyncio.sleep(0.005)
        assert home.calls == [8]
        m2, p2 = _group(4, b"b")
        f2 = asyncio.ensure_future(
            svc.verify_group(m2, p2, source="mempool", dedup=False)
        )
        # the second bucket must complete on the sibling while home is
        # still parked on the gate
        assert all(await asyncio.wait_for(f2, 5.0))
        assert sibling.calls == [4], sibling.calls
        assert home.calls == [8], home.calls
        assert svc.scheduler.stats["steals"] == 1
        assert svc.scheduler.summary()["backends"] == 2
        gate.set()
        assert all(await asyncio.wait_for(f1, 5.0))

    run_async(body())


def test_critical_never_steals_even_with_siblings(run_async):
    """Consensus-critical dispatches always ride the home backend (the
    committee-registered one), no matter how many siblings are free."""

    async def body():
        home = StubBackend()
        sibling = StubBackend()
        svc = BatchVerificationService(
            home, steal_backends=[sibling]
        )
        m, p = _group(3, b"q")
        assert all(await svc.verify_group(m, p, source="consensus", dedup=False))
        assert home.calls == [3]
        assert sibling.calls == []
        assert svc.scheduler.stats["steals"] == 0

    run_async(body())


def test_inline_chaos_mode_forces_stealing_off(run_async):
    """inline=True (the chaos virtual-time mode) must stay bit-identical
    per seed: which backend a bucket lands on cannot depend on thread
    timing, so steal_backends is dropped and n_backends stays 1."""

    async def body():
        svc = BatchVerificationService(
            StubBackend(), inline=True, steal_backends=[StubBackend()]
        )
        assert svc.scheduler.n_backends == 1
        assert svc._steal_backends == []
        m, p = _group(2)
        assert all(await svc.verify_group(m, p, source="mempool", dedup=False))
        assert svc.scheduler.stats["steals"] == 0

    run_async(body())


# ---------------------------------------------------------------------------
# The critical lane's dispatch window (ISSUE 35): on a backend with a device
# grid a critical dispatch is a whole device program, so the lane keeps
# `bulk_concurrency` of them in flight on an account of its own and groups
# that find it full ride the next one together. Gridless backends never
# consult it.


class GatedBackend(CryptoBackend):
    """Backend whose k-th call parks on its own gate, then raises if told
    to; `hold_below` lets calls of fewer signatures through ungated (the
    critical group among blocked bulk buckets)."""

    name = "gated"

    def __init__(self, alignment: int = 0, raises=(), hold_below: int = 0):
        import threading

        self.calls: list[int] = []
        self.gates = [threading.Event() for _ in range(8)]
        self._raises = set(raises)
        self._hold_below = hold_below
        self._lock = threading.Lock()
        if alignment:
            self.bucket_alignment = alignment

    def verify_batch_mask(self, messages, keys, signatures, **_kw):
        with self._lock:
            k = len(self.calls)
            self.calls.append(len(messages))
        if len(messages) >= self._hold_below:
            assert self.gates[k].wait(timeout=10), f"call {k} never released"
        if k in self._raises:
            raise RuntimeError(f"device refused call {k}")
        return [True] * len(messages)


async def _until(cond, what: str):
    for _ in range(1000):
        if cond():
            return
        await asyncio.sleep(0.002)
    raise AssertionError(f"timed out waiting for {what}")


def _critical_counts():
    return (
        sched._M_CRITICAL.value,
        sched._M_CRITICAL_GROUPS.value,
        sched._M_CRITICAL_HELD.value,
    )


def _submit_critical(svc, n: int, tag: bytes):
    m, p = _group(n, tag)
    return asyncio.ensure_future(
        svc.verify_group(m, p, source="consensus", dedup=False)
    )


def test_critical_window_holds_then_ships_the_held_as_one(run_async):
    """Two critical device programs in flight: the third and fourth groups
    stay in the lane (no third program queues on the device) and ship as
    ONE dispatch the moment the first ends — no timer, and only they count
    as held."""

    async def body():
        backend = GatedBackend(alignment=64)
        svc = BatchVerificationService(backend)
        before = _critical_counts()
        a = _submit_critical(svc, 70, b"a")
        await _until(lambda: backend.calls == [70], "the first program")
        b = _submit_critical(svc, 80, b"b")
        await _until(lambda: backend.calls == [70, 80], "the second program")
        c = _submit_critical(svc, 90, b"c")
        d = _submit_critical(svc, 100, b"d")
        await asyncio.sleep(0.05)  # long past any flush deadline (4 ms at most)
        assert backend.calls == [70, 80], backend.calls
        assert svc.scheduler.depth() == 2 and not c.done() and not d.done()
        assert svc.scheduler._inflight_critical == 2
        backend.gates[0].set()  # the older program ends ...
        await _until(lambda: len(backend.calls) == 3, "the shared program")
        assert backend.calls == [70, 80, 190]  # ... and both ride the next
        assert len(await a) == 70 and not b.done()
        backend.gates[1].set()
        backend.gates[2].set()
        assert [len(await f) for f in (b, c, d)] == [80, 90, 100]
        await _until(lambda: svc.scheduler._inflight_critical == 0, "the slots")
        after = _critical_counts()
        assert [x - y for x, y in zip(after, before)] == [3, 4, 2]
        assert svc.scheduler.stats["critical_dispatches"] == 3

    run_async(body())


def test_bulk_dispatches_in_flight_never_hold_a_critical_group(run_async):
    """The window is the critical lane's own: with both bulk slots of a
    gridded backend taken, a critical group still ships at once."""

    async def body():
        backend = GatedBackend(alignment=64, hold_below=64)
        svc = BatchVerificationService(backend)
        before = _critical_counts()
        bulk = []
        for i in range(2):  # a full grid row each: two grid flushes in flight
            m, p = _group(64, b"w%d" % i)
            bulk.append(asyncio.ensure_future(
                svc.verify_group(m, p, source="mempool", dedup=False)
            ))
            await _until(lambda: len(backend.calls) == i + 1, "a bulk program")
        assert svc.scheduler._inflight == [2]
        q = _submit_critical(svc, 3, b"q")
        assert await asyncio.wait_for(q, 5.0) == [True] * 3
        assert backend.calls == [64, 64, 3] and not any(f.done() for f in bulk)
        for gate in backend.gates:
            gate.set()
        assert all(all(m) for m in await asyncio.gather(*bulk))
        after = _critical_counts()
        assert [x - y for x, y in zip(after, before)] == [1, 1, 0]

    run_async(body())


def test_gridless_backend_dispatches_every_critical_group_as_before(run_async):
    """No grid (every node's RemoteBackend, CpuBackend): four critical
    groups arriving one after another make four dispatches in flight at
    once, and the window's account is never touched."""

    async def body():
        backend = GatedBackend()
        svc = BatchVerificationService(backend)
        before = _critical_counts()
        futs = []
        for i in range(4):
            futs.append(_submit_critical(svc, 70 + i, b"g%d" % i))
            await _until(lambda: len(backend.calls) == i + 1, f"dispatch {i}")
        assert backend.calls == [70, 71, 72, 73]
        assert svc.scheduler._inflight_critical == 0
        for gate in backend.gates:
            gate.set()
        assert [len(await f) for f in futs] == [70, 71, 72, 73]
        after = _critical_counts()
        assert [x - y for x, y in zip(after, before)] == [4, 4, 0]

    run_async(body())


def test_critical_dispatch_that_raises_frees_its_slot(run_async):
    """A device program that raises fails its own groups alone and gives
    its slot back: the group held behind it ships and verifies."""

    async def body():
        backend = GatedBackend(alignment=64, raises={0})
        svc = BatchVerificationService(backend)
        a = _submit_critical(svc, 70, b"a")
        await _until(lambda: len(backend.calls) == 1, "the first program")
        b = _submit_critical(svc, 80, b"b")
        await _until(lambda: len(backend.calls) == 2, "the second program")
        c = _submit_critical(svc, 90, b"c")
        await asyncio.sleep(0.02)
        assert backend.calls == [70, 80]
        backend.gates[0].set()
        with pytest.raises(RuntimeError, match="device refused call 0"):
            await a
        await _until(lambda: backend.calls == [70, 80, 90], "the held group")
        backend.gates[1].set()
        backend.gates[2].set()
        assert len(await b) == 80 and len(await c) == 90
        await _until(lambda: svc.scheduler._inflight_critical == 0, "the slots")

    run_async(body())
