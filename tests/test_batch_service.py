"""BatchVerificationService: deadline/size flush semantics and correctness."""

import asyncio
import random

import pytest

pytest.importorskip("cryptography")

from hotstuff_tpu.crypto import Digest, Signature, generate_keypair
from hotstuff_tpu.crypto.backend import CpuBackend
from hotstuff_tpu.crypto.batch_service import BatchVerificationService


@pytest.fixture
def keys():
    rng = random.Random(0)
    return [generate_keypair(rng) for _ in range(4)]


def test_single_requests_batched(keys, run_async):
    async def body():
        svc = BatchVerificationService(CpuBackend())
        digest = Digest.of(b"vote")
        results = await asyncio.gather(
            *[
                svc.verify(digest.data, pk, Signature.new(digest, sk))
                for pk, sk in keys
            ]
        )
        assert results == [True] * 4
        # all four individual requests coalesced into one backend flush
        assert svc.stats["flushes"] == 1 and svc.stats["verified"] == 4

    run_async(body())


def test_invalid_items_isolated(keys, run_async):
    async def body():
        svc = BatchVerificationService(CpuBackend())
        digest = Digest.of(b"vote")
        pk0, sk0 = keys[0]
        pk1, sk1 = keys[1]
        good = svc.verify(digest.data, pk0, Signature.new(digest, sk0))
        bad = svc.verify(digest.data, pk1, Signature.new(digest, sk0))
        assert await asyncio.gather(good, bad) == [True, False]

    run_async(body())


def test_size_flush_before_deadline(keys, run_async):
    async def body():
        svc = BatchVerificationService(
            CpuBackend(), max_batch=8
        )
        digest = Digest.of(b"vote")
        pk, sk = keys[0]
        sig = Signature.new(digest, sk)
        t0 = asyncio.get_running_loop().time()
        results = await asyncio.gather(
            *[svc.verify(digest.data, pk, sig) for _ in range(8)]
        )
        took = asyncio.get_running_loop().time() - t0
        assert all(results)
        assert took < 5.0, "size flush must not wait for the deadline"
        assert svc.stats["size_flushes"] >= 1

    run_async(body())


def test_group_larger_than_max_batch(keys, run_async):
    async def body():
        svc = BatchVerificationService(
            CpuBackend(), max_batch=3
        )
        digest = Digest.of(b"qc")
        pairs = [(pk, Signature.new(digest, sk)) for pk, sk in keys]
        mask = await svc.verify_group([digest.data] * 4, pairs)
        assert mask == [True] * 4

    run_async(body())


class _RecordingBackend(CpuBackend):
    """CpuBackend that records each dispatch's size, with a latch to hold
    dispatches in flight."""

    def __init__(self, gate: "asyncio.Event | None" = None):
        super().__init__()
        self.calls: list[int] = []
        self._gate = gate

    def verify_batch_mask(self, messages, keys, signatures):
        self.calls.append(len(messages))
        if self._gate is not None:
            # Runs in a to_thread worker: block until released.
            import time

            while not self._gate.is_set():
                time.sleep(0.001)
        return super().verify_batch_mask(messages, keys, signatures)


def test_urgent_group_dispatches_separately(keys, run_async):
    """An urgent QC-sized group drained in the same coalescing pass as
    workload groups must NOT ride the combined backend call (ADVICE r3):
    it flushes in its own dispatch."""

    async def body():
        backend = _RecordingBackend()
        svc = BatchVerificationService(backend, max_batch=1000)
        digest = Digest.of(b"vote")
        sigs = {pk: Signature.new(digest, sk) for pk, sk in keys}
        pk0, sk0 = keys[0]

        big = [(pk, sigs[pk]) for pk, _ in keys] * 25  # 100-item workload
        small = [(pk0, sigs[pk0])] * 3  # urgent QC check

        w = asyncio.ensure_future(
            svc.verify_group([digest.data] * len(big), big, urgent=False)
        )
        await asyncio.sleep(0)  # queue the workload group first
        u = asyncio.ensure_future(
            svc.verify_group([digest.data] * 3, small, urgent=True)
        )
        assert all(await u) and all(await w)
        assert sorted(backend.calls) == [3, 100], backend.calls

    run_async(body())


def test_urgent_flush_not_blocked_by_full_dispatch_slots(keys, run_async):
    """With every dispatch slot held by in-flight workload batches, an
    urgent flush must still complete promptly (the semaphore is acquired
    inside _dispatch, and urgent dispatches bypass it)."""

    async def body():
        gate = asyncio.Event()

        class GatedBackend(_RecordingBackend):
            def verify_batch_mask(self, messages, keys_, signatures):
                self.calls.append(len(messages))
                import time

                if len(messages) > 10:  # only workload batches block
                    while not gate.is_set():
                        time.sleep(0.001)
                return CpuBackend.verify_batch_mask(
                    self, messages, keys_, signatures
                )

        backend = GatedBackend()
        svc = BatchVerificationService(
            backend, max_batch=50, max_concurrent_dispatches=2
        )
        digest = Digest.of(b"vote")
        pk0, sk0 = keys[0]
        sig = Signature.new(digest, sk0)

        # Two size-flushed workload batches occupy both dispatch slots.
        workers = [
            asyncio.ensure_future(
                svc.verify_group(
                    [digest.data] * 50, [(pk0, sig)] * 50, urgent=False
                )
            )
            for _ in range(2)
        ]
        await asyncio.sleep(0.05)  # both in flight, gated

        t0 = asyncio.get_running_loop().time()
        mask = await asyncio.wait_for(
            svc.verify(digest.data, pk0, sig, urgent=True), 1.0
        )
        took = asyncio.get_running_loop().time() - t0
        assert mask is True
        assert took < 0.5, f"urgent flush waited {took:.3f}s behind workload"
        gate.set()
        assert all(all(m) for m in await asyncio.gather(*workers))

    run_async(body())


# ---------------------------------------------------------------------------
# The critical lane's dispatch window, through the real service with real
# verdicts (ISSUE 35; the policy's own cases are in tests/test_scheduler.py).


class _GriddedBackend(_RecordingBackend):
    """OpenSSL verdicts behind a device grid, as the sidecar's TpuBackend
    presents one: the k-th call parks on its own gate."""

    bucket_alignment = 4096

    def __init__(self):
        import threading

        super().__init__()
        self.gates = [threading.Event() for _ in range(4)]
        self._lock = threading.Lock()

    def verify_batch_mask(self, messages, keys, signatures):
        with self._lock:
            k = len(self.calls)
            self.calls.append(len(messages))
        assert self.gates[k].wait(timeout=20), f"call {k} never released"
        return CpuBackend.verify_batch_mask(self, messages, keys, signatures)


@pytest.mark.parametrize(
    "kinds", ["rows+list", "list+rows", "rows+rows", "list+list"]
)
def test_held_critical_groups_share_one_dispatch_and_keep_their_masks(
    kinds, run_async
):
    """Urgent requests as `fab local` payloads make them (64-255 rows, a
    fifth of the triples corrupted in the probe's seven ways): the third
    and fourth wait while two critical programs are in flight, ride ONE
    dispatch when the first ends, columnar and list groups mixed, and
    every future gets exactly its own slice of the mask, as OpenSSL judges
    each triple."""
    import numpy as np

    from chipbench import reference as ref

    sizes = (70, 130, 200, 255)
    corpus = [
        tuple(col[:n] for col in request)
        for n, request in zip(sizes, ref.probe_corpus(35, 4, 255, 5))
    ]
    expected = [
        [ref.verify_strict(m, k, s) for m, k, s in zip(*request)]
        for request in corpus
    ]
    assert all(0.15 < 1 - sum(e) / len(e) < 0.25 for e in expected)

    def submit(svc, request, kind):
        msgs, pks, sigs = request
        if kind == "rows":
            rows = np.frombuffer(
                b"".join(m + k + s for m, k, s in zip(msgs, pks, sigs)), np.uint8
            ).reshape(len(msgs), 128)
            return asyncio.ensure_future(svc.verify_rows(rows, urgent=True))
        from hotstuff_tpu.crypto.primitives import PublicKey

        pairs = [(PublicKey(k), Signature(s)) for k, s in zip(pks, sigs)]
        return asyncio.ensure_future(svc.verify_group(msgs, pairs, urgent=True))

    async def until(cond):
        for _ in range(2000):
            if cond():
                return
            await asyncio.sleep(0.002)
        raise AssertionError(f"timed out: calls {backend.calls}")

    async def body():
        svc = BatchVerificationService(backend)
        third, fourth = kinds.split("+")
        futs = [submit(svc, corpus[0], "rows")]
        await until(lambda: len(backend.calls) == 1)
        futs.append(submit(svc, corpus[1], "list"))
        await until(lambda: len(backend.calls) == 2)
        futs.append(submit(svc, corpus[2], third))
        futs.append(submit(svc, corpus[3], fourth))
        await asyncio.sleep(0.05)
        assert backend.calls == [70, 130]  # both held, nothing on a timer
        backend.gates[0].set()
        await until(lambda: len(backend.calls) == 3)
        assert backend.calls == [70, 130, 455]  # one dispatch for both
        backend.gates[1].set()
        backend.gates[2].set()
        masks = await asyncio.wait_for(asyncio.gather(*futs), 30.0)
        assert [len(m) for m in masks] == list(sizes)
        for mask, want, kind in zip(masks, expected, ("rows", "list", third, fourth)):
            assert isinstance(mask, np.ndarray if kind == "rows" else list)
            assert [bool(b) for b in mask] == want
        assert svc.stats["flushes"] == 3 and svc.stats["urgent_flushes"] == 3
        assert svc.scheduler.stats["critical_dispatches"] == 3

    backend = _GriddedBackend()
    run_async(body())
