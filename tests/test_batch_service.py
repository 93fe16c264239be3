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
