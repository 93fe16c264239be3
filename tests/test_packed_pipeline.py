"""Round-3 crypto path: packed wire-format staging + pipelined verifier,
urgent dispatch bypass, and payload-maker intake guards.

The packed path is the production transport for TPU verification
(ops/ed25519.prepare_batch_packed -> Ed25519TpuVerifier packed pipeline);
these tests pin its parity with the f32 path and with OpenSSL, on the CPU
backend (conftest forces the virtual CPU mesh — same code path as TPU).
"""

import asyncio
import random

import numpy as np
import pytest

from hotstuff_tpu.ops import ed25519 as ed


def _signed(n, seed=3, msg_len=32):
    pytest.importorskip("cryptography")
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    rng = random.Random(seed)
    msgs, pks, sigs = [], [], []
    for _ in range(n):
        sk = Ed25519PrivateKey.from_private_bytes(rng.randbytes(32))
        m = rng.randbytes(msg_len)
        msgs.append(m)
        pks.append(sk.public_key().public_bytes_raw())
        sigs.append(sk.sign(m))
    return msgs, pks, sigs


class TestPackedStaging:
    def test_native_matches_python(self):
        msgs, pks, sigs = _signed(33)
        native = ed.prepare_batch_packed(msgs, pks, sigs, allow_native=True)
        py = ed.prepare_batch_packed(msgs, pks, sigs, allow_native=False)
        assert np.array_equal(native["packed"], py["packed"])
        assert np.array_equal(native["s_ok"], py["s_ok"])

    def test_packed_rows_match_f32_staging(self):
        msgs, pks, sigs = _signed(17)
        packed = ed.prepare_batch_packed(msgs, pks, sigs, allow_native=False)
        f32 = ed.prepare_batch(msgs, pks, sigs, allow_native=False)
        p = packed["packed"]
        # rows 0-31 = A (with sign bit), 96-127 = h; f32 staging splits the
        # sign bit out of a_y and pre-nibbles the scalars
        a_bytes = p[0:32].astype(np.float32)
        a_bytes[31] = a_bytes[31] % 128
        assert np.array_equal(a_bytes, f32["a_y"])
        assert np.array_equal((p[31] >> 7).astype(np.float32), f32["a_sign"])
        assert np.array_equal(p[32:64].astype(np.float32), f32["r_enc"])
        h_lo = (p[96:128] & 0x0F).astype(np.float32)
        h_hi = (p[96:128] >> 4).astype(np.float32)
        assert np.array_equal(f32["h_digits"][0::2], h_lo)
        assert np.array_equal(f32["h_digits"][1::2], h_hi)

    def test_non_canonical_s_flagged(self):
        msgs, pks, sigs = _signed(4)
        sigs[2] = sigs[2][:32] + int(ed.L_ORDER).to_bytes(32, "little")
        staged = ed.prepare_batch_packed(msgs, pks, sigs)
        assert staged["s_ok"].tolist() == [True, True, False, True]

    @pytest.mark.parametrize("n", [1, 63, 300])
    @pytest.mark.parametrize("framed", [False, True], ids=["rows", "wire-view"])
    def test_rows_staging_equals_device_hash_staging(self, n, framed):
        """`prepare_rows_packed_dh` of the column views of (n, 128) wire rows
        msg | pk | sig is `prepare_batch_packed_dh` of the same triples byte
        for byte, `packed` and `s_ok`, s >= L included; also on the strided
        view that the sidecar cuts out of a request body (132-byte records)."""
        msgs, pks, sigs = _signed(n, seed=n)
        for i, s in ((0, ed.L_ORDER), (n // 2, ed.L_ORDER + 1), (n - 1, 2**256 - 1)):
            sigs[i] = sigs[i][:32] + int(s).to_bytes(32, "little")
        if framed:
            body = b"".join(b"\x20\0\0\0" + m + k + s for m, k, s in zip(msgs, pks, sigs))
            rows = np.frombuffer(body, np.uint8).reshape(n, 132)[:, 4:]
            assert rows.strides == (132, 1)
        else:
            rows = np.frombuffer(
                b"".join(m + k + s for m, k, s in zip(msgs, pks, sigs)), np.uint8
            ).reshape(n, 128)
        want = ed.prepare_batch_packed_dh(msgs, pks, sigs)
        got = ed.prepare_rows_packed_dh(rows[:, :32], rows[:, 32:64], rows[:, 64:])
        assert got["packed"].dtype == np.uint8 and got["packed"].flags.c_contiguous
        assert got["packed"].tobytes() == want["packed"].tobytes()
        assert got["packed"].shape == want["packed"].shape == (128, n)
        assert np.array_equal(got["s_ok"], want["s_ok"])
        assert not got["s_ok"][[0, n // 2, n - 1]].any()
        assert got["s_ok"].sum() == n - len({0, n // 2, n - 1})


class TestPipelinedVerifier:
    def test_chunked_pipeline_matches_openssl(self):
        msgs, pks, sigs = _signed(300)
        bad = [0, 150, 299]
        for i in bad:
            b = bytearray(sigs[i])
            b[5] ^= 0xFF
            sigs[i] = bytes(b)
        v = ed.Ed25519TpuVerifier(max_bucket=256, kernel="w4", chunk=128)
        mask = v.verify_batch_mask(msgs, pks, sigs)
        want = np.ones(300, bool)
        want[bad] = False
        assert np.array_equal(mask, want)

    def test_empty_batch(self):
        v = ed.Ed25519TpuVerifier(max_bucket=128, kernel="w4")
        assert v.verify_batch_mask([], [], []).shape == (0,)

    def test_single_chunk_path(self):
        msgs, pks, sigs = _signed(40)
        v = ed.Ed25519TpuVerifier(max_bucket=128, kernel="w4", chunk=128)
        assert v.verify_batch_mask(msgs, pks, sigs).all()


class TestUrgentBypass:
    def test_urgent_flush_bypasses_busy_dispatch_slots(self, run_async):
        """With every dispatch slot held by a slow backend call, an urgent
        group must still dispatch immediately (consensus-critical QC checks
        must not wait out a device round trip)."""
        pytest.importorskip("cryptography")
        from hotstuff_tpu.crypto import Digest, Signature, generate_keypair
        from hotstuff_tpu.crypto.backend import CpuBackend
        from hotstuff_tpu.crypto.batch_service import BatchVerificationService

        class SlowBackend(CpuBackend):
            def __init__(self, slow_event):
                super().__init__()
                self._slow = slow_event

            def verify_batch_mask(self, messages, keys, signatures):
                if len(messages) > 1:  # the big non-urgent batches
                    self._slow.wait(timeout=5)
                return super().verify_batch_mask(messages, keys, signatures)

        async def body():
            import threading

            release = threading.Event()
            svc = BatchVerificationService(
                SlowBackend(release), max_concurrent_dispatches=1
            )
            rng = random.Random(1)
            pk, sk = generate_keypair(rng)
            d = Digest.of(b"block")
            sig = Signature.new(d, sk)
            # occupy the single dispatch slot with a slow 2-item group
            slow = asyncio.create_task(
                svc.verify_group([d.data, d.data], [(pk, sig), (pk, sig)])
            )
            await asyncio.sleep(0.05)  # let it flush + block in the backend
            # urgent single check must complete while the slot is held
            ok = await asyncio.wait_for(
                svc.verify(d.data, pk, sig, urgent=True), timeout=1.0
            )
            assert ok
            release.set()
            assert await slow == [True, True]

        run_async(body())


class TestPayloadMakerGuards:
    def test_oversized_tx_dropped(self, run_async):
        from hotstuff_tpu.crypto import SignatureService
        from hotstuff_tpu.mempool.payload_maker import PayloadMaker
        from hotstuff_tpu.utils.actors import channel
        from tests.common import keys

        async def body():
            pk, sk = keys(1)[0]
            tx_in, core = channel(), channel()
            maker = PayloadMaker(pk, SignatureService(sk), 100, 0, tx_in, core)
            await tx_in.put(b"x" * 500)  # oversized: dropped
            await tx_in.put(b"y" * 60)
            await asyncio.sleep(0.05)  # let the maker ingest both
            payload = await maker.request_make()
            assert payload.transactions == (b"y" * 60,)

        run_async(body())

    def test_make_request_not_starved_by_tx_stream(self, run_async):
        """A consensus-driven make request must be served even while the tx
        queue is continuously refilled (drain-loop starvation guard)."""
        from hotstuff_tpu.crypto import SignatureService
        from hotstuff_tpu.mempool.payload_maker import PayloadMaker
        from hotstuff_tpu.utils.actors import channel, spawn
        from tests.common import keys

        async def body():
            pk, sk = keys(1)[0]
            tx_in, core = channel(), channel()
            maker = PayloadMaker(
                pk, SignatureService(sk), 10_000, 0, tx_in, core
            )

            stop = asyncio.Event()

            async def flood():
                while not stop.is_set():
                    await tx_in.put(b"t" * 64)
                    await asyncio.sleep(0)

            spawn(flood())
            try:
                payload = await asyncio.wait_for(maker.request_make(), 2.0)
                assert payload is not None
            finally:
                stop.set()

        run_async(body())


class TestSelectorFairness:
    def test_round_robin_no_starvation(self, run_async):
        from hotstuff_tpu.utils.actors import Selector, channel

        async def body():
            a, b = channel(), channel()
            sel = Selector()
            sel.add("a", a.get)
            sel.add("b", b.get)
            for _ in range(10):
                await a.put("A")
            await b.put("B")
            served = [await sel.next() for _ in range(5)]
            names = [n for n, _ in served]
            assert "b" in names, f"flooded branch starved b: {names}"

        run_async(body())

    def test_starved_priority_branch_served_within_bound(self, run_async):
        """A continuously-ready priority-0 flood must not defer a ready
        priority-1 branch forever (a peer spraying cheap SyncRequests would
        otherwise suppress the pacemaker indefinitely): after at most
        STARVATION_BOUND consecutive losses the deferred branch is served."""
        from hotstuff_tpu.utils.actors import Selector, channel

        async def body():
            msg, timer = channel(), channel()
            sel = Selector()
            sel.add("message", msg.get)
            sel.add("timer", timer.get, priority=1)
            await timer.put("T")
            for _ in range(sel.STARVATION_BOUND + 5):
                await msg.put("M")
            await asyncio.sleep(0.01)  # both branches armed + done
            order = [
                (await sel.next())[0]
                for _ in range(sel.STARVATION_BOUND + 2)
            ]
            assert "timer" in order, f"timer starved: {order}"
            # ...but it still loses the first STARVATION_BOUND - 1 ties.
            assert order.index("timer") >= sel.STARVATION_BOUND - 1, order

        run_async(body())

    def test_priority_branch_loses_ties(self, run_async):
        """A priority-1 branch (the pacemaker pattern) must lose ties to
        priority-0 branches even when both are continuously ready."""
        from hotstuff_tpu.utils.actors import Selector, channel

        async def body():
            msg, timer = channel(), channel()
            sel = Selector()
            sel.add("message", msg.get)
            sel.add("timer", timer.get, priority=1)
            await timer.put("T")
            for _ in range(3):
                await msg.put("M")
            await asyncio.sleep(0.01)  # both branches armed + done
            order = [(await sel.next())[0] for _ in range(4)]
            assert order == ["message", "message", "message", "timer"], order

        run_async(body())
