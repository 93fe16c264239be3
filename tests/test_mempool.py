"""Mempool tests, mirroring mempool/src/tests/{mempool,core,synchronizer}_tests.rs."""

import asyncio

import pytest

from hotstuff_tpu.consensus.mempool_driver import (
    MempoolGet,
    MempoolVerify,
    PayloadStatus,
)
from hotstuff_tpu.crypto import Digest, SignatureService
from hotstuff_tpu.mempool import Mempool, MempoolParameters, Payload
from hotstuff_tpu.mempool.messages import (
    decode_mempool_message,
    encode_mempool_message,
    PayloadRequest,
)
from hotstuff_tpu.network.net import frame
from hotstuff_tpu.store import Store
from hotstuff_tpu.utils.actors import channel
from hotstuff_tpu.utils.serde import Writer
# Whole-module OpenSSL dependency (tests/common.py is importable
# without the wheel; the skip now lives with the modules that need it).
pytest.importorskip("cryptography")

from tests.common import chain, committee, keys
from tests.common_mempool import mempool_committee


def test_payload_roundtrip_and_verify():
    cmt = mempool_committee(0)
    pk, sk = keys()[0]
    txs = [b"\x01" + bytes(40), b"\x00" + (7).to_bytes(8, "big") + bytes(32)]
    payload = Payload.new_from_key(txs, pk, sk)
    assert payload.verify(cmt)
    assert payload.size() == sum(len(t) for t in txs)
    assert payload.sample_tx_ids() == [7]
    decoded = decode_mempool_message(encode_mempool_message(payload))
    assert decoded == payload


def test_mempool_end_to_end(run_async, base_port):
    """Four mempools over real TCP; client txs to every Front; every node's
    own payload is gossiped to all others; consensus Get returns digests and
    Verify accepts (mempool/src/tests/mempool_tests.rs:16-90)."""

    async def body():
        n = 4
        cmt = mempool_committee(base_port, n)
        params = MempoolParameters(max_payload_size=128, min_block_delay=10)
        cm_channels = []
        for pk, sk in keys(n):
            store = Store()
            sig = SignatureService(sk)
            cm = channel()
            cm_channels.append(cm)
            Mempool.run(pk, cmt, params, store, sig, cm, channel())
        await asyncio.sleep(0.1)

        # Send enough transactions to each front to trigger payload flushes.
        for i, (pk, _) in enumerate(keys(n)):
            _, w = await asyncio.open_connection("127.0.0.1", base_port + i)
            for j in range(10):
                w.write(frame(b"\x01" + bytes(60)))
            await w.drain()
            w.close()

        # Each node must produce digests for consensus.
        for cm in cm_channels:
            digests = []
            for _ in range(50):  # poll: payload making is async
                fut = asyncio.get_running_loop().create_future()
                await cm.put(MempoolGet(500, fut))
                digests = await asyncio.wait_for(fut, 5)
                if digests:
                    break
                await asyncio.sleep(0.1)
            assert digests, "mempool never produced a payload digest"

    run_async(body())


def test_verify_payload_missing_then_wait_and_loopback(run_async, base_port):
    """The suspend/resume contract for payload availability
    (mempool/src/tests/synchronizer_tests.rs:29-88)."""

    async def body():
        n = 4
        mcmt = mempool_committee(base_port, n)
        ccmt = committee(base_port + 2 * n)
        params = MempoolParameters()
        pk, sk = keys()[0]
        store = Store()
        sig = SignatureService(sk)
        cm = channel()
        consensus_channel = channel()
        core = Mempool.run(pk, mcmt, params, store, sig, cm, consensus_channel)
        await asyncio.sleep(0.05)

        # A block referencing a payload we don't have.
        author_pk, author_sk = keys()[1]
        payload = Payload.new_from_key([b"\x01" + bytes(40)], author_pk, author_sk)
        blocks = chain(1, ccmt)
        block = blocks[0]
        object.__setattr__(block, "payload", (payload.digest(),))

        fut = asyncio.get_running_loop().create_future()
        await cm.put(MempoolVerify(block, fut))
        assert await asyncio.wait_for(fut, 5) == PayloadStatus.WAIT

        # The payload arrives (as if from the author's mempool): store write
        # resolves the waiter, which loops the block back to consensus.
        w = Writer()
        payload.encode(w)
        await store.write(b"payload:" + payload.digest().data, w.bytes())
        lb = await asyncio.wait_for(consensus_channel.get(), 5)
        assert lb.block == block

        # Now verification accepts.
        fut2 = asyncio.get_running_loop().create_future()
        await cm.put(MempoolVerify(block, fut2))
        assert await asyncio.wait_for(fut2, 5) == PayloadStatus.ACCEPT

    run_async(body())


def test_payload_request_served(run_async, base_port):
    """A peer's PayloadRequest is answered with the stored payload
    (mempool/src/core.rs:236-249)."""

    async def body():
        n = 4
        cmt = mempool_committee(base_port, n)
        params = MempoolParameters(max_payload_size=64, min_block_delay=10)
        stores = []
        for pk, sk in keys(n):
            store = Store()
            stores.append(store)
            Mempool.run(pk, cmt, params, store, SignatureService(sk), channel(), channel())
        await asyncio.sleep(0.1)

        # Node 0 makes a payload (via its front) and gossips it everywhere.
        _, w = await asyncio.open_connection("127.0.0.1", base_port + 0)
        for _ in range(5):
            w.write(frame(b"\x01" + bytes(60)))
        await w.drain()

        # Wait for gossip to reach node 1's store.
        digest = None
        for _ in range(50):
            await asyncio.sleep(0.1)
            # find any payload key in node 1's store
            keys_found = [
                k for k in stores[1]._data.keys() if k.startswith(b"payload:")
            ]
            if keys_found:
                digest = Digest(keys_found[0][len(b"payload:"):])
                break
        assert digest is not None, "payload gossip never arrived"

        # Node 3 requests it from node 1, pretending to have missed it:
        # connect straight to node 1's mempool port with a PayloadRequest
        # naming node 2 as requester; node 2's store must then receive it.
        requester = keys(n)[2][0]
        msg = encode_mempool_message(PayloadRequest((digest,), requester))
        _, w2 = await asyncio.open_connection("127.0.0.1", base_port + n + 1)
        w2.write(frame(msg))
        await w2.drain()
        for _ in range(50):
            await asyncio.sleep(0.1)
            if (b"payload:" + digest.data) in stores[2]._data:
                return
        raise AssertionError("requested payload never delivered")

    run_async(body())


class _ScriptReader:
    """Scripted stream: each chunk is one read() result; EOF after."""

    def __init__(self, chunks):
        self.chunks = list(chunks)

    async def read(self, n):
        return self.chunks.pop(0) if self.chunks else b""


class _FakeWriter:
    def close(self):
        pass


def _bare_front(q):
    from hotstuff_tpu.mempool.front import Front

    front = Front.__new__(Front)  # no listener: drive _handle directly
    front._deliver = q
    front.dropped = 0
    return front


def test_front_drop_oldest_admission_control(run_async):
    """Overload: a full intake queue evicts the OLDEST tx for the newest
    (bounded, fresh) instead of blocking the reader (unbounded latency)."""

    async def body():
        q = channel(3)
        front = _bare_front(q)
        reader = _ScriptReader([frame(bytes([i]) * 12) for i in range(10)])
        await front._handle(reader, _FakeWriter())
        assert front.dropped == 7
        assert q.qsize() == 3
        kept = [q.get_nowait()[0] for _ in range(3)]
        assert kept == [7, 8, 9], "queue must hold the newest transactions"

    run_async(body())


def test_front_parses_whole_burst(run_async):
    """A multi-frame TCP burst is fully drained from one read."""

    async def body():
        q = channel(10)
        front = _bare_front(q)
        burst = b"".join(frame(bytes([i]) * 8) for i in range(5))
        await front._handle(_ScriptReader([burst]), _FakeWriter())
        assert q.qsize() == 5
        assert [q.get_nowait()[0] for _ in range(5)] == [0, 1, 2, 3, 4]
        assert front.dropped == 0

    run_async(body())


def test_front_survives_byzantine_length_in_burst(run_async):
    """An oversized length prefix buffered BEHIND a valid frame must drop
    the connection cleanly (valid prefix delivered, no exception escapes
    the handler)."""

    async def body():
        q = channel(10)
        front = _bare_front(q)
        burst = frame(b"ok-tx-1") + b"\xff\xff\xff\xff" + b"x" * 32
        await front._handle(_ScriptReader([burst]), _FakeWriter())
        assert q.qsize() == 1 and q.get_nowait() == b"ok-tx-1"

    run_async(body())


def test_payload_maker_sheds_on_backlog(run_async):
    """With the mempool queue at capacity, incoming txs are shed before
    buffering — no signature burn, no payload flush."""

    async def body():
        from hotstuff_tpu.mempool.payload_maker import PayloadMaker

        pk, sk = keys()[0]
        tx_in, core_ch = channel(), channel()
        maker = PayloadMaker(pk, SignatureService(sk), 64, 0, tx_in, core_ch)
        maker.backlog_fn = lambda: True
        for _ in range(5):
            await tx_in.put(b"\x01" + bytes(40))
        await asyncio.sleep(0.05)
        assert maker.shed == 5
        assert maker._buffer == [] and core_ch.empty()
        # Backlog clears -> intake resumes and payloads flush again.
        maker.backlog_fn = lambda: False
        for _ in range(2):
            await tx_in.put(b"\x01" + bytes(40))
        payload = (await asyncio.wait_for(core_ch.get(), 1.0)).payload
        assert len(payload.transactions) >= 1

    run_async(body())


def test_others_payload_runs_synthetic_workload(run_async, base_port, caplog):
    """A foreign payload must trigger the OTHER synthetic verification
    batch (the fork's core.rs:211-224 workload) — its log line is the
    votes/sec metric source."""
    import logging

    async def body():
        n = 4
        cmt = mempool_committee(base_port, n)
        params = MempoolParameters(
            max_payload_size=64,
            min_block_delay=10,
            benchmark_mode=True,
            synthetic_pool_size=64,
        )
        for pk, sk in keys(n):
            Mempool.run(pk, cmt, params, Store(), SignatureService(sk), channel(), channel())
        await asyncio.sleep(0.1)
        _, w = await asyncio.open_connection("127.0.0.1", base_port + 0)
        for _ in range(5):
            w.write(frame(b"\x01" + bytes(60)))
        await w.drain()
        for _ in range(100):
            await asyncio.sleep(0.05)
            if any(
                "Verifying OTHER transaction batch" in r.message
                for r in caplog.records
            ):
                break
        else:
            raise AssertionError("OTHER synthetic batch never ran")
        assert any(
            "Verifying OWN transaction batch" in r.message
            for r in caplog.records
        )

    with caplog.at_level(logging.INFO, logger="hotstuff.mempool"):
        run_async(body())


def test_oversized_payload_request_clamped(run_async, base_port):
    """A Byzantine PayloadRequest naming more digests than the configured
    cap is served only up to the cap (prefix) — the replies ride the
    urgent egress lane, so unbounded requests would be a
    priority-amplified reflector. An honest requester with a large block
    still makes progress (prefix served, retry fetches the rest)."""

    async def body():
        from hotstuff_tpu.mempool.core import PAYLOAD_PREFIX, Core
        from hotstuff_tpu.mempool.messages import (
            PayloadRequest,
            encode_mempool_message,
            decode_mempool_message,
        )
        from hotstuff_tpu.utils.serde import Writer

        n = 4
        cmt = mempool_committee(base_port, n)
        params = MempoolParameters(
            max_payload_size=64, min_block_delay=10, max_request_digests=2
        )
        (pk0, sk0), (pk1, sk1) = keys(n)[:2]
        store = Store()
        network_tx = channel()
        core = Core(
            pk0, cmt, params, store, None, None, channel(), channel(), network_tx
        )

        from hotstuff_tpu.crypto import Signature

        # Store three real payloads so serving is observable.
        payloads = [
            Payload((bytes([i]) * 8,), pk1, Signature.new(Digest.of(b"x"), sk1))
            for i in range(3)
        ]
        for p in payloads:
            w = Writer()
            p.encode(w)
            await store.write(PAYLOAD_PREFIX + p.digest().data, w.bytes())

        req = decode_mempool_message(
            encode_mempool_message(
                PayloadRequest(tuple(p.digest() for p in payloads), pk1)
            )
        )
        await core._handle_request(req)
        # Only the 2-digest prefix was served; the clamp was counted.
        assert core._requests_clamped == 1
        served = []
        while not network_tx.empty():
            served.append(network_tx.get_nowait())
        assert len(served) == 2, f"expected clamped prefix, got {len(served)}"
        assert all(m.urgent for m in served)

        # An at-cap request is NOT clamped (boundary: '>' not '>=').
        req_ok = PayloadRequest(tuple(p.digest() for p in payloads[:2]), pk1)
        await core._handle_request(req_ok)
        assert core._requests_clamped == 1
        count = 0
        while not network_tx.empty():
            network_tx.get_nowait()
            count += 1
        assert count == 2

    run_async(body())


# -- a payload is accepted once ------------------------------------------------


class _HeldService:
    """The node's verification service with every payload check counted and
    held until `gate` is set (so a first copy stays in acceptance while a
    second arrives); `answers` are verdicts handed out instead of the real
    ones, first calls first. Workload batches pass straight through."""

    def __init__(self, answers=()):
        from hotstuff_tpu.crypto.batch_service import BatchVerificationService

        self.service = BatchVerificationService()
        self.gate = asyncio.Event()
        self.checked = []
        self.answers = list(answers)

    async def verify(self, msg, key, sig, **kw):
        self.checked.append((msg, sig.data))
        await self.gate.wait()
        if self.answers:
            return self.answers.pop(0)
        return await self.service.verify(msg, key, sig, **kw)

    async def verify_group(self, *args, **kw):
        return await self.service.verify_group(*args, **kw)


class _CountingStore(Store):
    def __init__(self):
        super().__init__()
        self.writes = []

    async def write(self, key, value):
        self.writes.append(key)
        await super().write(key, value)


def _counts():
    from hotstuff_tpu.utils import metrics

    return (
        metrics.counter("mempool.payloads_duplicate").value,
        metrics.counter("mempool.payloads_other").value,
        metrics.histogram("mempool.verify_batch_size", metrics.SIZE_BUCKETS).count,
    )


def _workload_core(store, service):
    from hotstuff_tpu.mempool.core import Core

    params = MempoolParameters(benchmark_mode=True, synthetic_pool_size=16)
    return Core(
        keys()[0][0], mempool_committee(0), params, store, None, None,
        channel(), channel(), channel(), verification_service=service,
    )


def _copy(payload):
    """The same bytes as another object: what a PayloadRequest's reply is."""
    return decode_mempool_message(encode_mempool_message(payload))


def _since(before):
    return tuple(now - then for now, then in zip(_counts(), before))


def test_a_copy_that_arrives_while_the_first_is_in_acceptance_is_not_checked_again(
    run_async,
):
    """The gossiped copy's check is in flight when the reply to the
    synchronizer's PayloadRequest brings the same bytes: one check, one store
    write, one OTHER batch, and the reply counted as a duplicate."""

    async def body():
        author_pk, author_sk = keys()[1]
        gossip = Payload.new_from_key([b"\x01" + bytes(40)], author_pk, author_sk)
        store, service = _CountingStore(), _HeldService()
        core = _workload_core(store, service)
        before = _counts()
        await core._handle_others_payload(gossip)
        await core._handle_others_payload(_copy(gossip))
        await asyncio.sleep(0.01)  # the first copy's check is waiting
        assert len(service.checked) == 1
        service.gate.set()
        await core.drain_verifications()
        key = b"payload:" + gossip.digest().data
        assert store.writes.count(key) == 1
        # duplicates +1, accepted +1, workload batches +1
        assert _since(before) == (1, 1, 1)
        assert list(core.payloads.queue) == [gossip.digest()]
        assert not core._accepting

    run_async(body())


def test_a_copy_of_a_stored_payload_is_skipped(run_async):
    async def body():
        author_pk, author_sk = keys()[1]
        payload = Payload.new_from_key([b"\x01" + bytes(40)], author_pk, author_sk)
        store, service = _CountingStore(), _HeldService()
        service.gate.set()
        core = _workload_core(store, service)
        before = _counts()
        await core._handle_others_payload(payload)
        await core.drain_verifications()
        assert _since(before) == (0, 1, 1)
        await core._handle_others_payload(_copy(payload))
        await core.drain_verifications()
        assert len(service.checked) == 1
        assert store.writes.count(b"payload:" + payload.digest().data) == 1
        assert _since(before) == (1, 1, 1)

    run_async(body())


@pytest.mark.parametrize("forged_first", [False, True], ids=["valid_first", "forged_first"])
def test_a_copy_with_other_signature_bytes_is_checked_on_its_own(run_async, forged_first):
    """The same digest signed by the wrong key, while the valid copy is in
    acceptance (or ahead of it): each is verified, the forged one rejected,
    the valid one accepted and charged once; neither shadows the other."""

    async def body():
        author_pk, author_sk = keys()[1]
        _, wrong_sk = keys()[2]
        txs = [b"\x01" + bytes(40)]
        valid = Payload.new_from_key(txs, author_pk, author_sk)
        forged = Payload.new_from_key(txs, author_pk, wrong_sk)
        assert forged.digest() == valid.digest() and forged.signature != valid.signature
        store, service = _CountingStore(), _HeldService()
        core = _workload_core(store, service)
        before = _counts()
        for p in (forged, valid) if forged_first else (valid, forged):
            await core._handle_others_payload(p)
        await asyncio.sleep(0.01)
        assert sorted(sig for _m, sig in service.checked) == sorted(
            [valid.signature.data, forged.signature.data]
        )
        service.gate.set()
        await core.drain_verifications()
        assert _since(before) == (0, 1, 1)
        raw = await store.read(b"payload:" + valid.digest().data)
        w = Writer()
        valid.encode(w)
        assert raw == w.bytes()
        assert list(core.payloads.queue) == [valid.digest()]
        assert not core._accepting

    run_async(body())


def test_after_a_failed_acceptance_a_later_copy_is_checked_again(run_async):
    async def body():
        author_pk, author_sk = keys()[1]
        payload = Payload.new_from_key([b"\x01" + bytes(40)], author_pk, author_sk)
        store, service = _CountingStore(), _HeldService(answers=[False])
        service.gate.set()
        core = _workload_core(store, service)
        before = _counts()
        await core._handle_others_payload(payload)
        await core.drain_verifications()
        key = b"payload:" + payload.digest().data
        assert await store.read(key) is None and not core._accepting
        assert _since(before) == (0, 0, 0)
        await core._handle_others_payload(_copy(payload))
        await core.drain_verifications()
        assert len(service.checked) == 2
        assert store.writes.count(key) == 1
        assert _since(before) == (0, 1, 1)

    run_async(body())


def test_a_waiting_block_wakes_once_on_the_first_copys_store(run_async, base_port):
    """A block waits for a payload whose gossiped copy is in acceptance; the
    reply to the synchronizer's request brings a second copy. The block goes
    back to consensus once, when the first copy is stored."""

    async def body():
        n = 4
        pk, sk = keys()[0]
        store, consensus_channel, cm = _CountingStore(), channel(), channel()
        core = Mempool.run(
            pk, mempool_committee(base_port, n), MempoolParameters(), store,
            SignatureService(sk), cm, consensus_channel,
        )
        service = _HeldService()
        core.verification_service = service
        await asyncio.sleep(0.05)
        author_pk, author_sk = keys()[1]
        payload = Payload.new_from_key([b"\x01" + bytes(40)], author_pk, author_sk)
        block = chain(1, committee(base_port + 2 * n))[0]
        object.__setattr__(block, "payload", (payload.digest(),))
        fut = asyncio.get_running_loop().create_future()
        await cm.put(MempoolVerify(block, fut))
        assert await asyncio.wait_for(fut, 5) == PayloadStatus.WAIT

        before = _counts()
        await core._handle_others_payload(payload)
        await core._handle_others_payload(_copy(payload))
        await asyncio.sleep(0.05)
        assert consensus_channel.empty()
        service.gate.set()
        lb = await asyncio.wait_for(consensus_channel.get(), 5)
        assert lb.block == block
        await core.drain_verifications()
        await asyncio.sleep(0.1)
        assert consensus_channel.empty()
        assert len(service.checked) == 1
        assert store.writes.count(b"payload:" + payload.digest().data) == 1
        assert _since(before)[:2] == (1, 1)

    run_async(body())
