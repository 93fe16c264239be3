"""What becomes of a payload digest once a block holds it: pending while the
block may still commit, committed once, and back at the front of the queue
when a commit leaves its block out (mempool/payload_queue.py), directly and
through the mempool core's consensus channel."""

import asyncio

import pytest

from hotstuff_tpu.crypto import Digest
from hotstuff_tpu.mempool.errors import QueueFullError
from hotstuff_tpu.mempool.payload_queue import PayloadQueue


def _digests(n: int, tag: str = "p") -> list[Digest]:
    return [Digest.of(f"{tag}-{i}".encode()) for i in range(n)]


def test_an_orphans_digests_go_back_to_the_front():
    q = PayloadQueue(capacity=100)
    d = _digests(6)
    for x in d:
        q.insert(x)
    # round 5 takes two digests; round 6 (which will commit) two more
    orphan = q.take(2, 5)
    kept = q.take(2, 6)
    assert orphan == d[:2] and kept == d[2:4]
    assert list(q.queue) == d[4:]
    # a commit at round 7 of blocks holding `kept`, round 5 left out
    assert q.note_commit(7, kept) == orphan
    assert list(q.queue) == orphan + d[4:]
    assert not q.pending


def test_a_committed_digest_never_returns():
    q = PayloadQueue(capacity=100)
    d = _digests(3)
    for x in d:
        q.insert(x)
    q.note_block(4, d)
    assert not q.queue and set(q.pending) == set(d)
    q.note_commit(4, d)
    # late verification of the same payload, or content made again
    for x in d:
        q.insert(x)
    assert not q.queue and not q.pending
    # a block processed at or below the committed round settles as committed
    late = _digests(2, "late")
    q.note_block(3, late)
    assert q.note_commit(9, []) == [] and not q.queue
    for x in late:
        q.insert(x)
    assert not q.queue


def test_a_block_above_the_commit_stays_pending():
    q = PayloadQueue(capacity=100)
    d = _digests(4)
    for x in d:
        q.insert(x)
    q.note_block(8, d[:2])
    q.note_block(10, d[2:])
    assert q.note_commit(9, []) == d[:2]
    assert set(q.pending) == set(d[2:])
    # a digest held by two blocks returns only when the higher one is settled
    q.take(2, 12)
    q.note_block(11, d[:2])
    assert q.note_commit(11, []) == d[2:]
    assert q.note_commit(12, []) == d[:2]


def test_an_orphans_digest_this_node_never_held_is_dropped():
    """A block's digests leave the queue when it is verified, before its
    payloads are fetched. A digest whose payload never came (made up, or its
    maker crashed before gossiping it) must not come back at the front of
    the queue for every honest leader to propose and time out on."""
    q = PayloadQueue(capacity=100)
    known, late, unknown = _digests(3)
    q.insert(known)
    q.note_block(5, [known, late, unknown])
    q.insert(late)  # its payload arrives while the block waits
    assert q.note_commit(6, []) == [known, late]
    assert list(q.queue) == [known, late] and not q.pending
    # dropped, not committed: should the payload arrive after all, it is queued
    q.insert(unknown)
    assert list(q.queue) == [known, late, unknown]


def test_a_returning_digest_is_never_refused_for_capacity():
    q = PayloadQueue(capacity=2)
    a, b, c = _digests(3)
    q.insert(a)
    q.insert(b)
    with pytest.raises(QueueFullError):
        q.insert(c)
    taken = q.take(2, 3)
    q.insert(c)
    q.insert(Digest.of(b"d"))
    assert q.note_commit(4, []) == taken
    assert len(q) == 4 and list(q.queue)[:2] == taken


def test_requeued_and_proposed_again_it_commits_once(run_async):
    """Through the mempool core: Get for a round, Cleanup when the blocks are
    processed, Commit when the chain commits past the orphan; the orphan's
    digest comes back first, is proposed again, and commits once."""
    from hotstuff_tpu.consensus import Block, QC
    from hotstuff_tpu.consensus.mempool_driver import (
        MempoolCleanup,
        MempoolCommit,
        MempoolGet,
    )
    from hotstuff_tpu.crypto import PublicKey, Signature
    from hotstuff_tpu.mempool import MempoolParameters
    from hotstuff_tpu.mempool.core import Core
    from hotstuff_tpu.store import Store
    from hotstuff_tpu.utils import metrics
    from hotstuff_tpu.utils.actors import channel, spawn

    requeued = metrics.counter("mempool.orphans_requeued")
    author = PublicKey(bytes(32))

    def block(round_, payload):
        return Block(QC.genesis(), None, author, round_, tuple(payload), Signature(bytes(64)))

    async def body():
        consensus = channel()
        core = Core(author, None, MempoolParameters(), Store(), None, None,
                    channel(), consensus, channel())
        core.synchronizer = type("Sync", (), {"cleanup": lambda self, r: None})()
        spawn(core.run())
        d = _digests(3)
        for x in d:
            core._queue_insert(x)

        async def get(round_):
            fut = asyncio.get_running_loop().create_future()
            await consensus.put(MempoolGet(32, fut, round_))  # one digest a block
            return await asyncio.wait_for(fut, 5)

        async def settle(*msgs):
            for m in msgs:
                await consensus.put(m)
            while not consensus.empty():
                await asyncio.sleep(0.01)
            await asyncio.sleep(0.01)

        before = requeued.value
        genesis = Block.genesis()
        b1 = block(1, await get(1))  # committed below
        b2 = block(2, await get(2))  # its QC is never formed: an orphan
        b4 = block(4, await get(4))  # extends b1, TC round 3
        assert (b1.payload, b2.payload, b4.payload) == ((d[0],), (d[1],), (d[2],))
        await settle(MempoolCleanup(genesis, genesis, b1),
                     MempoolCleanup(genesis, b1, b2),
                     MempoolCleanup(genesis, b1, b4))
        assert not core.payloads.queue
        # b5 on b4, b6 on b5: b4 commits (with b1); b2 is left out
        await settle(MempoolCommit(4, (d[2], d[0])))
        assert list(core.payloads.queue) == [d[1]] and requeued.value - before == 1
        b7 = block(7, await get(7))
        assert b7.payload == (d[1],)
        await settle(MempoolCleanup(b4, b4, b7), MempoolCommit(7, (d[1],)))
        assert requeued.value - before == 1
        # committed, all three: none returns, whatever arrives again
        for x in d:
            core._queue_insert(x)
        assert not core.payloads.queue and not core.payloads.pending

    run_async(body())


def test_a_verified_proposal_waiting_for_its_payloads_holds_its_digests(run_async):
    """A leader can assemble the next QC from others' votes while the block
    it extends still waits for its payloads: asked for a payload then, it
    must not propose that block's digests again (the same payload would
    commit in two blocks of one chain)."""
    from hotstuff_tpu.consensus import Block, QC
    from hotstuff_tpu.consensus.mempool_driver import MempoolGet, MempoolVerify, PayloadStatus
    from hotstuff_tpu.crypto import PublicKey, Signature
    from hotstuff_tpu.mempool import MempoolParameters
    from hotstuff_tpu.mempool.core import Core
    from hotstuff_tpu.store import Store
    from hotstuff_tpu.utils.actors import channel, spawn

    author = PublicKey(bytes(32))

    class Waiting:  # the payloads have not arrived
        async def verify_payload(self, block):
            return PayloadStatus.WAIT

    async def body():
        consensus = channel()
        core = Core(author, None, MempoolParameters(), Store(), None, Waiting(),
                    channel(), consensus, channel())
        spawn(core.run())
        d = _digests(3)
        for x in d:
            core._queue_insert(x)
        block = Block(QC.genesis(), None, author, 5, tuple(d[:2]), Signature(bytes(64)))
        fut = asyncio.get_running_loop().create_future()
        await consensus.put(MempoolVerify(block, fut))
        assert await asyncio.wait_for(fut, 5) == PayloadStatus.WAIT
        fut = asyncio.get_running_loop().create_future()
        await consensus.put(MempoolGet(1_000, fut, 6))
        assert await asyncio.wait_for(fut, 5) == [d[2]]
        # the block never commits: a commit past it puts its digests back
        assert core.payloads.note_commit(6, [d[2]]) == d[:2]

    run_async(body())


def test_a_commit_past_a_verified_block_drops_the_digests_never_held(run_async):
    """Through the mempool core: a verified proposal carries a digest whose
    payload this node never received; a commit past the block puts the
    digest this node queued back, and not the other."""
    from hotstuff_tpu.consensus import Block, QC
    from hotstuff_tpu.consensus.mempool_driver import (
        MempoolCommit,
        MempoolVerify,
        PayloadStatus,
    )
    from hotstuff_tpu.crypto import PublicKey, Signature
    from hotstuff_tpu.mempool import MempoolParameters
    from hotstuff_tpu.mempool.core import Core
    from hotstuff_tpu.store import Store
    from hotstuff_tpu.utils import metrics
    from hotstuff_tpu.utils.actors import channel, spawn

    requeued = metrics.counter("mempool.orphans_requeued")
    author = PublicKey(bytes(32))

    class Waiting:  # the made-up payload never arrives
        async def verify_payload(self, block):
            return PayloadStatus.WAIT

    async def body():
        consensus = channel()
        core = Core(author, None, MempoolParameters(), Store(), None, Waiting(),
                    channel(), consensus, channel())
        spawn(core.run())
        known, unknown = _digests(2)
        core._queue_insert(known)
        block = Block(QC.genesis(), None, author, 5, (known, unknown), Signature(bytes(64)))
        fut = asyncio.get_running_loop().create_future()
        await consensus.put(MempoolVerify(block, fut))
        assert await asyncio.wait_for(fut, 5) == PayloadStatus.WAIT
        before = requeued.value
        await consensus.put(MempoolCommit(7, ()))
        for _ in range(100):
            if core.payloads.queue:
                break
            await asyncio.sleep(0.01)
        assert list(core.payloads.queue) == [known] and not core.payloads.pending
        assert requeued.value - before == 1

    run_async(body())
