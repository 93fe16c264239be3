"""Consensus core unit tests, mirroring consensus/src/tests/core_tests.rs:
drive a real Core by channel injection and assert on emitted NetMessages
(decoded) and recipients. No TCP involved: the network tx queue is held by
the test."""

import asyncio

import pytest

from hotstuff_tpu.consensus import Block, Committee, Parameters, Vote
from hotstuff_tpu.consensus.core import Core
from hotstuff_tpu.consensus.leader import LeaderElector
from hotstuff_tpu.consensus.mempool_driver import MempoolDriver
from hotstuff_tpu.consensus.messages import (
    Timeout,
    decode_consensus_message,
)
from hotstuff_tpu.consensus.synchronizer import Synchronizer
from hotstuff_tpu.crypto import SignatureService
from hotstuff_tpu.store import Store
from hotstuff_tpu.utils.actors import channel, spawn
# Whole-module OpenSSL dependency (tests/common.py is importable
# without the wheel; the skip now lives with the modules that need it).
pytest.importorskip("cryptography")

from tests.common import MockMempool, chain, committee, keys, qc_for


def make_core(name_index: int, cmt: Committee, timeout_ms: int = 2_000):
    """Build a Core whose channels are all held by the test."""
    pk, sk = keys()[name_index]
    store = Store()
    sig_service = SignatureService(sk)
    mock = MockMempool()
    mock.start()
    core_channel = channel()
    network_tx = channel()
    commit_channel = channel()
    params = Parameters(timeout_delay=timeout_ms)
    sync = Synchronizer(pk, cmt, store, network_tx, core_channel, params.sync_retry_delay)
    core = Core(
        pk,
        cmt,
        params,
        sig_service,
        store,
        LeaderElector(cmt),
        MempoolDriver(mock.channel),
        sync,
        core_channel,
        network_tx,
        commit_channel,
    )
    return core, core_channel, network_tx, commit_channel


def test_handle_proposal_emits_vote_to_next_leader(run_async, base_port):
    async def body():
        cmt = committee(base_port)
        elector = LeaderElector(cmt)
        b1 = chain(1, cmt)[0]
        # Pick a node that is neither the round-1 proposer nor the round-2
        # leader, so the vote goes out on the network.
        next_leader = elector.get_leader(2)
        idx = next(
            i
            for i, (pk, _) in enumerate(keys())
            if pk not in (b1.author, next_leader)
        )
        core, core_channel, network_tx, _ = make_core(idx, cmt)
        spawn(core.run())
        await core_channel.put(b1)
        msg = await asyncio.wait_for(network_tx.get(), 10)
        vote = decode_consensus_message(msg.data)
        assert isinstance(vote, Vote)
        assert vote.hash == b1.digest() and vote.round == 1
        assert msg.addresses == [cmt.address(next_leader)]

    run_async(body())


def test_generate_proposal_on_qc(run_async, base_port):
    async def body():
        cmt = committee(base_port)
        elector = LeaderElector(cmt)
        b1 = chain(1, cmt)[0]
        # The round-2 leader aggregates votes for b1 into a QC and proposes.
        leader2 = elector.get_leader(2)
        idx = next(i for i, (pk, _) in enumerate(keys()) if pk == leader2)
        core, core_channel, network_tx, _ = make_core(idx, cmt)
        spawn(core.run())
        for pk, sk in keys():
            await core_channel.put(Vote.new_from_key(b1.digest(), 1, pk, sk))
        while True:
            msg = await asyncio.wait_for(network_tx.get(), 10)
            out = decode_consensus_message(msg.data)
            if isinstance(out, Block):
                break
        assert out.round == 2
        assert out.qc.hash == b1.digest()
        assert out.author == leader2
        out.qc.verify(cmt)

    run_async(body())


@pytest.mark.parametrize("k", [1, 3])
def test_one_block_a_round_whatever_moves_the_leader(run_async, base_port, k):
    """Every node that assembles a round's TC broadcasts it, so the next
    leader meets that round's QC or TC again and again after the first moved
    it on. It proposes once: the block the QC made, and for each of the k
    TCs that followed, nothing but `consensus.proposals_suppressed`."""
    from itertools import combinations

    from hotstuff_tpu.consensus.messages import TC
    from hotstuff_tpu.utils import metrics

    suppressed = metrics.counter("consensus.proposals_suppressed")

    async def body():
        cmt = committee(base_port)
        elector = LeaderElector(cmt)
        b1, b2 = chain(2, cmt)
        idx = next(i for i, (pk, _) in enumerate(keys()) if pk == elector.get_leader(3))
        core, core_channel, network_tx, _ = make_core(idx, cmt, timeout_ms=60_000)
        spawn(core.run())
        before = suppressed.value
        for pk, sk in keys()[:3]:  # a quorum of votes on b2: the round-2 QC
            await core_channel.put(Vote.new_from_key(b2.digest(), 2, pk, sk))
        for signers in list(combinations(keys(), 3))[:k]:  # and k round-2 TCs
            timeouts = [Timeout.new_from_key(qc_for(b1), 2, pk, sk) for pk, sk in signers]
            await core_channel.put(
                TC(2, tuple((t.author, t.signature, t.high_qc.round) for t in timeouts))
            )
        for _ in range(200):
            if suppressed.value - before >= k:
                break
            await asyncio.sleep(0.05)
        await asyncio.sleep(0.2)
        blocks = []
        while not network_tx.empty():
            out = decode_consensus_message(network_tx.get_nowait().data)
            if isinstance(out, Block):
                blocks.append(out)
        assert [(b.round, b.qc.hash) for b in blocks] == [(3, b2.digest())]
        assert suppressed.value - before == k
        assert core.round == 3

    run_async(body())


def test_commit_on_two_chain(run_async, base_port):
    async def body():
        cmt = committee(base_port)
        b1, b2, b3 = chain(3, cmt)
        # Feed the chain in order to a non-leader node: processing b3 gives
        # ancestors (b1, b2) in consecutive rounds -> b1 commits.
        idx = next(
            i for i, (pk, _) in enumerate(keys()) if pk not in (b3.author,)
        )
        core, core_channel, _, commit_channel = make_core(idx, cmt)
        spawn(core.run())
        for b in (b1, b2, b3):
            await core_channel.put(b)
        committed = await asyncio.wait_for(commit_channel.get(), 10)
        assert committed == b1

    run_async(body())


def test_local_timeout_broadcasts_timeout(run_async, base_port):
    async def body():
        cmt = committee(base_port)
        core, _, network_tx, _ = make_core(2, cmt, timeout_ms=200)
        spawn(core.run())
        msg = await asyncio.wait_for(network_tx.get(), 10)
        out = decode_consensus_message(msg.data)
        assert isinstance(out, Timeout)
        assert out.round == 1
        assert set(msg.addresses) == set(
            cmt.broadcast_addresses(keys()[2][0])
        )

    run_async(body())


def test_proposal_from_wrong_leader_ignored(run_async, base_port):
    async def body():
        cmt = committee(base_port)
        b1 = chain(1, cmt)[0]
        wrong_author_pk, wrong_author_sk = next(
            (pk, sk) for pk, sk in keys() if pk != b1.author
        )
        bad = Block.new_from_key(
            b1.qc, None, wrong_author_pk, 1, list(b1.payload), wrong_author_sk
        )
        idx = next(
            i
            for i, (pk, _) in enumerate(keys())
            if pk not in (wrong_author_pk, LeaderElector(cmt).get_leader(2))
        )
        core, core_channel, network_tx, _ = make_core(idx, cmt)
        spawn(core.run())
        await core_channel.put(bad)
        await core_channel.put(b1)  # the real proposal still gets a vote
        msg = await asyncio.wait_for(network_tx.get(), 10)
        vote = decode_consensus_message(msg.data)
        assert isinstance(vote, Vote) and vote.hash == b1.digest()

    run_async(body())


def test_no_double_vote_same_round(run_async, base_port):
    async def body():
        cmt = committee(base_port)
        b1 = chain(1, cmt)[0]
        elector = LeaderElector(cmt)
        idx = next(
            i
            for i, (pk, _) in enumerate(keys())
            if pk not in (b1.author, elector.get_leader(2))
        )
        core, core_channel, network_tx, _ = make_core(idx, cmt)
        spawn(core.run())
        await core_channel.put(b1)
        msg = await asyncio.wait_for(network_tx.get(), 10)
        assert isinstance(decode_consensus_message(msg.data), Vote)
        # Replay the same proposal: safety rule 1 forbids a second vote.
        await core_channel.put(b1)
        with pytest.raises(asyncio.TimeoutError):
            await asyncio.wait_for(network_tx.get(), 0.5)

    run_async(body())


def test_equivocating_leader_gets_one_vote(run_async, base_port):
    """Byzantine leader sends TWO different valid blocks for the same round:
    a correct replica votes for the first and withholds a vote for the
    second (safety rule: last_voted_round strictly increases —
    consensus/src/core.rs:106-123)."""
    from hotstuff_tpu.crypto import Digest
    from hotstuff_tpu.consensus.messages import QC
    from tests.common import _secret_of

    async def body():
        cmt = committee(base_port)
        elector = LeaderElector(cmt)
        leader = elector.get_leader(1)
        b1 = Block.new_from_key(
            QC.genesis(), None, leader, 1, [Digest.of(b"tx-a")], _secret_of(leader)
        )
        b1_equiv = Block.new_from_key(
            QC.genesis(), None, leader, 1, [Digest.of(b"tx-b")], _secret_of(leader)
        )
        assert b1.digest() != b1_equiv.digest()
        next_leader = elector.get_leader(2)
        idx = next(
            i
            for i, (pk, _) in enumerate(keys())
            if pk not in (leader, next_leader)
        )
        core, core_channel, network_tx, _ = make_core(idx, cmt)
        spawn(core.run())
        await core_channel.put(b1)
        msg = await asyncio.wait_for(network_tx.get(), 10)
        vote = decode_consensus_message(msg.data)
        assert isinstance(vote, Vote) and vote.hash == b1.digest()
        # the equivocated block must produce NO second vote
        await core_channel.put(b1_equiv)
        with pytest.raises(asyncio.TimeoutError):
            while True:
                msg = await asyncio.wait_for(network_tx.get(), 1.0)
                extra = decode_consensus_message(msg.data)
                assert not (
                    isinstance(extra, Vote) and extra.round == 1
                ), "replica voted twice in round 1 (equivocation!)"

    run_async(body())


def test_respammed_proposal_does_not_suppress_timeout(run_async, base_port):
    """Byzantine leader re-sends its round-1 proposal repeatedly: the
    replica must still fire its round-1 Timeout (pacemaker re-arms only on
    round ADVANCE, consensus/src/core.rs:267-268 — a per-block reset would
    let the leader suppress this replica's timeout forever)."""
    async def body():
        cmt = committee(base_port)
        elector = LeaderElector(cmt)
        b1 = chain(1, cmt)[0]
        next_leader = elector.get_leader(2)
        idx = next(
            i
            for i, (pk, _) in enumerate(keys())
            if pk not in (b1.author, next_leader)
        )
        core, core_channel, network_tx, _ = make_core(idx, cmt, timeout_ms=1_000)
        spawn(core.run())
        # Spam the same valid proposal more often than the timeout period,
        # CONTINUOUSLY until the timeout is observed: with a per-block timer
        # reset (the guarded regression) the pacemaker would never fire
        # while spam is active, so the assertion below would fail.
        stop_spam = asyncio.Event()

        async def spam():
            while not stop_spam.is_set():
                await core_channel.put(b1)
                await asyncio.sleep(0.05)

        spawn(spam())
        saw_timeout = False
        deadline = asyncio.get_running_loop().time() + 6.0
        try:
            while asyncio.get_running_loop().time() < deadline and not saw_timeout:
                msg = await asyncio.wait_for(network_tx.get(), 6.0)
                decoded = decode_consensus_message(msg.data)
                if isinstance(decoded, Timeout) and decoded.round == 1:
                    saw_timeout = True
        finally:
            stop_spam.set()
        assert saw_timeout, "replica's round-1 timeout was suppressed by spam"

    run_async(body())


def test_sync_request_flood_does_not_suppress_timeout(run_async, base_port):
    """Byzantine liveness (ADVICE r3): a peer continuously spraying cheap
    valid messages must not starve the pacemaker — the expired timer is
    served within the selector's starvation bound and the Timeout still
    broadcasts."""

    from hotstuff_tpu.consensus.messages import SyncRequest
    from hotstuff_tpu.crypto import Digest

    async def body():
        cmt = committee(base_port)
        core, core_channel, network_tx, _ = make_core(2, cmt, timeout_ms=150)
        spawn(core.run())

        requester = keys()[1][0]

        async def flood():
            # keep the message branch continuously ready
            while True:
                await core_channel.put(
                    SyncRequest(Digest.of(b"missing"), requester)
                )
                await asyncio.sleep(0)

        task = spawn(flood())
        try:
            # The flooded requests are dropped silently (unknown digest),
            # so the ONLY message that can appear is the Timeout itself.
            try:
                msg = await asyncio.wait_for(network_tx.get(), 8.0)
            except asyncio.TimeoutError:
                raise AssertionError(
                    "pacemaker starved by SyncRequest flood"
                ) from None
            out = decode_consensus_message(msg.data)
            assert isinstance(out, Timeout) and out.round == 1
        finally:
            task.cancel()

    run_async(body())


def test_pacemaker_backoff_grows_caps_and_resets(run_async, base_port):
    """Consecutive local timeouts back the pacemaker delay off exponentially
    (capped); a QC that advances the round restores the base delay. Backoff
    is liveness-only: it never changes WHAT is sent, only when the next
    timeout fires."""

    async def body():
        cmt = committee(base_port)
        core, _core_channel, network_tx, _ = make_core(0, cmt, timeout_ms=100)
        core.parameters.timeout_backoff = 2.0
        core.parameters.max_timeout_delay = 500
        from hotstuff_tpu.utils.actors import Timer

        core.timer = Timer(core.parameters.timeout_delay)
        assert core.timer.delay_ms == 100

        # Growth starts at the THIRD consecutive timeout: a single crashed
        # leader stalls two rounds per rotation, which must not be taxed.
        await core._local_timeout_round()
        assert core.timer.delay_ms == 100
        await core._local_timeout_round()
        assert core.timer.delay_ms == 100
        await core._local_timeout_round()
        assert core.timer.delay_ms == 200
        await core._local_timeout_round()
        assert core.timer.delay_ms == 400
        await core._local_timeout_round()
        assert core.timer.delay_ms == 500  # capped
        await core._local_timeout_round()
        assert core.timer.delay_ms == 500

        # Each timeout still broadcast a Timeout message (6 total).
        for _ in range(6):
            msg = await asyncio.wait_for(network_tx.get(), 5)
            assert isinstance(decode_consensus_message(msg.data), Timeout)

        # A QC advancing the round restores the base delay...
        qc = qc_for(chain(1, cmt)[0])
        await core._process_qc(qc)
        assert core.timer.delay_ms == 100
        assert core._consecutive_timeouts == 0

        # ...but a STALE QC after new timeouts must not.
        for _ in range(3):
            await core._local_timeout_round()
        assert core.timer.delay_ms == 200
        await core._process_qc(qc)  # qc.round < core.round now
        assert core.timer.delay_ms == 200

    run_async(body())


def test_pacemaker_backoff_disabled_matches_reference(run_async, base_port):
    """timeout_backoff=1.0 keeps the fixed-delay reference behavior."""

    async def body():
        cmt = committee(base_port)
        core, _cc, network_tx, _ = make_core(0, cmt, timeout_ms=100)
        core.parameters.timeout_backoff = 1.0
        from hotstuff_tpu.utils.actors import Timer

        core.timer = Timer(core.parameters.timeout_delay)
        for _ in range(3):
            await core._local_timeout_round()
        assert core.timer.delay_ms == 100

    run_async(body())
