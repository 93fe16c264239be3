"""Synchronizer tests, mirroring consensus/src/tests/synchronizer_tests.rs:
the suspend/resume contract -- a missing parent triggers a SyncRequest
broadcast and returns None; storing the parent later triggers the LoopBack."""

import asyncio

from hotstuff_tpu.consensus.messages import (
    LoopBack,
    SyncRequest,
    decode_consensus_message,
    encode_stored_block,
)
from hotstuff_tpu.consensus.synchronizer import Synchronizer
from hotstuff_tpu.store import Store
from hotstuff_tpu.utils.actors import channel
import pytest

# Whole-module OpenSSL dependency (tests/common.py is importable
# without the wheel; the skip now lives with the modules that need it).
pytest.importorskip("cryptography")

from tests.common import chain, committee, keys


def test_get_existing_parent(run_async, base_port):
    async def body():
        cmt = committee(base_port)
        b1, b2 = chain(2, cmt)
        store = Store()
        await store.write(b1.digest().data, encode_stored_block(b1))
        sync = Synchronizer(keys()[0][0], cmt, store, channel(), channel(), 10_000)
        parent = await sync.get_parent_block(b2)
        assert parent == b1
        # genesis parent resolves without the store
        g = await sync.get_parent_block(b1)
        assert g is not None and g.is_genesis()

    run_async(body())


def test_missing_parent_requests_then_loops_back(run_async, base_port):
    async def body():
        cmt = committee(base_port)
        b1, b2 = chain(2, cmt)
        store = Store()
        network_tx = channel()
        core_channel = channel()
        me = keys()[0][0]
        sync = Synchronizer(me, cmt, store, network_tx, core_channel, 10_000)

        assert await sync.get_parent_block(b2) is None
        msg = await asyncio.wait_for(network_tx.get(), 5)
        req = decode_consensus_message(msg.data)
        assert isinstance(req, SyncRequest)
        assert req.digest == b1.digest() and req.requester == me
        # Attempt 0 goes to ONE peer, a pure function of (digest, own key):
        # always broadcasting made every missing digest n-1 frames per
        # retry tick across the committee (Synchronizer._peers).
        everyone = set(cmt.broadcast_addresses(me))
        assert list(msg.addresses) == sync._peers(b1.digest(), 0)
        assert len(msg.addresses) == 1 and set(msg.addresses) <= everyone

        # A retry past sync_retry_delay escalates the same request to all.
        await sync._retry_pass(asyncio.get_running_loop().time() + 10.001)
        again = await asyncio.wait_for(network_tx.get(), 5)
        assert decode_consensus_message(again.data) == req
        assert set(again.addresses) == everyone

        # The parent arrives (e.g. via a peer's re-send) -> LoopBack fires.
        await store.write(b1.digest().data, encode_stored_block(b1))
        lb = await asyncio.wait_for(core_channel.get(), 5)
        assert isinstance(lb, LoopBack) and lb.block == b2

    run_async(body())
