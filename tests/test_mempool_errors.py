"""Typed Byzantine-input rejection at the mempool ingress
(reference mempool/src/error.rs + mempool/src/core.rs:193-234): oversized,
unknown-author, and bad-signature payloads are rejected with the right
MempoolError — testable by assertion, not just a log line."""

import asyncio
import random

import pytest

from hotstuff_tpu.crypto import generate_keypair
from hotstuff_tpu.mempool import MempoolParameters, Payload
from hotstuff_tpu.mempool.core import Core
from hotstuff_tpu.mempool.errors import (
    MempoolError,
    PayloadTooBigError,
    QueueFullError,
    UnknownAuthorityError,
)
from hotstuff_tpu.store import Store
from hotstuff_tpu.utils.actors import channel
# Whole-module OpenSSL dependency (tests/common.py is importable
# without the wheel; the skip now lives with the modules that need it).
pytest.importorskip("cryptography")

from tests.common import keys
from tests.common_mempool import mempool_committee


def make_core(**params) -> Core:
    pk, _ = keys()[0]
    return Core(
        pk,
        mempool_committee(0),
        MempoolParameters(**params),
        Store(),
        payload_maker=None,
        synchronizer=None,
        core_channel=channel(),
        consensus_mempool_channel=channel(),
        network_tx=channel(),
    )


def test_unknown_authority_rejected(run_async):
    async def body():
        core = make_core()
        outsider_pk, outsider_sk = generate_keypair(random.Random(99))
        payload = Payload.new_from_key([b"\x01" + bytes(40)], outsider_pk, outsider_sk)
        with pytest.raises(UnknownAuthorityError):
            await core._handle_others_payload(payload)
        await core.drain_verifications()
        assert not core.payloads.queue

    run_async(body())


def test_oversized_payload_rejected(run_async):
    async def body():
        core = make_core(max_payload_size=32)
        author_pk, author_sk = keys()[1]
        payload = Payload.new_from_key([b"\x01" + bytes(60)], author_pk, author_sk)
        with pytest.raises(PayloadTooBigError):
            await core._handle_others_payload(payload)
        await core.drain_verifications()
        assert not core.payloads.queue

    run_async(body())


def test_bad_signature_rejected(run_async):
    async def body():
        core = make_core()
        author_pk, _ = keys()[1]
        _, wrong_sk = keys()[2]
        # signed by the WRONG secret key: structural checks pass, the
        # signature check (in the background verification task) must reject
        # and the payload must be neither stored nor queued.
        payload = Payload.new_from_key([b"\x01" + bytes(40)], author_pk, wrong_sk)
        await core._handle_others_payload(payload)
        await core.drain_verifications()
        assert not core.payloads.queue
        assert await core.store.read(b"payload:" + payload.digest().data) is None

    run_async(body())


def test_valid_payload_accepted(run_async):
    async def body():
        core = make_core()
        author_pk, author_sk = keys()[1]
        payload = Payload.new_from_key([b"\x01" + bytes(40)], author_pk, author_sk)
        await core._handle_others_payload(payload)
        await core.drain_verifications()
        assert payload.digest() in core.payloads.queue
        assert await core.store.read(b"payload:" + payload.digest().data) is not None

    run_async(body())


def test_queue_full_rejected(run_async):
    async def body():
        core = make_core(queue_capacity=1)
        author_pk, author_sk = keys()[1]
        p1 = Payload.new_from_key([b"\x01" + bytes(40)], author_pk, author_sk)
        p2 = Payload.new_from_key([b"\x02" + bytes(40)], author_pk, author_sk)
        await core._handle_others_payload(p1)
        await core.drain_verifications()
        assert len(core.payloads) == 1
        # second one: stored (it IS valid) but the queue insert must raise
        await core._handle_others_payload(p2)
        await core.drain_verifications()
        assert len(core.payloads) == 1

    run_async(body())


def test_error_types_are_mempool_errors():
    assert issubclass(UnknownAuthorityError, MempoolError)
    assert issubclass(PayloadTooBigError, MempoolError)
    assert issubclass(QueueFullError, MempoolError)
