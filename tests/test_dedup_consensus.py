"""E2e consensus rounds with the verified-signature dedup cache: the number
of backend-verified signatures must drop >= 2x versus the uncached path
while the commit decisions are unchanged (ISSUE 2 acceptance criterion).

Uses the one-fault pattern of tests/test_consensus_e2e.py: with the
round-3 leader dead, every live node sees the same (timeout, high_qc, TC)
signatures several times — its own Timeout verification, each peer's TC,
and the TC-justified block — which is exactly the repeat traffic the
dedup cache collapses (the aggregator seeds timeout/vote triples, so
assembled TCs/QCs re-verify zero signatures)."""

import asyncio

import pytest

pytest.importorskip("cryptography")

from hotstuff_tpu.consensus import Consensus, Parameters
from hotstuff_tpu.crypto import SignatureService
from hotstuff_tpu.crypto.backend import CpuBackend
from hotstuff_tpu.crypto.batch_service import BatchVerificationService
from hotstuff_tpu.store import Store
from hotstuff_tpu.utils.actors import channel
from tests.common import MockMempool, committee, keys


class _RecordingCpuBackend(CpuBackend):
    """CpuBackend keeping every (message, key, signature) it is handed, in
    order: one per node, as each node's service has its own cache."""

    def __init__(self):
        super().__init__()
        self.seen: list[tuple[bytes, bytes, bytes]] = []

    def verify_batch_mask(self, messages, keys_, signatures):
        self.seen += [
            (bytes(m), k.data, s.data)
            for m, k, s in zip(messages, keys_, signatures)
        ]
        return super().verify_batch_mask(messages, keys_, signatures)


def _run_faulty_round(run_async, base_port, dedup_cache_size):
    """Boot 3 of 4 nodes (the round-3 leader never does), await the first
    commit on every live node; returns (each node's backend-verified
    triples, first committed (round, digest))."""
    backends = [_RecordingCpuBackend() for _ in range(3)]

    async def body():
        cmt = committee(base_port)
        params = Parameters(timeout_delay=1_000)
        commit_channels = []
        for (pk, sk), backend in zip(keys()[:3], backends):
            store = Store()
            sig_service = SignatureService(sk)
            mock = MockMempool()
            mock.start()
            commit_channel = channel()
            commit_channels.append(commit_channel)
            service = BatchVerificationService(
                backend, dedup_cache_size=dedup_cache_size
            )
            Consensus.run(
                pk,
                cmt,
                params,
                store,
                sig_service,
                mock.channel,
                commit_channel,
                verification_service=service,
            )
        firsts = await asyncio.wait_for(
            asyncio.gather(*(c.get() for c in commit_channels)), 60
        )
        assert all(b == firsts[0] for b in firsts)
        return firsts[0]

    first = run_async(body(), timeout=90)
    return [b.seen for b in backends], (first.round, first.digest())


def test_dedup_halves_backend_verified_signatures(run_async, base_port):
    cached, cached_commit = _run_faulty_round(
        run_async, base_port, dedup_cache_size=65536
    )
    uncached, uncached_commit = _run_faulty_round(
        run_async, base_port + 20, dedup_cache_size=0
    )
    # identical commit output: the same first committed block on every live
    # node within each run, and the same block across runs
    assert cached_commit == uncached_commit
    # What the cache guarantees, counted from the runs (no factor is
    # promised: how often a signature recurs before the first commit is the
    # scenario's, about 2x with one silent leader). With it a node's
    # backend sees each distinct (message, key, signature) ONCE: the
    # aggregator seeds every vote and timeout signature it checked, so the
    # TC of each peer, the TC-justified block and the high_qc riding every
    # Timeout resolve from the cache. Without it every occurrence reaches
    # the backend, so the same triples come again.
    for seen in cached:
        assert seen and len(seen) == len(set(seen)), (
            f"{len(seen) - len(set(seen))} repeats reached the backend "
            "past the cache"
        )
    repeats = sum(len(seen) - len(set(seen)) for seen in uncached)
    assert repeats > 0, "the scenario repeats no signature: nothing to save"
    total = lambda runs: sum(len(seen) for seen in runs)
    assert total(cached) < total(uncached), (total(cached), total(uncached))
