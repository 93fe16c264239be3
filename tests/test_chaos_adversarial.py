"""Adversarial regression for PR 2's verified-signature dedup cache on the
committee-resident TPU verify path.

A forged-signature vote burst routed through the REAL service + backend
stack (BatchVerificationService -> TpuBackend committee kernel) must:
  * produce `verifier.committee_*` rejections (the committee kernel's
    rejection lanes fire),
  * yield zero false accepts in an assembled QC, and
  * leave ZERO `VerifiedSigCache` entries for the rejected triples — a
    replayed forged burst pays full verification again (dedup misses),
    never a cache hit.

Dependency-free: committee keys/signatures come from the pure-python
RFC 8032 signer (tests/common.py -> hotstuff_tpu/crypto/pysigner.py).
Kernel shapes (w4, bucket 128) match tests/test_committee_verify.py and
tests/test_mesh_committee.py, so the persistent XLA cache is shared.
"""

import pytest

from hotstuff_tpu.consensus.config import Committee
from hotstuff_tpu.consensus.messages import QC, _vote_digest
from hotstuff_tpu.crypto.backend import make_backend
from hotstuff_tpu.crypto.batch_service import BatchVerificationService
from hotstuff_tpu.crypto.primitives import Digest, PublicKey, Signature
from hotstuff_tpu.utils import metrics
from tests.common import rfc8032_keypair, rfc8032_sign

pytestmark = pytest.mark.chaos

_M_CBATCHES = metrics.counter("verifier.committee_batches")
_M_CREJECTED = metrics.counter("verifier.committee_rejected_sigs")
_M_DEDUP_HITS = metrics.counter("verifier.dedup_hits")
_M_DEDUP_MISSES = metrics.counter("verifier.dedup_misses")


@pytest.fixture(scope="module")
def committee_keys():
    return [rfc8032_keypair(bytes([i + 31]) * 32) for i in range(4)]


@pytest.fixture(scope="module")
def backend(committee_keys):
    # crossover=1 keeps every batch on the device path (the CPU fallback
    # needs the OpenSSL wheel this host may lack); bucket 128 matches the
    # kernel shapes the committee-verify tests already compiled.
    b = make_backend("tpu", crossover=1, committee_crossover=1, max_bucket=128)
    assert b.register_committee(
        [PublicKey(pk) for pk, _ in committee_keys]
    ) == len(committee_keys)
    return b


def _vote_burst(committee_keys, rng_seed: int = 99):
    """(msgs, pairs, want): 2 valid votes + forged-signature votes claiming
    every authority, all over the same block digest/round."""
    import random

    rng = random.Random(rng_seed)
    block_digest = Digest(bytes(31) + b"\x07")
    round_ = 5
    digest = _vote_digest(block_digest, round_)
    msgs, pairs, want = [], [], []
    for pk, seed in committee_keys[:2]:  # honest votes
        msgs.append(digest.data)
        pairs.append(
            (PublicKey(pk), Signature(rfc8032_sign((pk, seed), digest.data)))
        )
        want.append(True)
    for pk, _ in committee_keys:  # forged burst: garbage signatures
        msgs.append(digest.data)
        pairs.append((PublicKey(pk), Signature(rng.randbytes(64))))
        want.append(False)
    return block_digest, round_, msgs, pairs, want


def test_forged_burst_rejected_on_committee_path_and_never_cached(
    run_async, backend, committee_keys
):
    async def body():
        service = BatchVerificationService(backend=backend)
        block_digest, round_, msgs, pairs, want = _vote_burst(committee_keys)

        b0, r0 = _M_CBATCHES.value, _M_CREJECTED.value
        mask = await service.verify_group(msgs, pairs, committee=True)
        assert mask == want
        assert _M_CBATCHES.value > b0, "burst did not ride the committee kernel"
        assert _M_CREJECTED.value >= r0 + 4, "committee rejections missing"

        # Dedup cache: valid triples cached, every forged triple absent.
        cache = service.dedup
        for (m, (pk, sig)), ok in zip(zip(msgs, pairs), want):
            cached = cache.holds(m, pk, sig)
            assert cached == ok, (
                f"forged triple cached={cached} ok={ok} — rejected triples "
                "must never enter the VerifiedSigCache"
            )

        # Replay the forged burst: zero cache hits for forged lanes (the
        # two valid votes may hit), and the mask is unchanged.
        h0, m0 = _M_DEDUP_HITS.value, _M_DEDUP_MISSES.value
        mask2 = await service.verify_group(msgs, pairs, committee=True)
        assert mask2 == want
        assert _M_DEDUP_HITS.value - h0 == 2  # only the valid votes
        assert _M_DEDUP_MISSES.value - m0 == 4  # every forged lane re-misses

        # Zero false accepts in an assembled QC: only accepted votes make
        # a valid QC; a QC smuggling one forged vote must fail.
        cmt = Committee.new(
            [
                (PublicKey(pk), 1, ("127.0.0.1", 18_000 + i))
                for i, (pk, _) in enumerate(committee_keys)
            ]
        )
        honest = [
            (pk, sig)
            for (pk, sig), ok in zip(pairs, want)
            if ok
        ]
        # a third valid vote for quorum (2f+1 = 3 of 4)
        pk3, seed3 = committee_keys[2]
        digest = _vote_digest(block_digest, round_)
        honest.append(
            (PublicKey(pk3), Signature(rfc8032_sign((pk3, seed3), digest.data)))
        )
        good_qc = QC(block_digest, round_, tuple(honest))
        await good_qc.verify_async(cmt, service)  # must not raise

        forged_pair = pairs[2 + 3]  # a forged lane by the 4th authority
        bad_qc = QC(block_digest, round_, tuple(honest[:2]) + (forged_pair,))
        from hotstuff_tpu.consensus.errors import InvalidSignatureError

        with pytest.raises(InvalidSignatureError):
            await bad_qc.verify_async(cmt, service)

    run_async(body(), timeout=300)
