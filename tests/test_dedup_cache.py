"""Verified-signature dedup cache (crypto/batch_service.VerifiedSigCache).

The aggregator verifies each vote on arrival; the QC assembled from those
votes re-verifies the SAME (digest, pk, sig) triples 1-2 more times over
their lifetime. The dedup cache short-circuits those repeats before the
backend dispatch. Unit tests here are dependency-free (stub backend, raw
key bytes — no `cryptography` wheel needed); the consensus-round e2e
assertion lives in test_dedup_consensus.py.
"""

import asyncio

import pytest

from hotstuff_tpu.crypto.backend import CryptoBackend
from hotstuff_tpu.crypto.batch_service import (
    BatchVerificationService,
    VerifiedSigCache,
)
from hotstuff_tpu.crypto.primitives import PublicKey, Signature
from hotstuff_tpu.utils import metrics


def _triple(i: int):
    return (
        bytes([i]) * 32,
        PublicKey(bytes([i]) * 32),
        Signature(bytes([i]) * 64),
    )


class _CountingBackend(CryptoBackend):
    """All-true backend counting every signature it is asked to verify."""

    name = "counting"

    def __init__(self, committee_routing: bool = False):
        self.verified = 0
        self.calls: list[int] = []
        self.committee_tags: list[bool] = []
        if committee_routing:
            self.supports_committee_routing = True

    def verify_batch_mask(self, messages, keys, signatures, committee=False):
        self.verified += len(messages)
        self.calls.append(len(messages))
        self.committee_tags.append(committee)
        return [True] * len(messages)


class TestVerifiedSigCache:
    def test_hit_requires_exact_triple(self):
        cache = VerifiedSigCache(8)
        m, pk, sig = _triple(1)
        cache.add(m, pk, sig)
        assert cache.hit(m, pk, sig)
        # a forged signature over the same digest can never alias the entry
        assert not cache.hit(m, pk, Signature(bytes(64)))
        assert not cache.hit(bytes(32), pk, sig)

    def test_lru_eviction_bounds_memory(self):
        ev0 = metrics.counter("verifier.dedup_evictions").value
        cache = VerifiedSigCache(4)
        for i in range(10):
            cache.add(*_triple(i))
            assert len(cache) <= 4
        assert metrics.counter("verifier.dedup_evictions").value == ev0 + 6
        # oldest evicted, newest retained
        assert not cache.hit(*_triple(0))
        assert cache.hit(*_triple(9))

    def test_recency_refresh_on_hit(self):
        cache = VerifiedSigCache(2)
        cache.add(*_triple(1))
        cache.add(*_triple(2))
        assert cache.hit(*_triple(1))  # refresh 1 -> 2 becomes LRU
        cache.add(*_triple(3))  # evicts 2
        assert cache.hit(*_triple(1))
        assert not cache.hit(*_triple(2))

    def test_rejects_zero_size(self):
        with pytest.raises(ValueError):
            VerifiedSigCache(0)


class TestServiceDedup:
    def test_repeat_verification_skips_backend(self, run_async):
        async def body():
            backend = _CountingBackend()
            svc = BatchVerificationService(backend)
            m, pk, sig = _triple(1)
            assert await svc.verify(m, pk, sig)
            assert backend.verified == 1
            # the same triple again: cache hit, no second backend call
            assert await svc.verify(m, pk, sig)
            assert backend.verified == 1
            assert svc.stats["verified"] == 2

        run_async(body())

    def test_seed_verified_short_circuits_first_check(self, run_async):
        async def body():
            backend = _CountingBackend()
            svc = BatchVerificationService(backend)
            m, pk, sig = _triple(2)
            svc.seed_verified(m, pk, sig)  # the aggregator's seam
            assert await svc.verify(m, pk, sig)
            assert backend.verified == 0, "seeded triple must not dispatch"

        run_async(body())

    def test_dedup_disabled_dispatches_every_time(self, run_async):
        async def body():
            backend = _CountingBackend()
            svc = BatchVerificationService(
                backend, dedup_cache_size=0
            )
            assert svc.dedup is None
            m, pk, sig = _triple(3)
            assert await svc.verify(m, pk, sig)
            assert await svc.verify(m, pk, sig)
            assert backend.verified == 2

        run_async(body())

    def test_mixed_group_only_misses_dispatch(self, run_async):
        async def body():
            backend = _CountingBackend()
            svc = BatchVerificationService(backend)
            triples = [_triple(i) for i in range(4)]
            for m, pk, sig in triples[:2]:
                svc.seed_verified(m, pk, sig)
            mask = await svc.verify_group(
                [m for m, _, _ in triples],
                [(pk, sig) for _, pk, sig in triples],
            )
            assert mask == [True] * 4
            assert backend.verified == 2, "only the 2 cache misses dispatch"

        run_async(body())

    def test_dedup_opt_out_group_always_dispatches(self, run_async):
        """Synthetic benchmark groups (dedup=False) must pay full backend
        verification on every repeat — the cache must neither serve nor
        learn their triples."""

        async def body():
            backend = _CountingBackend()
            svc = BatchVerificationService(backend)
            m, pk, sig = _triple(9)
            for _ in range(2):
                mask = await svc.verify_group(
                    [m], [(pk, sig)], dedup=False
                )
                assert mask == [True]
            assert backend.verified == 2
            # and the opted-out triple was never inserted
            assert not svc.dedup.hit(m, pk, sig)

        run_async(body())

    def test_committee_tag_reaches_backend(self, run_async):
        async def body():
            backend = _CountingBackend(committee_routing=True)
            svc = BatchVerificationService(backend)
            m, pk, sig = _triple(5)
            await svc.verify(m, pk, sig, committee=True)
            m2, pk2, sig2 = _triple(6)
            await svc.verify(m2, pk2, sig2, committee=False)
            assert backend.committee_tags == [True, False]

        run_async(body())

    def test_untagged_backend_never_gets_kwarg(self, run_async):
        """A backend without supports_committee_routing (CpuBackend,
        RemoteBackend) must be called with the plain 3-arg signature."""

        class StrictBackend(CryptoBackend):
            name = "strict"
            verified = 0

            def verify_batch_mask(self, messages, keys, signatures):
                StrictBackend.verified += len(messages)
                return [True] * len(messages)

        async def body():
            svc = BatchVerificationService(StrictBackend())
            m, pk, sig = _triple(7)
            assert await svc.verify(m, pk, sig, committee=True)
            assert StrictBackend.verified == 1

        run_async(body())


# -- columnar groups (verify_rows) beside list groups --------------------------


def _row(i: int) -> bytes:
    m, pk, sig = _triple(i)
    return m + pk.data + sig.data


def _rows(ids):
    import numpy as np

    return np.frombuffer(b"".join(_row(i) for i in ids), np.uint8).reshape(-1, 128)


class _OddIdsBackend(CryptoBackend):
    """Verifies a `_triple(i)` iff i is odd; takes lists only, so the
    service has to take a columnar bucket apart for it."""

    name = "odd"

    def __init__(self):
        self.calls: list[list[int]] = []

    def verify_batch_mask(self, messages, keys, signatures):
        assert all(type(m) is bytes for m in messages)
        assert all(type(k) is PublicKey for k in keys)
        assert all(type(s) is Signature for s in signatures)
        ids = [m[0] for m in messages]
        assert [k.data[0] for k in keys] == ids
        assert [s.data[0] for s in signatures] == ids
        self.calls.append(ids)
        return [i % 2 == 1 for i in ids]


_DEDUP_COUNTERS = ("hits", "misses", "inserts", "evictions")


def _dedup_counters():
    return [metrics.counter(f"verifier.dedup_{n}").value for n in _DEDUP_COUNTERS]


# buckets of one sequence: duplicates inside a bucket, repeats across
# buckets, failures (even ids), more distinct successes than the cache holds
_SEQUENCE = ([1, 2, 3, 3, 5], [3, 1, 7, 2, 9, 11], [13, 15, 1, 17], [5, 3, 19, 4, 4])


class TestColumnarGroups:
    @pytest.mark.parametrize("columnar", [False, True], ids=["lists", "rows"])
    def test_same_cache_trajectory_either_path(self, columnar, run_async):
        """Masks, backend calls, LRU order, evictions and the four counters
        of the columnar path are the list path's on the same sequence; a
        failure is never cached."""

        async def drive(as_rows):
            backend = _OddIdsBackend()
            svc = BatchVerificationService(
                backend, dedup_cache_size=4
            )
            before = _dedup_counters()
            masks = []
            for ids in _SEQUENCE:
                if as_rows:
                    mask = await svc.verify_rows(_rows(ids))
                    assert mask.dtype == bool
                    masks.append(mask.tolist())
                else:
                    t = [_triple(i) for i in ids]
                    masks.append(await svc.verify_group(
                        [m for m, _, _ in t], [(pk, s) for _, pk, s in t]
                    ))
            moved = [b - a for a, b in zip(before, _dedup_counters())]
            return masks, backend.calls, list(svc.dedup._entries), moved

        masks, calls, order, moved = run_async(drive(columnar))
        assert masks == [[i % 2 == 1 for i in ids] for ids in _SEQUENCE]
        # by hand, cache of 4: bucket 1 misses all five (the duplicate 3
        # twice), inserts 1 3 5; bucket 2 hits 3 1, inserts 7 (full), 9 and
        # 11 (evicting 5, 3); bucket 3 hits 1, inserts 13 15 17 (evicting 7
        # 9 11); bucket 4 misses all five, inserts 5 3 19 (evicting 1 13 15)
        assert calls == [[1, 2, 3, 3, 5], [7, 2, 9, 11], [13, 15, 17], [5, 3, 19, 4, 4]]
        assert order == [_row(i) for i in (17, 5, 3, 19)]
        assert dict(zip(_DEDUP_COUNTERS, moved)) == {
            "hits": 3, "misses": 17, "inserts": 12, "evictions": 8,
        }

    def test_triple_cached_by_one_path_hits_by_the_other(self, run_async):
        async def body():
            backend = _OddIdsBackend()
            svc = BatchVerificationService(backend)
            # list path and the aggregator's seam in, rows out
            m, pk, sig = _triple(21)
            assert await svc.verify(m, pk, sig)
            svc.seed_verified(*_triple(23))
            assert (await svc.verify_rows(_rows([21, 23, 25]))).tolist() == [True] * 3
            assert backend.calls == [[21], [25]]
            # rows in, list path out; the forged sibling of a cached triple
            # (same message and key, another signature) is a miss
            assert await svc.verify(*_triple(25))
            assert backend.calls == [[21], [25]]
            assert svc.dedup.holds(*_triple(25))
            assert not svc.dedup.hit(m, pk, Signature(bytes(64)))

        run_async(body())

    def test_mixed_bucket_takes_the_list_path(self, run_async):
        """A columnar and a list group coalesced into one bucket: one
        backend call, each group its own slice in its own form, a
        dedup-opted-out list group neither served nor learned."""

        async def body():
            backend = _OddIdsBackend()
            svc = BatchVerificationService(backend)
            svc.seed_verified(*_triple(31))
            t = [_triple(i) for i in (31, 32, 33)]
            got = await asyncio.gather(
                svc.verify_rows(_rows([31, 33, 34, 35])),
                svc.verify_group(
                    [m for m, _, _ in t], [(pk, s) for _, pk, s in t], dedup=False
                ),
            )
            assert got[0].tolist() == [True, True, False, True]
            assert got[1] == [True, False, True]
            assert backend.calls == [[33, 34, 35, 31, 32, 33]]
            assert svc.stats["flushes"] == 1
            assert svc.dedup.holds(*_triple(35))
            assert not svc.dedup.holds(*_triple(34))

        run_async(body())

    def test_columnar_bucket_coalesces_into_one_array(self, run_async):
        """Two columnar groups in one bucket reach a backend that takes
        columns as three column arrays of one flattened batch."""
        import numpy as np

        seen = []

        class ColumnBackend(CryptoBackend):
            name = "columns"
            accepts_columns = True

            def verify_batch_mask(self, messages, keys, signatures):
                seen.append((messages, keys, signatures))
                return messages[:, 0] % 2 == 1

        async def body():
            svc = BatchVerificationService(ColumnBackend())
            svc.seed_verified(*_triple(43))
            got = await asyncio.gather(
                svc.verify_rows(_rows([41, 42])), svc.verify_rows(_rows([43, 44, 45]))
            )
            assert [g.tolist() for g in got] == [[True, False], [True, False, True]]
            ((m, k, s),) = seen
            assert (m.shape, k.shape, s.shape) == ((4, 32), (4, 32), (4, 64))
            assert all(a.dtype == np.uint8 for a in (m, k, s))
            assert m[:, 0].tolist() == k[:, 0].tolist() == s[:, 0].tolist() == [41, 42, 44, 45]

        run_async(body())

    def test_forged_audit_reads_the_new_key(self):
        """chaos `forged_triples_cached` asks `holds`: membership without
        touching recency or the counters."""
        cache = VerifiedSigCache(2)
        cache.add(*_triple(1))
        cache.add(*_triple(2))
        before = _dedup_counters()
        assert cache.holds(*_triple(1)) and not cache.holds(*_triple(3))
        assert _dedup_counters() == before
        cache.add(*_triple(3))  # 1 was not refreshed: it goes
        assert not cache.holds(*_triple(1)) and cache.holds(*_triple(2))
