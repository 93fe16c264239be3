"""TpuBackend dispatch + sharded mesh verification on the virtual 8-device
CPU mesh (conftest.py). Mirrors the reference's batch-verification tests
(crypto/src/tests/crypto_tests.rs:73-114) through the CryptoBackend seam."""

import os
import random

import numpy as np
import pytest

pytest.importorskip("cryptography")

from hotstuff_tpu.crypto import (
    Digest,
    Signature,
    generate_keypair,
)
from hotstuff_tpu.crypto.backend import CpuBackend, get_backend, make_backend, set_backend


@pytest.fixture
def keys():
    rng = random.Random(0)
    return [generate_keypair(rng) for _ in range(4)]


@pytest.fixture
def tpu_backend():
    backend = make_backend("tpu", crossover=1)  # force everything to jax
    prev = set_backend(backend)
    yield backend
    set_backend(prev)


class TestTpuBackend:
    def test_verify_batch_valid(self, keys, tpu_backend):
        digest = Digest.of(b"batch")
        votes = [(pk, Signature.new(digest, sk)) for pk, sk in keys]
        assert Signature.verify_batch(digest, votes)
        assert tpu_backend.stats["tpu_sigs"] == 4

    def test_verify_batch_rejects_wrong_digest(self, keys, tpu_backend):
        digest = Digest.of(b"batch")
        votes = [(pk, Signature.new(digest, sk)) for pk, sk in keys]
        assert not Signature.verify_batch(Digest.of(b"other"), votes)

    def test_verify_batch_alt_distinct_messages(self, keys, tpu_backend):
        msgs = [bytes([i]) * 32 for i in range(4)]
        pairs = [
            (pk, Signature.new(Digest(m), sk)) for m, (pk, sk) in zip(msgs, keys)
        ]
        assert Signature.verify_batch_alt(msgs, pairs)
        # one bad signature fails the whole batch (dalek semantics)...
        bad = pairs[:2] + [(pairs[2][0], pairs[3][1])] + pairs[3:]
        assert not Signature.verify_batch_alt(msgs, bad)
        # ...but the mask pinpoints it (stronger than the reference)
        mask = tpu_backend.verify_batch_mask(
            msgs, [p for p, _ in bad], [s for _, s in bad]
        )
        assert mask == [True, True, False, True]

    def test_cpu_fallback_below_crossover(self, keys):
        backend = make_backend("tpu", crossover=100)
        digest = Digest.of(b"small")
        votes = [(pk, Signature.new(digest, sk)) for pk, sk in keys]
        assert backend.verify_batch(
            [digest.data] * 4, [pk for pk, _ in votes], [s for _, s in votes]
        )
        assert backend.stats["cpu_sigs"] == 4 and backend.stats["tpu_sigs"] == 0

    def test_agrees_with_cpu_backend(self, keys, tpu_backend):
        rng = random.Random(3)
        msgs, pks, sigs = [], [], []
        for i in range(8):
            pk, sk = keys[i % 4]
            m = rng.randbytes(32)
            msgs.append(m)
            pks.append(pk)
            sigs.append(Signature.new(Digest(m), sk))
        sigs[5] = sigs[2]  # corrupt
        cpu = CpuBackend().verify_batch_mask(msgs, pks, sigs)
        tpu = tpu_backend.verify_batch_mask(msgs, pks, sigs)
        assert cpu == tpu


class TestShardedVerifier:
    def test_sharded_matches_single(self):
        import jax

        from hotstuff_tpu.parallel import ShardedEd25519Verifier, default_mesh

        assert len(jax.devices()) == 8, "conftest must provide 8 CPU devices"
        from __graft_entry__ import _signed_batch

        msgs, pks, sigs = _signed_batch(16)
        sigs[3] = bytes(64)
        v = ShardedEd25519Verifier(mesh=default_mesh(8))
        mask = v.verify_batch_mask(msgs, pks, sigs)
        want = [True] * 16
        want[3] = False
        assert mask.tolist() == want

    def test_sharded_multi_chunk_pipeline(self):
        """Oversize batches split at `chunk` and ride the threaded upload
        pipeline with sharded device_put per chunk."""
        from hotstuff_tpu.parallel import ShardedEd25519Verifier, default_mesh

        from __graft_entry__ import _signed_batch

        msgs, pks, sigs = _signed_batch(24, seed=6)
        sigs[13] = bytes(64)
        v = ShardedEd25519Verifier(
            mesh=default_mesh(4), min_bucket=128, max_bucket=4096
        )
        v.chunk = 8  # force 3 pipelined chunks
        mask = v.verify_batch_mask(msgs, pks, sigs)
        want = [True] * 24
        want[13] = False
        assert mask.tolist() == want


class TestColumnarBatch:
    """A columnar batch (three uint8 column views of one (n, 128) array,
    crypto/backend.py) through `TpuBackend.verify_batch_mask` itself."""

    @staticmethod
    def _rows(keys, n=12, seed=9):
        """n wire rows msg | pk | sig with lanes 3 (corrupted signature),
        5 (s + L: the same scalar, not canonical) and 8 (wrong key) invalid."""
        from hotstuff_tpu.ops.ed25519 import L_ORDER

        rng = random.Random(seed)
        rows = []
        for i in range(n):
            pk, sk = keys[i % 4]
            m = rng.randbytes(32)
            sig = Signature.new(Digest(m), sk).data
            if i == 3:
                sig = sig[:40] + bytes([sig[40] ^ 4]) + sig[41:]
            if i == 5:
                s = int.from_bytes(sig[32:], "little") + L_ORDER
                sig = sig[:32] + s.to_bytes(32, "little")
            if i == 8:
                pk = keys[(i + 1) % 4][0]
            rows.append(m + pk.data + sig)
        want = [i not in (3, 5, 8) for i in range(n)]
        return np.frombuffer(b"".join(rows), np.uint8).reshape(n, 128), want

    def test_columns_equal_lists(self, keys, tpu_backend):
        from hotstuff_tpu.crypto.backend import columns_to_lists, row_columns

        rows, want = self._rows(keys)
        cols = row_columns(rows)
        as_lists = tpu_backend.verify_batch_mask(*columns_to_lists(*cols))
        before = dict(tpu_backend.report())
        mask = tpu_backend.verify_batch_mask(*cols)
        assert isinstance(mask, np.ndarray) and mask.dtype == bool
        assert mask.tolist() == as_lists == want
        assert CpuBackend().verify_batch_mask(*columns_to_lists(*cols)) == want
        # counted like any other batch: the benchmark reads these
        after = tpu_backend.report()
        assert after["tpu_sigs"] - before["tpu_sigs"] == 12
        assert after["tpu_batches"] - before["tpu_batches"] == 1
        assert after["dispatched"]["w4p128dh"] - before["dispatched"]["w4p128dh"] == 1

    def test_columns_below_the_crossover_verify_on_the_host(self, keys):
        from hotstuff_tpu.crypto.backend import row_columns

        backend = make_backend("tpu", crossover=100)
        rows, want = self._rows(keys)
        assert backend.verify_batch_mask(*row_columns(rows)) == want
        assert backend.stats["cpu_sigs"] == 12 and backend.stats["tpu_sigs"] == 0

    def test_service_calls_verify_batch_mask_itself(self, keys, monkeypatch, run_async):
        """The benchmark's control (`chipbench/faulty.py --fault skip_half`)
        patches `TpuBackend.verify_batch_mask`: a columnar bucket that took
        another way to the device would make the control read correct."""
        from chipbench import faulty
        from hotstuff_tpu.crypto.batch_service import BatchVerificationService
        from hotstuff_tpu.crypto.tpu_backend import TpuBackend

        # monkeypatch restores the honest method afterwards
        monkeypatch.setattr(TpuBackend, "verify_batch_mask", TpuBackend.verify_batch_mask)
        faulty.break_sidecar("skip_half")
        rows, want = self._rows(keys)

        async def body():
            svc = BatchVerificationService(
                make_backend("tpu", crossover=1)
            )
            mask = await svc.verify_rows(rows)
            assert mask.tolist() == [ok or i % 2 == 1 for i, ok in enumerate(want)]
            assert mask.tolist() != want
            # the cache believes what the backend said: every lane but 8
            assert len(svc.dedup) == 11

        run_async(body())


class TestGraftEntry:
    def test_dryrun_multichip(self):
        from __graft_entry__ import dryrun_multichip

        dryrun_multichip(8)


class TestWarmup:
    def test_warmup_compiles_every_bucket(self, keys):
        # Tiny buckets keep the test fast: one device-hash compile at
        # width 128 (the shape is already cached by earlier tests). The
        # host-hash twin is NOT warmed: nothing falls back to it.
        backend = make_backend(
            "tpu", crossover=1, min_bucket=128, max_bucket=128
        )
        secs = backend.warmup()
        assert secs > 0
        assert dict(backend._verifier.dispatched) == {"w4p128dh": 1}
        # Warmed backend still verifies correctly end to end.
        pk, sk = keys[0]
        d = Digest.of(b"warm")
        sig = Signature.new(d, sk)
        assert backend.verify_batch_mask([d.data] * 4, [pk] * 4, [sig] * 4) == [
            True
        ] * 4


class TestNamesItsDevice:
    def test_raises_on_a_cpu_nobody_asked_for(self, monkeypatch):
        """No chip and no JAX_PLATFORMS=cpu: TpuBackend refuses to exist —
        the sidecar and `node run --crypto tpu` die at boot instead of
        serving from the CPU under the name "tpu"."""
        from hotstuff_tpu import ops

        monkeypatch.setattr(ops, "cpu_requested", lambda: False)
        with pytest.raises(RuntimeError, match="found no TPU"):
            make_backend("tpu")

    def test_report_names_platform_kernels_and_routing(self, keys):
        backend = make_backend(
            "tpu", crossover=2, min_bucket=128, max_bucket=128
        )
        assert (backend.platform, backend.device_count) == ("cpu", 8)
        pk, sk = keys[0]
        d = Digest.of(b"report")
        sig = Signature.new(d, sk)
        backend.verify_batch_mask([d.data] * 3, [pk] * 3, [sig] * 3)
        backend.verify_batch_mask([d.data], [pk], [sig])  # sub-crossover
        rep = backend.report()
        assert rep["platform"] == "cpu" and rep["device_kind"] == "cpu"
        assert rep["kernels"] == {"generic": "w4p128dh", "committee": "w4c96dh"}
        assert rep["dispatched"] == {"w4p128dh": 1}
        assert (rep["tpu_sigs"], rep["cpu_sigs"]) == (3, 1)


class TestCompileCachePlacement:
    def test_placed_by_the_environment_or_fixed_in_the_checkout(
        self, monkeypatch
    ):
        """JAX_COMPILATION_CACHE_DIR set: JAX reads it itself and no code
        sets a directory. Unset: the one fixed directory in the checkout."""
        import jax

        from hotstuff_tpu import ops

        updates = []
        monkeypatch.setattr(
            jax.config, "update", lambda k, v: updates.append((k, v))
        )
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/placed")
        assert ops.enable_persistent_cache() == "/somewhere/placed"
        assert "jax_compilation_cache_dir" not in dict(updates)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        updates.clear()
        assert ops.enable_persistent_cache() == ops.CACHE_DIR
        assert dict(updates)["jax_compilation_cache_dir"] == ops.CACHE_DIR
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert ops.CACHE_DIR == os.path.join(repo, ".jax_cache")
