"""Committee-resident verification on the device mesh (parallel/mesh.py).

PR 2 made committee keys device-resident on a single chip; this module
locks in the MULTI-CHIP inheritance: the shard_map-wrapped committee
kernels (replicated `CommitteeTable` operands, dp-sharded 96 B + 4 B-index
wire rows) must produce masks byte-identical to the single-chip committee
kernel AND the generic sharded kernel on valid, forged-R, forged-s,
wrong-message, wrong-index and non-canonical-s lanes; steady-state batches
must perform zero per-batch decompressions/table builds; and an epoch
re-registration must never swap the replicated tables under a pinned
in-flight snapshot.

Dependency-free on purpose: signatures come from the exact-integer
pure-python RFC 8032 signer shared via tests/common.py
(hotstuff_tpu/crypto/pysigner.py), so this file runs on hosts without
the `cryptography` wheel.
Runs on conftest.py's virtual 8-device CPU mesh using a 4-device sub-mesh
(the forced 4-device host-platform configuration of the acceptance check).
"""

import hashlib

import numpy as np
import pytest

from hotstuff_tpu.ops import ed25519 as ed
from hotstuff_tpu.parallel.mesh import ShardedEd25519Verifier, default_mesh
from hotstuff_tpu.utils import metrics
from tests.test_committee_verify import _vector_batch

NDEV = 4  # sub-mesh of conftest's virtual 8-device CPU platform

_M_DECOMP = metrics.counter("verifier.decompressions")
_M_BUILDS = metrics.counter("verifier.table_builds")
_M_CBATCHES = metrics.counter("verifier.committee_batches")
_M_PAD = metrics.counter("verifier.pad_lanes")


# --- dependency-free ed25519 signer (RFC 8032, exact host integers) --------
# Promoted to tests/common.py (canonical implementation:
# hotstuff_tpu/crypto/pysigner.py) so the chaos tests share it; a keypair
# here is (compressed public key bytes, seed).

from tests.common import rfc8032_keypair as _keypair, rfc8032_sign as _sign


@pytest.fixture(scope="module")
def committee():
    kps = [_keypair(bytes([i + 1]) * 32) for i in range(8)]
    return kps, [kp[0] for kp in kps]


@pytest.fixture(scope="module")
def digest_batch(committee):
    """32-byte-digest lanes (the protocol hot path -> device-hash kernel):
    8 valid votes + one of every rejection class the kernels distinguish.
    Returns (msgs, keys, claimed_idx, sigs, want)."""
    kps, pks = committee
    msgs, keys, idx, sigs = [], [], [], []
    for i in range(8):
        m = hashlib.sha512(bytes([i])).digest()[:32]
        msgs.append(m)
        keys.append(pks[i])
        idx.append(i)
        sigs.append(_sign(kps[i], m))
    want = [True] * 8
    # forged R (bit flip)
    msgs.append(msgs[0]); keys.append(keys[0]); idx.append(0)
    sigs.append(bytes([sigs[0][0] ^ 1]) + sigs[0][1:])
    # forged s (bit flip)
    msgs.append(msgs[1]); keys.append(keys[1]); idx.append(1)
    sigs.append(sigs[1][:33] + bytes([sigs[1][33] ^ 1]) + sigs[1][34:])
    # wrong message (another lane's digest)
    msgs.append(msgs[3]); keys.append(keys[2]); idx.append(2)
    sigs.append(sigs[2])
    # wrong INDEX: valid signature by key 3, claimed as validator 4 — the
    # committee kernel gathers validator 4's table (and key bytes for the
    # device hash), the generic path receives validator 4's key; both fail
    msgs.append(msgs[3]); keys.append(pks[4]); idx.append(4)
    sigs.append(sigs[3])
    # non-canonical s' = s + L: cofactored rules accept it, strict
    # verification must reject it on every path (host s < L check)
    s_int = int.from_bytes(sigs[5][32:], "little") + ed.L_ORDER
    msgs.append(msgs[5]); keys.append(keys[5]); idx.append(5)
    sigs.append(sigs[5][:32] + s_int.to_bytes(32, "little"))
    want += [False] * 5
    return msgs, keys, idx, sigs, want


@pytest.fixture(scope="module")
def sharded(committee):
    """4-device mesh verifier with the committee registered. max_bucket 512
    on purpose: with lane alignment 128 * 4 every batch in this module pads
    to ONE width, sharing a single compile per kernel variant."""
    _, pks = committee
    v = ShardedEd25519Verifier(
        mesh=default_mesh(NDEV), max_bucket=512, kernel="w4"
    )
    v.set_committee(pks)
    return v


@pytest.fixture(scope="module")
def single(committee):
    """Single-chip committee verifier over the SAME keys (width 128)."""
    _, pks = committee
    v = ed.Ed25519TpuVerifier(max_bucket=128, kernel="w4")
    v.set_committee(pks)
    return v


class TestShardedCommitteeKernel:
    def test_mesh_alignment(self, sharded):
        assert sharded.mesh_alignment == 128 * NDEV
        assert sharded.min_bucket == 512 and sharded.max_bucket == 512
        assert sharded.supports_committee

    def test_min_bucket_rounds_up_to_alignment(self):
        # an off-grid user min_bucket must round UP to lane*ndev, not leak
        # through and shard into ragged per-device lanes
        v = ShardedEd25519Verifier(
            mesh=default_mesh(NDEV), min_bucket=600, max_bucket=4096
        )
        assert v.min_bucket == 1024
        assert v.max_bucket % v.mesh_alignment == 0

    @pytest.mark.slow
    def test_masks_byte_identical_device_hash(
        self, committee, digest_batch, sharded, single
    ):
        """32-byte digests ride the device-hash committee kernel: the
        committee `keys_u8` gather feeds the on-device SHA-512. Sharded
        committee == single-chip committee == sharded generic == expected.

        Marked slow (~3 min on a 1-core CPU host): the on-device-SHA-512
        kernel variants are the most expensive compiles in the suite, and
        the host-hash mesh mask test plus the single-chip committee mask
        tests keep the byte-identical cross-checks in tier-1."""
        msgs, keys, idx, sigs, want = digest_batch
        s_committee = sharded.verify_batch_mask_committee(msgs, idx, sigs)
        assert s_committee.tolist() == want
        c_single = single.verify_batch_mask_committee(msgs, idx, sigs)
        assert c_single.dtype == s_committee.dtype
        assert c_single.tolist() == s_committee.tolist()
        s_generic = sharded.verify_batch_mask(msgs, keys, sigs)
        assert s_generic.tolist() == s_committee.tolist()

    def test_masks_byte_identical_rfc8032_host_hash(self, sharded, single):
        """RFC 8032 vectors (+ forged and non-canonical-s lanes) have
        non-32-byte messages, exercising the HOST-hash committee wire
        format (rows 64-95 carry h) over the mesh."""
        msgs, pks, sigs = _vector_batch()
        t = sharded.set_committee(sorted(set(pks)))
        idx = [t.index[k] for k in pks]
        got = sharded.verify_batch_mask_committee(msgs, idx, sigs)
        assert got.tolist() == [True] * 4 + [False] * 4
        ts = single.set_committee(sorted(set(pks)))
        sidx = [ts.index[k] for k in pks]
        assert got.tolist() == single.verify_batch_mask_committee(
            msgs, sidx, sigs
        ).tolist()

    @pytest.mark.parametrize(
        "device_hash", [False, True], ids=["host_hash", "device_hash"]
    )
    def test_two_chunks_ragged_tail_on_the_mesh(
        self, committee, digest_batch, sharded, device_hash
    ):
        """The mesh verifier overrides placement and the program table and
        nothing of dispatch: the base class's one chunk loop splits a
        committee batch at `chunk` (one full mesh bucket of 512 lanes and
        a ragged one of 40), puts each chunk's index vector and wire rows
        sharded, and the replicated tables of one snapshot ride ahead of
        them. A forged lane in each chunk; the mask is the expected one."""
        n = 552
        if device_hash:
            _, pks = committee
            msgs, _, idx, sigs, _ = digest_batch
            pool = list(zip(msgs[:8], idx[:8], sigs[:8]))
            table = sharded.set_committee(pks)
        else:
            vmsgs, vpks, vsigs = _vector_batch()
            table = sharded.set_committee(sorted(set(vpks)))
            pool = [
                (m, table.index[k], s)
                for m, k, s in zip(vmsgs[:4], vpks[:4], vsigs[:4])
            ]
        k = len(pool)
        msgs = [pool[i % k][0] for i in range(n)]
        idx = [pool[i % k][1] for i in range(n)]
        sigs = [pool[i % k][2] for i in range(n)]
        for lane in (7, 530):  # chunk 0, chunk 1
            sigs[lane] = bytes([sigs[lane][0] ^ 1]) + sigs[lane][1:]
        assert sharded.chunk == 512
        name = sharded.program_name(True, device_hash)
        c0, p0 = sharded.dispatched[name], _M_PAD.value
        got = sharded.verify_batch_mask_committee(msgs, idx, sigs, table=table)
        assert got.tolist() == [lane not in (7, 530) for lane in range(n)]
        assert sharded.dispatched[name] == c0 + 2
        assert _M_PAD.value == p0 + 2 * 512 - n

    def test_zero_decompressions_in_steady_state(
        self, committee, digest_batch, sharded
    ):
        """Acceptance: committee batches on the mesh gather replicated
        tables — zero per-batch decompressions/table builds, with
        committee_batches advancing."""
        _, pks = committee
        msgs, _, idx, sigs, want = digest_batch
        sharded.set_committee(pks)  # restore after the vector-batch test
        sharded.verify_batch_mask_committee(msgs, idx, sigs)  # warm
        d0, b0, c0 = _M_DECOMP.value, _M_BUILDS.value, _M_CBATCHES.value
        for _ in range(3):
            got = sharded.verify_batch_mask_committee(msgs, idx, sigs)
        assert got.tolist() == want
        assert _M_DECOMP.value == d0, "sharded committee path decompressed"
        assert _M_BUILDS.value == b0, "sharded committee path built tables"
        assert _M_CBATCHES.value == c0 + 3

    def test_pad_lanes_counter(self, committee, digest_batch, sharded):
        """A sub-alignment batch pads up to the full lane*ndev bucket; the
        waste is visible in verifier.pad_lanes (the signal behind the
        mesh-aware committee_crossover)."""
        _, pks = committee
        msgs, _, idx, sigs, _ = digest_batch
        sharded.set_committee(pks)
        p0 = _M_PAD.value
        sharded.verify_batch_mask_committee(msgs, idx, sigs)
        assert _M_PAD.value == p0 + (512 - len(msgs))

    def test_reregistration_never_swaps_pinned_snapshot(
        self, committee, digest_batch, sharded
    ):
        """The reconfig-safety contract on the mesh: indices resolved
        against a pinned table snapshot stay valid through dispatch even
        when a re-registration installs new replicated tables mid-flight
        (here: between resolution and dispatch, the worst-case
        interleaving a concurrent epoch change can produce)."""
        _, pks = committee
        msgs, _, idx, sigs, want = digest_batch
        t1 = sharded.set_committee(pks)
        # epoch reconfiguration: REVERSED key order permutes every index
        t2 = sharded.set_committee(list(reversed(pks)))
        assert t2 is not t1 and sharded.committee is t2
        # in-flight batch pinned t1: old indices + old replicas still
        # produce the correct masks (nothing was swapped underneath)
        got = sharded.verify_batch_mask_committee(msgs, idx, sigs, table=t1)
        assert got.tolist() == want
        # fresh traffic resolves against t2's permuted indices (each lane's
        # claimed validator pks[j] maps through the new table)
        idx2 = [t2.index[pks[j]] for j in idx]
        got2 = sharded.verify_batch_mask_committee(msgs, idx2, sigs)
        assert got2.tolist() == want
        # identical key sequence: no rebuild (same table object)
        assert sharded.set_committee(list(reversed(pks))) is t2


class TestMeshBackend:
    def test_register_committee_returns_size(self, committee):
        """Regression for the removed escape hatch: register_committee on
        a sharded backend is no longer a no-op — it returns the committee
        size and installs the replicated table."""
        from hotstuff_tpu.crypto.backend import make_backend
        from hotstuff_tpu.crypto.primitives import PublicKey

        _, pks = committee
        backend = make_backend("tpu", sharded=True, crossover=64)
        assert backend.register_committee([PublicKey(k) for k in pks]) == len(
            pks
        )
        assert backend._verifier.committee is not None
        assert backend._verifier.committee.size == len(pks)

    def test_backend_committee_dispatch_on_mesh(self, committee, digest_batch):
        """The acceptance check end to end: on a forced 4-device mesh,
        `verify_batch_mask(..., committee=True)` after `register_committee`
        rides the sharded committee kernel — byte-identical masks,
        committee_batches advancing, zero per-batch decompressions/table
        builds. Same mesh + bucket shapes as the verifier-level tests, so
        the kernel compile is shared through the persistent cache."""
        from hotstuff_tpu.crypto.backend import make_backend
        from hotstuff_tpu.crypto.primitives import PublicKey, Signature

        _, pks = committee
        msgs, keys, _, sigs, want = digest_batch
        # committee_crossover pinned below the batch size: the mesh-aware
        # default (alignment/8 = 64) would route this 13-lane batch to the
        # host CPU — exactly the sub-alignment behavior the crossover test
        # asserts, but here the device path is the subject
        backend = make_backend(
            "tpu",
            mesh=default_mesh(NDEV),
            crossover=1,
            committee_crossover=1,
            max_bucket=512,
        )
        assert backend.register_committee([PublicKey(k) for k in pks]) == len(
            pks
        )
        wkeys = [PublicKey(k) for k in keys]
        wsigs = [Signature(s) for s in sigs]
        backend.verify_batch_mask(msgs, wkeys, wsigs, committee=True)  # warm
        d0, b0, c0 = _M_DECOMP.value, _M_BUILDS.value, _M_CBATCHES.value
        mask = backend.verify_batch_mask(msgs, wkeys, wsigs, committee=True)
        assert mask == want
        assert _M_CBATCHES.value == c0 + 1
        assert _M_DECOMP.value == d0 and _M_BUILDS.value == b0

    def test_mesh_aware_committee_crossover(self, committee):
        """A sharded bucket is never narrower than lane*ndev, so the
        committee crossover scales with the alignment (min_bucket/8 —
        the single-chip ratio) instead of staying at crossover/4."""
        from hotstuff_tpu.crypto.backend import make_backend

        backend = make_backend("tpu", sharded=True, crossover=64)
        align = backend._verifier.mesh_alignment
        assert backend.committee_crossover == max(64 // 4, align // 8)
        # explicit override always wins
        forced = make_backend(
            "tpu", sharded=True, crossover=64, committee_crossover=7
        )
        assert forced.committee_crossover == 7
        # single-chip backends keep the plain crossover/4 default
        single = make_backend("tpu", crossover=64)
        assert single.committee_crossover == 16

    def test_warmup_widths_respect_mesh_alignment(self):
        """The warmup ladder must emit only batch sizes the sharded
        dispatcher actually buckets: every compiled width is on the
        alignment grid and no two sizes collapse onto one width."""
        from hotstuff_tpu.crypto.backend import make_backend

        backend = make_backend(
            "tpu", sharded=True, min_bucket=600, max_bucket=4096
        )
        v = backend._verifier
        sizes = backend._warmup_widths()
        widths = [v._bucket(n) for n in sizes]
        assert len(set(widths)) == len(widths), "duplicate compile shapes"
        assert all(w % v.mesh_alignment == 0 for w in widths)
        assert all(n <= min(v.chunk, v.max_bucket) for n in sizes)
        # the ladder covers the extremes the dispatcher uses
        assert v.min_bucket in widths
        assert v._bucket(min(v.chunk, v.max_bucket)) == widths[-1]
