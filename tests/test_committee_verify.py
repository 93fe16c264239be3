"""Committee-resident verification path (ops/ed25519.CommitteeTable).

The committee kernel gathers precomputed -A window tables by validator
index instead of decompressing keys and building tables per batch; its
masks must be BYTE-IDENTICAL to the generic kernel on the RFC 8032
vectors, forged-signature lanes, and non-canonical-s lanes — and the
steady-state batches must perform zero on-device decompressions/table
builds (verifier.decompressions / verifier.table_builds counters).

Dependency-free on purpose: the vectors are fixed constants, so this file
runs on hosts without the `cryptography` wheel.
"""

import numpy as np
import pytest

from hotstuff_tpu.ops import ed25519 as ed
from hotstuff_tpu.utils import metrics
from tests.test_rfc8032_vectors import VECTORS, _unhex

_M_DECOMP = metrics.counter("verifier.decompressions")
_M_BUILDS = metrics.counter("verifier.table_builds")
_M_CSIGS = metrics.counter("verifier.committee_sigs")
_M_CREGS = metrics.counter("verifier.committee_registrations")


def _vector_batch():
    """RFC 8032 vectors + forged (R, s, message) lanes + a non-canonical-s
    lane: exercises every rejection class the kernels distinguish."""
    triples = [_unhex(v) for v in VECTORS]
    msgs = [m for m, _, _ in triples]
    pks = [k for _, k, _ in triples]
    sigs = [s for _, _, s in triples]
    # forged R (bit flip)
    msgs.append(msgs[0])
    pks.append(pks[0])
    sigs.append(bytes([sigs[0][0] ^ 1]) + sigs[0][1:])
    # forged s (bit flip)
    msgs.append(msgs[1])
    pks.append(pks[1])
    sigs.append(sigs[1][:33] + bytes([sigs[1][33] ^ 1]) + sigs[1][34:])
    # wrong message
    msgs.append(msgs[2] + b"\x00")
    pks.append(pks[2])
    sigs.append(sigs[2])
    # non-canonical s' = s + L: verifies under cofactored rules, strict
    # verification must reject it on BOTH paths
    s_int = int.from_bytes(sigs[3][32:], "little") + ed.L_ORDER
    msgs.append(msgs[3])
    pks.append(pks[3])
    sigs.append(sigs[3][:32] + s_int.to_bytes(32, "little"))
    return msgs, pks, sigs


@pytest.fixture(scope="module")
def verifier():
    # min_bucket 128 (the default) on purpose: every batch in this module
    # pads to ONE width, and the generic-kernel compile is shared with
    # tests/test_rfc8032_vectors.py in the same pytest process — XLA CPU
    # compiles of the 253-step ladder are minutes each.
    return ed.Ed25519TpuVerifier(max_bucket=128, kernel="w4")


class TestCommitteeKernel:
    def test_masks_byte_identical_to_generic(self, verifier):
        msgs, pks, sigs = _vector_batch()
        generic = verifier.verify_batch_mask(msgs, pks, sigs)
        # expected shape: 4 valid vectors, then 4 rejected perturbations
        assert generic.tolist() == [True] * 4 + [False] * 4

        table = verifier.set_committee(sorted(set(pks)))
        idx = [table.index[k] for k in pks]
        committee = verifier.verify_batch_mask_committee(msgs, idx, sigs)
        assert committee.dtype == generic.dtype
        assert committee.tolist() == generic.tolist()

    def test_zero_decompressions_in_steady_state(self, verifier):
        msgs, pks, sigs = _vector_batch()
        table = verifier.set_committee(sorted(set(pks)))
        idx = [table.index[k] for k in pks]
        d0, b0, s0 = _M_DECOMP.value, _M_BUILDS.value, _M_CSIGS.value
        for _ in range(3):  # steady state: repeated batches, same committee
            verifier.verify_batch_mask_committee(msgs, idx, sigs)
        assert _M_DECOMP.value == d0, "committee path must not decompress"
        assert _M_BUILDS.value == b0, "committee path must not build tables"
        assert _M_CSIGS.value == s0 + 3 * len(msgs)

    def test_invalid_committee_key_lanes_fail(self, verifier):
        msgs, pks, sigs = _vector_batch()
        # y with no valid x (not on curve), same scan as test_ops_ed25519
        bad = None
        for cand in range(2, 50):
            u = (cand * cand - 1) % ed.P
            vv = (ed.D_INT * cand * cand + 1) % ed.P
            x2 = u * pow(vv, ed.P - 2, ed.P) % ed.P
            if pow(x2, (ed.P - 1) // 2, ed.P) == ed.P - 1:
                bad = cand
                break
        assert bad is not None
        bad_key = bad.to_bytes(32, "little")
        assert ed._decompress_int(bad_key) is None
        keys = sorted(set(pks)) + [bad_key]
        table = verifier.set_committee(keys)
        assert not np.asarray(table.valid)[table.index[bad_key]]
        idx = [table.index[k] for k in pks] + [table.index[bad_key]]
        mask = verifier.verify_batch_mask_committee(
            msgs + [msgs[0]], idx, sigs + [sigs[0]]
        )
        assert mask.tolist() == [True] * 4 + [False] * 4 + [False]

    def test_registration_idempotent_and_invalidated_on_change(self, verifier):
        msgs, pks, sigs = _vector_batch()
        keys = sorted(set(pks))
        t1 = verifier.set_committee(keys)
        regs = _M_CREGS.value
        # identical key set: no rebuild, same table object
        assert verifier.set_committee(list(keys)) is t1
        assert _M_CREGS.value == regs
        # changed key set (reconfiguration): rebuild + fresh indices
        reordered = list(reversed(keys))
        t2 = verifier.set_committee(reordered)
        assert t2 is not t1
        assert _M_CREGS.value == regs + 1
        assert verifier.committee is t2
        # verification against the NEW indices still byte-identical
        idx = [t2.index[k] for k in pks]
        committee = verifier.verify_batch_mask_committee(msgs, idx, sigs)
        assert committee.tolist() == [True] * 4 + [False] * 4

    def test_epoch_reregistration_pins_in_flight_snapshot(self, verifier):
        """The epoch-reconfig contract on a single chip (the mesh variant
        lives in tests/test_mesh_committee.py): a batch staged against a
        pinned table snapshot completes correctly on the OLD epoch's
        precompute even after a committee succession (one validator
        leaves) re-registers the tables mid-flight — what
        reconfig.EpochManager relies on when it swaps committees at a
        committed boundary with chunks still in the dispatch window."""
        msgs, pks, sigs = _vector_batch()
        want = [True] * 4 + [False] * 4
        keys = sorted(set(pks))
        t1 = verifier.set_committee(keys)
        idx_old = [t1.index[k] for k in pks]
        # epoch succession: the last validator departs; indices permute
        # and the departed key's precompute rows are gone from t2
        departed = keys[-1]
        t2 = verifier.set_committee(list(reversed(keys[:-1])))
        assert t2 is not t1 and verifier.committee is t2
        assert t2.size == t1.size - 1 and departed not in t2.index
        # the in-flight old-epoch batch, pinned to t1, still verifies
        # byte-identically (nothing swapped underneath it)
        got = verifier.verify_batch_mask_committee(
            msgs, idx_old, sigs, table=t1
        )
        assert got.tolist() == want
        # new-epoch traffic: the surviving keys' lanes resolve against
        # t2's fresh indices and keep their expected verdicts
        live = [
            (m, k, s, w)
            for m, k, s, w in zip(msgs, pks, sigs, want)
            if k != departed
        ]
        assert live
        got2 = verifier.verify_batch_mask_committee(
            [m for m, _k, _s, _w in live],
            [t2.index[k] for _m, k, _s, _w in live],
            [s for _m, _k, s, _w in live],
        )
        assert got2.tolist() == [w for _m, _k, _s, w in live]


@pytest.mark.parametrize("device_hash", [False, True], ids=["host_hash", "device_hash"])
def test_two_chunks_ragged_tail_match_generic_family(device_hash):
    """The families share one chunk loop (`Ed25519TpuVerifier._run`): a
    committee batch split at `chunk` into a full chunk and a ragged one,
    a forged lane in each, gives the generic family's mask lane for lane,
    with both staged arrays (indices, wire rows) padded per chunk and the
    tables of ONE snapshot ahead of them in every dispatch. Width 128 and
    `chunk=64`, as the rest of tier-1 compiles."""
    n = 101  # chunks of 64 and 37 lanes
    if device_hash:
        from tests.common import rfc8032_keypair, rfc8032_sign

        kps = [rfc8032_keypair(bytes([i + 1]) * 32) for i in range(4)]
        pool = []
        for i, kp in enumerate(kps):
            m = (b"chunks-%d" % i).ljust(32, b"\0")  # a 32-byte digest
            pool.append((m, kp[0], rfc8032_sign(kp, m)))
    else:
        pool = [_unhex(v) for v in VECTORS]  # messages of 0, 1, 2, 1023 bytes
    msgs = [pool[i % 4][0] for i in range(n)]
    pks = [pool[i % 4][1] for i in range(n)]
    sigs = [pool[i % 4][2] for i in range(n)]
    for lane in (5, 100):  # chunk 0, chunk 1
        sigs[lane] = sigs[lane][:33] + bytes([sigs[lane][33] ^ 1]) + sigs[lane][34:]
    want = [lane not in (5, 100) for lane in range(n)]

    v = ed.Ed25519TpuVerifier(
        min_bucket=128, max_bucket=128, kernel="w4", chunk=64
    )
    try:
        table = v.set_committee(sorted({k for _, k, _ in pool}))
        idx = [table.index[k] for k in pks]
        p0 = metrics.counter("verifier.pad_lanes").value
        b0, d0 = _M_BUILDS.value, _M_DECOMP.value
        committee = v.verify_batch_mask_committee(msgs, idx, sigs)
        assert metrics.counter("verifier.pad_lanes").value == p0 + 2 * 128 - n
        assert (_M_BUILDS.value, _M_DECOMP.value) == (b0, d0)
        generic = v.verify_batch_mask(msgs, pks, sigs)
        assert (_M_BUILDS.value, _M_DECOMP.value) == (b0 + 2, d0 + n)
    finally:
        v.close()
    assert committee.tolist() == want
    assert committee.tolist() == generic.tolist()
    dh = "dh" if device_hash else ""
    assert dict(v.dispatched) == {"w4c96" + dh: 2, "w4p128" + dh: 2}


class TestBackendRouting:
    def test_tagged_batches_ride_committee_kernel(self):
        """TpuBackend: committee-tagged batches whose keys all resolve ride
        the committee kernel; a batch containing an unregistered key falls
        back to the generic path (verifier.committee_misses)."""
        from hotstuff_tpu.crypto.backend import make_backend
        from hotstuff_tpu.crypto.primitives import PublicKey, Signature

        msgs, pks, sigs = _vector_batch()
        backend = make_backend(
            "tpu", crossover=1, min_bucket=128, max_bucket=128
        )
        backend.register_committee([PublicKey(k) for k in set(pks)])
        keys = [PublicKey(k) for k in pks]
        wraps = [Signature(s) for s in sigs]
        c0 = _M_CSIGS.value
        mask = backend.verify_batch_mask(msgs, keys, wraps, committee=True)
        assert mask == [True] * 4 + [False] * 4
        assert _M_CSIGS.value == c0 + len(msgs)

        # one unregistered key -> whole batch falls back to generic
        misses0 = metrics.counter("verifier.committee_misses").value
        outsider = PublicKey(bytes(31) + b"\x01")
        mask2 = backend.verify_batch_mask(
            msgs + [msgs[0]],
            keys + [outsider],
            wraps + [wraps[0]],
            committee=True,
        )
        assert mask2[: len(msgs)] == mask
        assert mask2[-1] is False
        assert (
            metrics.counter("verifier.committee_misses").value == misses0 + 1
        )
        assert _M_CSIGS.value == c0 + len(msgs), "miss must not ride kernel"

    def test_crossover_fallback_counter(self):
        from hotstuff_tpu.crypto.backend import make_backend
        from hotstuff_tpu.crypto.primitives import PublicKey, Signature

        msgs, pks, sigs = _vector_batch()
        backend = make_backend(
            "tpu", crossover=64, min_bucket=128, max_bucket=128
        )
        f0 = metrics.counter("verifier.crossover_fallbacks").value
        # n=8 < crossover: CPU fast path. Without the host `cryptography`
        # wheel the CPU backend raises — either way the counter must tick.
        try:
            backend.verify_batch_mask(
                msgs, [PublicKey(k) for k in pks], [Signature(s) for s in sigs]
            )
        except ImportError:
            pass
        assert (
            metrics.counter("verifier.crossover_fallbacks").value == f0 + 1
        )


class TestHostDecompression:
    def test_matches_device_decompress_on_vectors(self):
        """Host exact-int decompression must agree with the device kernel's
        decompress on every vector key (x, y as canonical ints)."""
        from hotstuff_tpu.ops import field as f

        for pk_hex, _, _ in VECTORS:
            kb = bytes.fromhex(pk_hex)
            got = ed._decompress_int(kb)
            assert got is not None
            x, y = got
            a = np.frombuffer(kb, np.uint8).astype(np.float32).reshape(32, 1)
            a_y = a.copy()
            a_y[31, 0] = float(kb[31] & 0x7F)
            sign = np.array([float(kb[31] >> 7)], np.float32)
            dx, _, valid = ed.decompress(a_y, sign)
            assert bool(np.asarray(valid)[0])
            assert f.int_of_limbs(np.asarray(dx))[0] == x
            # y round-trips through the curve equation: on-curve point
            assert (
                (-x * x + y * y - 1 - ed.D_INT * x * x * y * y) % ed.P == 0
            )
