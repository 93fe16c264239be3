"""Device-side SHA-512(R||A||M) mod L (ops/sha512.py): bit-exactness with
the host path (hashlib + bigint mod) is a consensus-safety requirement —
every replica, CPU or TPU, must accept exactly the same signature set
(reference crypto/src/lib.rs:209-220 computes h inside ed25519_dalek)."""

import hashlib
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hotstuff_tpu.ops import ed25519 as ed
from hotstuff_tpu.ops import sha512 as S


def _signed_batch(*args, **kwargs):
    """OpenSSL-signed batch; skips the test when the wheel is absent."""
    pytest.importorskip("cryptography")
    from __graft_entry__ import _signed_batch as real

    return real(*args, **kwargs)

RNG = random.Random(17)


def _cols(rows_of_bytes):
    n = len(rows_of_bytes)
    return np.frombuffer(b"".join(rows_of_bytes), np.uint8).reshape(n, 32).T.copy()


def test_sha512_96_matches_hashlib():
    B = 16
    rs = [RNG.randbytes(32) for _ in range(B)]
    as_ = [RNG.randbytes(32) for _ in range(B)]
    ms = [RNG.randbytes(32) for _ in range(B)]
    # include degenerate inputs
    rs[0] = bytes(32)
    as_[1] = b"\xff" * 32
    out = np.asarray(
        jax.jit(S.sha512_96)(
            jnp.asarray(_cols(rs)), jnp.asarray(_cols(as_)), jnp.asarray(_cols(ms))
        )
    )
    for i in range(B):
        want = hashlib.sha512(rs[i] + as_[i] + ms[i]).digest()
        got = bytes(int(out[j, i]) for j in range(64))
        assert got == want, f"item {i}"


def test_reduce_mod_l_exact():
    vals = [
        0,
        1,
        S.L - 1,
        S.L,
        S.L + 1,
        2 * S.L - 1,
        2**252,
        2**256 - 1,
        2**512 - 1,
        (S.L << 134) + 5,
        (S.L << 259) - 1,  # near the 2^512 input-domain ceiling
    ]
    vals += [RNG.randrange(2**512) for _ in range(500)]
    arr = np.zeros((64, len(vals)), np.float32)
    for i, v in enumerate(vals):
        for j in range(64):
            arr[j, i] = (v >> (8 * j)) & 0xFF
    red = np.asarray(jax.jit(S.reduce_mod_l)(jnp.asarray(arr)))
    assert red.max() <= 255 and red.min() >= 0
    for i, v in enumerate(vals):
        got = sum(int(red[j, i]) << (8 * j) for j in range(32))
        assert got == v % S.L, f"value index {i}"


def test_h_digits_on_device_matches_host_staging():
    msgs, pks, sigs = _signed_batch(32, seed=9)
    host = ed.prepare_batch(msgs, pks, sigs, allow_native=False)
    r = _cols([s[:32] for s in sigs])
    a = _cols(pks)
    m = _cols(msgs)
    dev = np.asarray(
        jax.jit(S.h_digits_on_device)(
            jnp.asarray(r), jnp.asarray(a), jnp.asarray(m)
        )
    )
    np.testing.assert_array_equal(dev, host["h_digits"])


def test_packed_dh_kernel_matches_packed():
    """The device-hash kernel must agree with the host-hash kernel on good
    AND adversarial items (corrupt signature, corrupt key, zero rows)."""
    msgs, pks, sigs = _signed_batch(8, seed=4)
    sigs[2] = bytes(64)
    pks[5] = bytes(31) + b"\xff"
    sigs[6] = sigs[0]
    staged_h = ed.prepare_batch_packed(msgs, pks, sigs, allow_native=False)
    staged_m = ed.prepare_batch_packed_dh(msgs, pks, sigs)
    np.testing.assert_array_equal(staged_h["s_ok"], staged_m["s_ok"])
    want = np.asarray(ed.PROGRAMS["w4p128"](jnp.asarray(staged_h["packed"])))
    got = np.asarray(ed.PROGRAMS["w4p128dh"](jnp.asarray(staged_m["packed"])))
    np.testing.assert_array_equal(got, want)
    assert want[0] and not want[2] and not want[5] and not want[6]


def test_s_canonical_mask_vectorized():
    L = ed.L_ORDER
    cases = [0, 1, L - 1, L, L + 1, 2**256 - 1, L + 2**255]
    cases += [RNG.randrange(2**256) for _ in range(200)]
    s = np.zeros((len(cases), 32), np.uint8)
    for i, v in enumerate(cases):
        s[i] = np.frombuffer(v.to_bytes(32, "little"), np.uint8)
    got = ed._s_canonical_mask(s)
    want = np.array([v < L for v in cases])
    np.testing.assert_array_equal(got, want)


def test_verifier_auto_selects_device_hash():
    """32-byte messages ride the device-hash path; mixed lengths fall back
    to host hashing — both must verify correctly."""
    v = ed.Ed25519TpuVerifier(kernel="w4", max_bucket=256)
    msgs, pks, sigs = _signed_batch(6, seed=11)
    sigs[3] = bytes(64)
    mask = v.verify_batch_mask(msgs, pks, sigs)
    assert mask.tolist() == [True, True, True, False, True, True]

    # non-32-byte messages: host-hash fallback
    msgs2, pks2, sigs2 = _signed_batch(4, msg_len=100, seed=12)
    sigs2[1] = bytes(64)
    mask2 = v.verify_batch_mask(msgs2, pks2, sigs2)
    assert mask2.tolist() == [True, False, True, True]


def test_sharded_device_hash_matches():
    from hotstuff_tpu.parallel import ShardedEd25519Verifier, default_mesh

    msgs, pks, sigs = _signed_batch(16, seed=13)
    sigs[9] = sigs[1]
    v = ShardedEd25519Verifier(mesh=default_mesh(4), kernel="w4")
    mask = v.verify_batch_mask(msgs, pks, sigs)
    want = [True] * 16
    want[9] = False
    assert mask.tolist() == want


def test_device_hash_failure_raises_and_nothing_reruns_on_host(monkeypatch):
    """A failure of the device-hash program raises like any other device
    error: no silent rerun through the host-hash twin, no latch. (A
    program the chip refuses is found at warm-up, which raises at boot.)"""
    v = ed.Ed25519TpuVerifier(kernel="w4", max_bucket=256)
    msgs, pks, sigs = _signed_batch(5, seed=21)

    def fail(*a, **k):
        raise RuntimeError("injected lowering failure")

    monkeypatch.setitem(v.programs, "w4p128dh", fail)
    with pytest.raises(RuntimeError, match="injected lowering failure"):
        v.verify_batch_mask(msgs, pks, sigs)
    assert "w4p128" not in v.dispatched  # the host-hash twin never ran


def test_dispatched_names_the_program_per_message_length():
    """32-byte digests ride the device-hash program, any other length the
    host-hash twin; `dispatched` says which — it is how a run shows what
    kernel checked its signatures."""
    v = ed.Ed25519TpuVerifier(kernel="w4", max_bucket=256)
    msgs, pks, sigs = _signed_batch(3, seed=22)
    assert v.verify_batch_mask(msgs, pks, sigs).all()
    assert dict(v.dispatched) == {"w4p128dh": 1}
    msgs2, pks2, sigs2 = _signed_batch(3, msg_len=100, seed=23)
    assert v.verify_batch_mask(msgs2, pks2, sigs2).all()
    assert dict(v.dispatched) == {"w4p128dh": 1, "w4p128": 1}
    assert v.program_name(True, True) == "w4c96dh"
    assert ed.Ed25519TpuVerifier(kernel="pallas").program_name(False, True) == (
        "pallas_p128dh"
    )


_TABLE_CASES = [
    (kernel, committee, device_hash)
    for kernel in ("w4", "pallas")
    for committee in (False, True)
    for device_hash in (False, True)
]


@pytest.mark.parametrize("kernel,committee,device_hash", _TABLE_CASES)
def test_program_name_resolves_to_one_callable(kernel, committee, device_hash):
    """Every name `program_name` can return is a key of the verifier's
    table, for the one-chip and the mesh verifier alike; the one-chip
    verifier holds the module's jitted singleton (no second compile of one
    program in a process), the mesh verifier its own wrapper of the same
    traceable kernel under the same name."""
    from hotstuff_tpu.ops import pallas_ladder
    from hotstuff_tpu.parallel import ShardedEd25519Verifier, default_mesh

    singletons = {**ed.PROGRAMS, **pallas_ladder.PROGRAMS}
    base = "w4c96" if committee else {"w4": "w4p128", "pallas": "pallas_p128"}[kernel]
    want = base + ("dh" if device_hash else "")
    single = ed.Ed25519TpuVerifier(kernel=kernel)
    sharded = ShardedEd25519Verifier(mesh=default_mesh(4), kernel=kernel)
    for v in (single, sharded):
        name = v.program_name(committee, device_hash)
        assert name == want
        assert len(v.programs) == 4 and callable(v.programs[name])
        # one callable per name: no other name of the table aliases it
        assert [k for k, fn in v.programs.items() if fn is v.programs[name]] == [name]
    assert single.programs[want] is singletons[want]
    assert sharded.programs[want] is not singletons[want]
    assert set(sharded.programs) == set(single.programs)


def test_no_jitted_verify_program_outside_the_table():
    """The two ops modules hold six jitted verify programs, all in their
    PROGRAMS tables under the names `program_name` returns, plus the one
    f32-argument reference the tests and __graft_entry__ compare against."""
    from hotstuff_tpu.ops import pallas_ladder

    jitted = type(jax.jit(lambda x: x))

    def loose(mod):
        return {
            name
            for name, obj in vars(mod).items()
            if isinstance(obj, jitted) and "verify" in name
        }

    assert loose(ed) == {"_verify_w4_jit"}
    assert loose(pallas_ladder) == set()
    assert set(ed.PROGRAMS) == {"w4p128", "w4p128dh", "w4c96", "w4c96dh"}
    assert set(pallas_ladder.PROGRAMS) == {"pallas_p128", "pallas_p128dh"}
    for table, kernels in (
        (ed.PROGRAMS, ed.KERNELS),
        (pallas_ladder.PROGRAMS, pallas_ladder.KERNELS),
    ):
        assert set(table) == set(kernels)
        assert all(isinstance(fn, jitted) for fn in table.values())
    with pytest.raises(ValueError, match="kernel"):
        ed.Ed25519TpuVerifier(kernel="bits")
