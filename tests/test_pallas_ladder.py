"""The Pallas ladder kernel, run on the CPU through the Pallas interpreter.

On a chip `TpuBackend` dispatches `pallas_ladder._verify_kernel_pallas_*`;
the CPU tests otherwise only ever run the jnp `w4` kernel. Here the kernel
BODY runs (interpret mode, passed by the test as a keyword — no environment
switch) on one BLOCK of seeded lanes and must agree with the w4 kernel and
with exact integer curve math. What the interpreter cannot show — tiling,
VMEM, the chip's own result — is tests/test_chip_compile.py's and
chip_smoke.py's job.
"""

import functools
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

from hotstuff_tpu.ops import ed25519 as ed
from hotstuff_tpu.ops import field as f
from hotstuff_tpu.ops import pallas_ladder

pytest.importorskip("cryptography")

BAD_S, BAD_R, WRONG_KEY, WRONG_MSG = 3, 17, 101, 200
NONCANONICAL_R, NO_SQRT_KEY, ZERO_X_KEY = 60, 150, 151
TABLE_PARTS = ("ypx", "ymx", "z", "t2d")


def _neg_a_table_pallas(x_neg, a_y):
    """`_neg_a_table_into` alone in an interpreted `pallas_call`: the four
    tables as outputs, where the ladder kernel keeps them in VMEM scratch."""

    def kernel(d2_ref, x_ref, y_ref, *table_refs):
        with f.mosaic_safe():
            pallas_ladder._neg_a_table_into(
                x_ref[:], y_ref[:], d2_ref[:], *table_refs
            )

    table = jax.ShapeDtypeStruct((16,) + a_y.shape, jnp.float32)
    return pl.pallas_call(kernel, out_shape=[table] * 4, interpret=True)(
        ed.D2, x_neg, a_y
    )


@pytest.fixture(scope="module")
def block():
    """One BLOCK of signed lanes, six of them corrupted (one key has no
    square root, one is the identity: x = 0), pushed through decompress ->
    `ladder_pallas(interpret=True)`, table prologue and all -> compress;
    plus the w4 kernel's mask on the same staged inputs, and the -A table
    of the same keys from the kernel's prologue and from the jnp build."""
    from __graft_entry__ import _signed_batch
    from chip_smoke import _off_curve_key

    n = pallas_ladder.BLOCK
    msgs, pks, sigs = _signed_batch(n, seed=22)
    rng = random.Random(22)
    s_bad = bytearray(sigs[BAD_S])
    s_bad[40] ^= 0x01
    sigs[BAD_S] = bytes(s_bad)
    sigs[BAD_R] = sigs[BAD_R + 1][:32] + sigs[BAD_R][32:]
    pks[WRONG_KEY] = pks[WRONG_KEY + 1]
    pks[NO_SQRT_KEY] = _off_curve_key()
    pks[ZERO_X_KEY] = (1).to_bytes(32, "little")  # y = 1: the identity
    msgs[WRONG_MSG] = rng.randbytes(32)
    staged = ed.prepare_batch(msgs, pks, sigs)
    a_y, a_sign, r_enc, s_digits, h_digits = ed.kernel_args(staged, n, "w4")

    @jax.jit
    def via_pallas(a_y, a_sign, r_enc, s_digits, h_digits):
        _x, xneg, valid = ed.decompress(a_y, a_sign)
        point = pallas_ladder.ladder_pallas(
            s_digits, h_digits, xneg, a_y, interpret=True
        )
        enc = ed.compress(point)
        tables = _neg_a_table_pallas(xneg, a_y), ed._build_neg_a_table(xneg, a_y)
        return enc, valid & (enc == r_enc).all(axis=0), valid, xneg, tables

    enc, mask, valid, xneg, (table, jnp_table) = via_pallas(
        a_y, a_sign, r_enc, s_digits, h_digits
    )
    w4_mask = ed._verify_w4_jit(a_y, a_sign, r_enc, s_digits, h_digits)
    return {
        "msgs": msgs,
        "pks": pks,
        "sigs": sigs,
        "enc": np.asarray(enc),
        "mask": np.asarray(mask) & staged["s_ok"],
        "w4_mask": np.asarray(w4_mask) & staged["s_ok"],
        "valid": np.asarray(valid),
        "xneg": np.asarray(xneg),
        "table": dict(zip(TABLE_PARTS, map(np.asarray, table))),
        "jnp_table": dict(zip(TABLE_PARTS, map(np.asarray, jnp_table))),
    }


def test_interpreted_pallas_ladder_masks_match_w4(block):
    want = np.ones(pallas_ladder.BLOCK, bool)
    want[[BAD_S, BAD_R, WRONG_KEY, NO_SQRT_KEY, ZERO_X_KEY, WRONG_MSG]] = False
    assert block["mask"].tolist() == want.tolist()
    assert block["mask"].tolist() == block["w4_mask"].tolist()


@pytest.mark.parametrize("part", TABLE_PARTS)
def test_kernel_prologue_table_equals_jnp_build_limb_for_limb(block, part):
    """All 16 cached multiples of -A in every lane, not only mod p: the
    values are exact integers in f32 and the prologue does the jnp build's
    operations on its operands, so the ladder's bound on a table limb
    (a lazy sum of two normalized elements) holds for it unchanged."""
    got, want = block["table"][part], block["jnp_table"][part]
    assert got.shape == want.shape == (16, f.NLIMB, pallas_ladder.BLOCK)
    assert np.array_equal(got, want)
    assert got.min() >= 0.0 and got.max() <= 590.0


def test_kernel_prologue_table_of_keys_that_are_no_curve_point_or_have_x_zero(block):
    """A key without a square root is not valid and its lane's table is
    still the jnp build's; the identity key (x = 0, valid) gives sixteen
    projective forms of the identity: Y+X = Y-X = Z != 0, 2dT = 0 mod p."""
    assert not block["valid"][NO_SQRT_KEY] and block["valid"][ZERO_X_KEY]
    assert not block["xneg"][:, ZERO_X_KEY].any()
    entries = {}
    for part in TABLE_PARTS:
        got, want = block["table"][part], block["jnp_table"][part]
        assert np.array_equal(got[:, :, NO_SQRT_KEY], want[:, :, NO_SQRT_KEY])
        lane = jnp.asarray(got[:, :, ZERO_X_KEY].T)  # (32 limbs, 16 entries)
        entries[part] = f.int_of_limbs(np.asarray(f.canonical(lane)))
    assert entries["ypx"] == entries["ymx"] == entries["z"]
    assert all(entries["z"]) and not any(entries["t2d"])


@pytest.mark.parametrize("lane", [0, BAD_S, WRONG_MSG])
def test_interpreted_pallas_ladder_point_matches_integer_math(block, lane):
    """enc([s]B - [h]A) from the kernel equals the same point computed
    with exact Python integers — on a valid lane it is R, on a corrupted
    one it is whatever the corrupted scalars give, and still must agree."""
    import hashlib

    pk, sig, msg = block["pks"][lane], block["sigs"][lane], block["msgs"][lane]
    s = int.from_bytes(sig[32:], "little")
    h = (
        int.from_bytes(hashlib.sha512(sig[:32] + pk + msg).digest(), "little")
        % ed.L_ORDER
    )
    ax, ay = ed._decompress_int(pk)

    def mul(k, pt):
        acc = (0, 1)
        while k:
            if k & 1:
                acc = ed._edwards_add_int(acc, pt)
            pt = ed._edwards_add_int(pt, pt)
            k >>= 1
        return acc

    x, y = ed._edwards_add_int(
        mul(s, (ed.BX_INT, ed.BY_INT)), mul(h, ((ed.P - ax) % ed.P, ay))
    )
    want = (y | ((x & 1) << 255)).to_bytes(32, "little")
    got = bytes(block["enc"][:, lane].astype(np.uint8))
    assert got == want


# --- the fixed-exponent chains (decompress's square root, compress's 1/Z) ---

CHAIN_EXPONENTS = {"invert": f.P - 2, "pow2523": (f.P - 5) // 8}


def _chain_inputs():
    """One BLOCK of elements: the corners, canonical random values,
    and lazily reduced ones (limbs past 255, up to `f.mul`'s stated input
    bound of 700 in every limb: more than either caller hands the chain)."""
    rng = random.Random(30)
    n = pallas_ladder.BLOCK
    canon = [0, 1, 2, f.P - 1] + [rng.randrange(f.P) for _ in range(n - 68)]
    z = np.concatenate([f.limbs_of_int(v) for v in canon], axis=1)
    lazy = np.array(
        [[rng.randrange(701) for _ in range(64)] for _ in range(f.NLIMB)],
        np.float32,
    )
    lazy[:, 0] = 700.0
    lazy[:, 1] = 295.0  # what a normalized `f.mul` output can reach
    return np.concatenate([z, lazy], axis=1)


@pytest.mark.parametrize("tail", sorted(CHAIN_EXPONENTS))
def test_interpreted_chain_kernel_matches_integer_pow(tail):
    z = _chain_inputs()
    assert z.shape == (f.NLIMB, pallas_ladder.BLOCK)
    out = pallas_ladder.chain_pallas(jnp.asarray(z), tail=tail, interpret=True)
    assert float(jnp.max(out)) <= 295.0  # normalized, as `f.mul` promises
    got = f.int_of_limbs(np.asarray(f.canonical(out)))
    want = [pow(v, CHAIN_EXPONENTS[tail], f.P) for v in f.int_of_limbs(z)]
    assert got == want
    assert got[0] == 0  # 0 -> 0: an invalid key's lane flows on


def test_sqr_n_squares_truly_only_inside_a_pallas_body():
    """Plain XLA keeps `mul(x, x)` in the chains' loops (the jnp programs'
    HLO does not move); a Mosaic body gets `sqr`."""
    from jax import lax

    a = jnp.zeros((f.NLIMB, 128), jnp.float32)

    def loop_of(step):
        return str(jax.make_jaxpr(lambda x: lax.fori_loop(0, 3, lambda _, y: step(y), x))(a))

    def chain():
        return str(jax.make_jaxpr(lambda x: f.sqr_n(x, 3))(a))

    assert chain() == loop_of(lambda y: f.mul(y, y))
    with f.mosaic_safe():
        inside, want = chain(), loop_of(f.sqr)
    assert inside == want != chain()


def test_whole_pallas_program_with_interpreted_chains_matches_w4(monkeypatch):
    """`_verify_kernel_pallas` as the chip runs it (decompress with the
    Pallas square root, Pallas ladder with its table prologue, compress with
    the Pallas inversion), its three kernels interpreted: every lane's
    verdict equals the jnp w4 program's, whose chains are the jnp `while`
    loops and whose table is the jnp build."""
    from __graft_entry__ import _signed_batch
    from chip_smoke import _off_curve_key

    n = pallas_ladder.BLOCK
    msgs, pks, sigs = _signed_batch(n, seed=30)
    s_bad = bytearray(sigs[BAD_S])
    s_bad[40] ^= 0x01
    sigs[BAD_S] = bytes(s_bad)
    # R's y is p + 3: the same field element as y = 3, never the encoding
    sigs[NONCANONICAL_R] = (f.P + 3).to_bytes(32, "little") + sigs[NONCANONICAL_R][32:]
    pks[NO_SQRT_KEY] = _off_curve_key()
    msgs[WRONG_MSG] = bytes(32)
    staged = ed.prepare_batch(msgs, pks, sigs)
    args = ed.kernel_args(staged, n, "w4")

    for name in ("ladder_pallas", "pow2523_pallas", "invert_pallas"):
        monkeypatch.setattr(
            pallas_ladder,
            name,
            functools.partial(getattr(pallas_ladder, name), interpret=True),
        )
    mask = np.asarray(jax.jit(pallas_ladder._verify_kernel_pallas)(*args))
    w4_mask = np.asarray(ed._verify_w4_jit(*args))
    want = np.ones(n, bool)
    want[[BAD_S, NONCANONICAL_R, NO_SQRT_KEY, WRONG_MSG]] = False
    assert (mask & staged["s_ok"]).tolist() == want.tolist()
    assert mask.tolist() == w4_mask.tolist()


def test_mosaic_safe_trace_mode_is_per_thread():
    """Programs are traced concurrently (service dispatch threads, the
    smoke's side-by-side compiles). The trace-mode flag used to be a module
    global: on four chips a Pallas body lost it mid-trace when another
    thread's context exited and Mosaic refused the resulting scatter-add;
    on one chip a w4 program traced meanwhile took the Pallas-safe form."""
    import threading

    from hotstuff_tpu.ops import field as f

    inside, release = threading.Event(), threading.Event()
    seen = {}

    def pallas_tracer():
        with f.mosaic_safe():
            inside.set()
            release.wait(5)
            seen["pallas_after_other_exit"] = f._mosaic_safe_on()

    t = threading.Thread(target=pallas_tracer)
    t.start()
    assert inside.wait(5)
    seen["xla_thread_meanwhile"] = f._mosaic_safe_on()
    with f.mosaic_safe():  # a second Pallas trace enters and exits...
        pass
    release.set()  # ...while the first is still tracing
    t.join(5)
    assert seen == {"xla_thread_meanwhile": False, "pallas_after_other_exit": True}
    assert not f._mosaic_safe_on()
