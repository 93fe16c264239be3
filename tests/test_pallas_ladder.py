"""The Pallas ladder kernel, run on the CPU through the Pallas interpreter.

On a chip `TpuBackend` dispatches `pallas_ladder._verify_kernel_pallas_*`;
the CPU tests otherwise only ever run the jnp `w4` kernel. Here the kernel
BODY runs (interpret mode, passed by the test as a keyword — no environment
switch) on one BLOCK of seeded lanes and must agree with the w4 kernel and
with exact integer curve math. What the interpreter cannot show — tiling,
VMEM, the chip's own result — is tests/test_chip_compile.py's and
chip_smoke.py's job.
"""

import random

import numpy as np
import pytest

import jax

from hotstuff_tpu.ops import ed25519 as ed
from hotstuff_tpu.ops import pallas_ladder

pytest.importorskip("cryptography")

BAD_S, BAD_R, WRONG_KEY, WRONG_MSG = 3, 17, 101, 200


@pytest.fixture(scope="module")
def block():
    """One BLOCK of signed lanes, four of them corrupted, pushed through
    decompress -> table build -> `ladder_pallas(interpret=True)` ->
    compress; plus the w4 kernel's mask on the same staged inputs."""
    from __graft_entry__ import _signed_batch

    n = pallas_ladder.BLOCK
    msgs, pks, sigs = _signed_batch(n, seed=22)
    rng = random.Random(22)
    s_bad = bytearray(sigs[BAD_S])
    s_bad[40] ^= 0x01
    sigs[BAD_S] = bytes(s_bad)
    sigs[BAD_R] = sigs[BAD_R + 1][:32] + sigs[BAD_R][32:]
    pks[WRONG_KEY] = pks[WRONG_KEY + 1]
    msgs[WRONG_MSG] = rng.randbytes(32)
    staged = ed.prepare_batch(msgs, pks, sigs)
    a_y, a_sign, r_enc, s_digits, h_digits = ed.kernel_args(staged, n, "w4")

    @jax.jit
    def via_pallas(a_y, a_sign, r_enc, s_digits, h_digits):
        _x, xneg, valid = ed.decompress(a_y, a_sign)
        table = ed._build_neg_a_table(xneg, a_y)
        point = pallas_ladder.ladder_pallas(
            s_digits, h_digits, *table, interpret=True
        )
        enc = ed.compress(point)
        return enc, valid & (enc == r_enc).all(axis=0)

    enc, mask = via_pallas(a_y, a_sign, r_enc, s_digits, h_digits)
    w4_mask = ed._verify_w4_jit(a_y, a_sign, r_enc, s_digits, h_digits)
    return {
        "msgs": msgs,
        "pks": pks,
        "sigs": sigs,
        "enc": np.asarray(enc),
        "mask": np.asarray(mask) & staged["s_ok"],
        "w4_mask": np.asarray(w4_mask) & staged["s_ok"],
    }


def test_interpreted_pallas_ladder_masks_match_w4(block):
    want = np.ones(pallas_ladder.BLOCK, bool)
    want[[BAD_S, BAD_R, WRONG_KEY, WRONG_MSG]] = False
    assert block["mask"].tolist() == want.tolist()
    assert block["mask"].tolist() == block["w4_mask"].tolist()


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
