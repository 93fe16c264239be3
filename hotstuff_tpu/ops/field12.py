"""EXPERIMENTAL: GF(2^255 - 19) in radix-2^12 uint32 limbs (22 limbs).

The production field (`ops.field`) uses 32 radix-256 f32 limbs because f32
accumulation is exact only below 2^24: with 32 limbs the schoolbook sum
bound forces b <= 9 bits per limb (32 * 2^(2b) < 2^24). A uint32
accumulator lifts the bound to 2^32, admitting 12-bit limbs:

    22 limbs x 12 bits = 264 >= 255
    products <= 8200 * 12400 < 2^26.6;  22 terms < 2^31.1 < 2^32  (exact)

so a multiply is a 22x22 convolution — 484 limb products vs the f32
field's 1024 (2.1x fewer), with shorter carry chains (22 rows vs 32).

Whether this BEATS the f32 field on a real TPU depends on the VPU's
int32 multiply issue rate vs f32 fma (not public; not measured). This module exists to make
that decision a benchmark away: it implements the exact same contract as
`ops.field` for the core ops (mul/sqr/add/sub/carry/canonical) with
value-level tests against Python bigints (`tests/test_field12.py`). The
verify kernel stays on `ops.field` until the device measurement says
otherwise.

Reference hot path this would accelerate: crypto/src/lib.rs:194-220.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

P = 2**255 - 19
NLIMB = 22
BITS = 12
RADIX = 1 << BITS  # 4096
MASK = RADIX - 1
# 2^264 = 2^9 * 2^255 ≡ 2^9 * 19 (mod p)
FOLD = 19 << 9  # 9728

U32 = jnp.uint32


def limbs_of_int(x: int, n: int = NLIMB) -> np.ndarray:
    assert 0 <= x < (1 << (BITS * n))
    out = np.zeros((n, 1), np.uint32)
    for i in range(n):
        out[i, 0] = (x >> (BITS * i)) & MASK
    return out


def int_of_limbs(limbs) -> list[int]:
    arr = np.asarray(limbs, np.uint64)
    return [
        sum(int(arr[i, b]) << (BITS * i) for i in range(arr.shape[0]))
        for b in range(arr.shape[1])
    ]


def _make_bias(mult: int, lo: int) -> np.ndarray:
    """Limbs of mult*p with every limb in [lo, 2^17): per-limb lower bound
    lets `sub` stay nonnegative without borrows."""
    digits = [(mult * P >> (BITS * i)) & MASK for i in range(NLIMB)]
    digits[NLIMB - 1] += RADIX * (mult * P >> (BITS * NLIMB))
    for i in range(NLIMB - 1):
        while digits[i] < lo:
            digits[i] += RADIX
            digits[i + 1] -= 1
    assert digits[NLIMB - 1] >= lo and all(0 <= d < 2**17 for d in digits)
    assert sum(d << (BITS * i) for i, d in enumerate(digits)) == mult * P
    return np.array(digits, np.uint32).reshape(NLIMB, 1)


# sub inputs can carry one lazy add of two normalized elements; limb 0's
# normalized bound is FOLD-amplified (~14k, see carry()), so the per-limb
# floor is 8*RADIX = 32768 > 2*14k.
# mult 8192 keeps the TOP digit (~ mult * p / 2^252 ≈ 8 * mult) above the
# floor after the borrow cascade.
BIAS = _make_bias(8192, 8 * RADIX)
P_COMPLEMENT = limbs_of_int((1 << (BITS * NLIMB)) - P)  # 2^264 - p

ZERO = limbs_of_int(0)
ONE = limbs_of_int(1)


def _carry_pass(c: jnp.ndarray, wrap: bool) -> jnp.ndarray:
    hi = c >> BITS
    lo = c & MASK
    if wrap:
        head = lo[:1] + hi[-1:] * jnp.uint32(FOLD)
    else:
        head = lo[:1]
    return jnp.concatenate([head, lo[1:] + hi[:-1]], axis=0)


def carry(c: jnp.ndarray) -> jnp.ndarray:
    """Input limbs < 2^30.6 -> normalized limbs: <= ~4100 for rows 1..21
    and <= RADIX + FOLD + eps (~14k) for row 0 (the 2^264 ≡ 9728 wrap can
    keep re-feeding limb 0, which converges to 4095 + 9728; this limb-0
    amplification is accounted for in the mul/sub input bounds)."""
    for _ in range(3):
        c = _carry_pass(c, wrap=True)
    return c


def add(a, b):
    """Lazy addition (at most one before a mul/sub)."""
    return a + b


def sub(a, b):
    """a - b (mod p); normalized output. Input bound: at most ONE lazy
    add of normalized elements per operand (limb 0 <= ~28k, others <=
    ~8.2k — the BIAS per-limb floor of 8*RADIX = 32768 must exceed every
    subtrahend limb or the uint32 difference wraps silently)."""
    return carry(a + jnp.asarray(BIAS) - b)


def _reduce(c46: jnp.ndarray) -> jnp.ndarray:
    """(46, B) raw product rows -> normalized 22-limb element.

    Carry the raw rows down (no wrap; rows 43-45 are headroom), fold rows
    44-45 (sig 2^528+) into rows 22-23 via 2^264 ≡ FOLD first (their
    values are tiny, so FOLD * row stays small), then fold rows 22-43
    into 0-21 with one more FOLD multiply (<= 4100 + FOLD * ~160k < 2^31,
    uint32-exact) and normalize."""
    for _ in range(3):
        c46 = _carry_pass(c46, wrap=False)
    tail = c46[2 * NLIMB :]  # rows 44-45, <= ~16 after carries
    mid = c46[NLIMB : 2 * NLIMB]
    mid = mid.at[0 : tail.shape[0]].add(jnp.uint32(FOLD) * tail)
    folded = c46[:NLIMB] + jnp.uint32(FOLD) * mid
    return carry(folded)


def mul(a, b):
    """Field multiplication; inputs' limbs <= ~12400 x ~8200 (normalized
    or one lazy add); exact in uint32 (sum < 2^31.1)."""
    batch = jnp.broadcast_shapes(a.shape[1:], b.shape[1:])
    c = jnp.zeros((2 * NLIMB + 2,) + batch, U32)
    for i in range(NLIMB):
        c = c.at[i : i + NLIMB].add(a[i] * b)
    return _reduce(c)


def sqr(a):
    batch = a.shape[1:]
    a2 = a + a
    c = jnp.zeros((2 * NLIMB + 2,) + batch, U32)
    for i in range(NLIMB):
        c = c.at[2 * i].add(a[i] * a[i])
        if i + 1 < NLIMB:
            c = c.at[2 * i + 1 : i + NLIMB].add(a2[i] * a[i + 1 :])
    return _reduce(c)


def sqr_n(a, n: int):
    return lax.fori_loop(0, n, lambda _, x: sqr(x), a)


def select(mask, a, b):
    return jnp.where(mask[None, :], a, b)


def _seq_carry(c: jnp.ndarray):
    def body(i, state):
        limbs, cin = state
        t = lax.dynamic_index_in_dim(limbs, i, axis=0, keepdims=False) + cin
        hi = t >> BITS
        lo = t & MASK
        return lax.dynamic_update_index_in_dim(limbs, lo, i, axis=0), hi

    carry0 = jnp.zeros(c.shape[1:], c.dtype)
    return lax.fori_loop(0, NLIMB, body, (c, carry0))


def _cond_sub_p(x):
    t = x + jnp.asarray(P_COMPLEMENT)
    t, cout = _seq_carry(t)
    return select(cout >= 1, t, x)


def canonical(x):
    """Normalized element -> THE canonical representative in [0, p).

    Unlike the radix-256 field (value < 2^256 < 3p, two conditional
    subtractions suffice), a 22x12-bit element spans 264 bits — up to
    ~512p — so the bits above 2^255 must fold down first: 2^255 ≡ 19,
    and bit 255 sits at bit 3 of limb 21. Two fold+carry passes bring
    the value below p + 38, then two conditional subtractions finish."""
    x, cout = _seq_carry(x)
    x = x.at[0].add(cout * jnp.uint32(FOLD))
    x, cout = _seq_carry(x)
    x = x.at[0].add(cout * jnp.uint32(FOLD))
    x, _ = _seq_carry(x)  # limbs < 4096, value < 2^264
    for _ in range(2):
        q = x[NLIMB - 1] >> 3  # value >> 255, <= 2^9 after the seq carry
        x = x.at[NLIMB - 1].set(x[NLIMB - 1] & jnp.uint32(7))
        x = x.at[0].add(q * jnp.uint32(19))
        x, _ = _seq_carry(x)
    x = _cond_sub_p(x)
    x = _cond_sub_p(x)
    return x


def eq_canonical(a, b):
    return jnp.all(a == b, axis=0)
