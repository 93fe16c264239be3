"""GF(2^255 - 19) arithmetic on TPU, batched over the lane dimension.

This is the bignum substrate for the TPU ed25519 batch-verification kernel
(the north-star offload of the reference's `Signature::verify_batch` /
`verify_batch_alt` hot path, crypto/src/lib.rs:194-220).

Design (TPU-first, not a port):
  * A field element batch is a `(32, B)` float32 array: 32 radix-256 limbs on
    the sublane axis, the batch on the lane axis (full 128-lane utilisation
    for B >= 128, tiled for larger B).
  * float32, not int32: every intermediate value is kept strictly below 2^24,
    where f32 arithmetic on integers is EXACT, and f32 multiply-add is the
    TPU VPU's fast path (TPU int32 multiplies lower to multi-op sequences).
    The radix/bound discipline below guarantees exactness:
      - "normalized" elements have limbs <= 294            (_carry32 output)
      - `add` is lazy (no carry): inputs <= 294 -> output <= 588
      - `mul` accepts limbs <= 700:  conv sum <= 32*700^2 = 15.7M < 2^24
      - `sub(a, b)` = a + BIAS16P - b with BIAS16P = 16p arranged so every
        limb >= 768 >= any subtrahend limb (<= 588); result is re-normalized
  * Multiplication is a 32-tap shifted multiply-accumulate (schoolbook
    convolution) over `(64, B)` vectors — static-slice updates that XLA fuses
    into VPU FMA chains; reduction folds limbs >= 32 via 2^256 = 38 (mod p).
  * No data-dependent control flow: carry chains are fixed-depth vectorized
    passes; the only sequential carries (exact canonicalisation) are
    `lax.fori_loop`s with O(32) trip counts, used once per verify.
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

P = 2**255 - 19
NLIMB = 32
RADIX = 256

# ---------------------------------------------------------------------------
# Host-side constant construction (Python ints -> limb arrays)
# ---------------------------------------------------------------------------


def limbs_of_int(x: int, n: int = NLIMB) -> np.ndarray:
    """Little-endian radix-256 limbs of a nonnegative int as (n, 1) f32."""
    assert 0 <= x < RADIX**n
    out = np.zeros((n, 1), np.float32)
    for i in range(n):
        out[i, 0] = (x >> (8 * i)) & 0xFF
    return out


def int_of_limbs(limbs: np.ndarray) -> list[int]:
    """Exact big-int value per batch column (for tests / host checks)."""
    arr = np.asarray(limbs, np.float64)
    return [
        sum(int(arr[i, b]) << (8 * i) for i in range(arr.shape[0]))
        for b in range(arr.shape[1])
    ]


def _make_bias(mult: int, lo: int) -> np.ndarray:
    """Limbs of mult*p with every limb in [lo, 2^13): per-limb lower bound
    lets `sub` stay nonnegative without borrows."""
    digits = [(mult * P >> (8 * i)) & 0xFF for i in range(NLIMB)]
    digits[NLIMB - 1] += 256 * (mult * P >> (8 * NLIMB))  # fold the overflow
    for i in range(NLIMB - 1):
        while digits[i] < lo:
            digits[i] += 256
            digits[i + 1] -= 1
    assert digits[NLIMB - 1] >= lo and all(0 <= d < 2**13 for d in digits)
    assert sum(d << (8 * i) for i, d in enumerate(digits)) == mult * P
    return np.array(digits, np.float32).reshape(NLIMB, 1)


BIAS16P = _make_bias(16, 768)  # per-limb >= 768 > 588 = max lazy-add limb
# In-trace construction of the bias (mostly-uniform limbs + a few specials
# via iota selects): Pallas kernels cannot capture array constants, and XLA
# constant-folds this outside Pallas, so both paths share one definition.
_BIAS_MID = float(np.bincount(BIAS16P[:, 0].astype(np.int64)).argmax())
_BIAS_SPECIAL = tuple(
    (i, float(BIAS16P[i, 0]))
    for i in range(NLIMB)
    if BIAS16P[i, 0] != _BIAS_MID
)


def bias_limbs() -> jnp.ndarray:
    """(NLIMB, 1) f32 limbs of 16p, built from scalars (Pallas-safe)."""
    i = lax.broadcasted_iota(jnp.int32, (NLIMB, 1), 0)
    out = jnp.full((NLIMB, 1), _BIAS_MID, jnp.float32)
    for idx, v in _BIAS_SPECIAL:
        out = jnp.where(i == idx, jnp.float32(v), out)
    return out
# 2^256 - p = 2^255 + 19: adding this and checking carry-out of limb 31
# implements the `x >= p` comparison used by canonical reduction.
P_COMPLEMENT = limbs_of_int(2**256 - P)

ZERO = limbs_of_int(0)
ONE = limbs_of_int(1)

# ---------------------------------------------------------------------------
# Carry propagation
# ---------------------------------------------------------------------------


def _carry_pass(c: jnp.ndarray, wrap: bool) -> jnp.ndarray:
    """One vectorized carry pass. If `wrap`, the top-limb carry folds into
    limb 0 via 2^(8*32) = 2^256 = 38 (mod p); else it adds into the next
    (existing) limb row — callers provide headroom rows."""
    hi = jnp.floor(c * (1.0 / RADIX))
    lo = c - hi * RADIX
    if wrap:
        head = lo[:1] + hi[-1:] * 38.0
    else:
        head = lo[:1]
    return jnp.concatenate([head, lo[1:] + hi[:-1]], axis=0)


def _carry32(c: jnp.ndarray) -> jnp.ndarray:
    """Three wrap passes: any input < 2^24 per limb -> limbs <= 294."""
    for _ in range(3):
        c = _carry_pass(c, wrap=True)
    return c


# ---------------------------------------------------------------------------
# Core ops (all shapes (32, B) f32 unless noted)
# ---------------------------------------------------------------------------


def add(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Lazy addition. At most one before a mul/sub (bound: 294+294=588)."""
    return a + b


def sub(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """a - b (mod p), inputs' limbs <= 588; normalized output (<= 294)."""
    return _carry32(a + bias_limbs() - b)


# Mosaic (Pallas TPU) cannot lower scatter-add, so kernels switch the
# convolution to explicit per-row sums at trace time via this flag. The
# scatter form traces smaller/faster for the plain-XLA path.
# Per THREAD: programs are traced concurrently (the batch service dispatches
# from several threads; chip_smoke.py compiles programs side by side), and a
# process-global flag leaked both ways — a Pallas body lost the flag when
# another thread's context exited (Mosaic then refuses `scatter-add`), and a
# plain-XLA program traced meanwhile silently took the larger per-row form.
_TRACE = threading.local()


def _mosaic_safe_on() -> bool:
    return getattr(_TRACE, "mosaic_safe", False)


@contextlib.contextmanager
def mosaic_safe():
    """Trace field ops without scatter/dynamic-update (for Pallas bodies)."""
    prev, _TRACE.mosaic_safe = _mosaic_safe_on(), True
    try:
        yield
    finally:
        _TRACE.mosaic_safe = prev


def _conv_scatter(a, b, batch):
    c = jnp.zeros((2 * NLIMB + 2,) + batch, jnp.float32)
    for i in range(NLIMB):
        c = c.at[i : i + NLIMB].add(a[i] * b)
    return c


def _conv_rows(a, b, batch):
    rows = []
    for k in range(2 * NLIMB - 1):
        lo, hi = max(0, k - NLIMB + 1), min(k, NLIMB - 1)
        term = a[lo] * b[k - lo]
        for i in range(lo + 1, hi + 1):
            term = term + a[i] * b[k - i]
        rows.append(jnp.broadcast_to(term, batch)[None])
    rows.append(jnp.zeros((3,) + batch, jnp.float32))  # carry headroom
    return jnp.concatenate(rows, axis=0)


def _conv_shift(a, b, batch):
    """Scatter-free conv on full (66, B) tiles: tree-sum of zero-padded
    shifted products. Same FLOPs as _conv_rows but each op covers whole
    (sublane, lane) tiles instead of single (B,) rows — better VPU issue
    efficiency inside Mosaic kernels."""
    parts = []
    for i in range(NLIMB):
        prod = jnp.broadcast_to(a[i] * b, (NLIMB,) + batch)
        parts.append(
            jnp.pad(prod, ((i, NLIMB + 2 - i), (0, 0)))
        )
    while len(parts) > 1:  # balanced tree keeps live values narrow
        parts = [
            parts[j] + parts[j + 1] if j + 1 < len(parts) else parts[j]
            for j in range(0, len(parts), 2)
        ]
    return parts[0]


def _reduce_512(c: jnp.ndarray) -> jnp.ndarray:
    """(66, B) raw product -> normalized 32-limb element."""
    # carry the product down to <=256/limb (no wrap: rows 63..65 give the
    # carries headroom and nothing overflows out of row 65), then fold
    # rows 32..63 via 2^256 = 38 and rows 64..65 via 2^512 = 1444 (mod p).
    for _ in range(3):
        c = _carry_pass(c, wrap=False)
    folded = c[:NLIMB] + 38.0 * c[NLIMB : 2 * NLIMB]
    extra = jnp.concatenate(
        [
            1444.0 * c[2 * NLIMB : 2 * NLIMB + 2],
            jnp.zeros_like(folded[2:]),
        ],
        axis=0,
    )
    return _carry32(folded + extra)


MOSAIC_CONV = "shift"  # "rows" | "shift" — conv flavour inside Pallas


def mul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Field multiplication; normalized output (limbs <= ~295).

    Input bound: max_limb(a) * max_limb(b) <= 2^19 (so each of the <=32
    convolution terms is < 2^19 and their sum < 2^24 stays f32-exact);
    normalized (<=295) and single-lazy-add (<=590) operands, and the
    madd pattern (<=590 x <=885), all satisfy this.

    The product of two lazily-reduced 256-bit-plus values can slightly
    exceed 2^512, so the convolution gets 66 rows (see _reduce_512).
    """
    batch = jnp.broadcast_shapes(a.shape[1:], b.shape[1:])
    if _mosaic_safe_on():
        conv = _conv_shift if MOSAIC_CONV == "shift" else _conv_rows
    else:
        conv = _conv_scatter
    return _reduce_512(conv(a, b, batch))


def sqr(a: jnp.ndarray) -> jnp.ndarray:
    """Squaring: symmetric convolution, ~55% of mul's multiplies
    (c_k = a_i^2 [i+i=k] + 2*a_i*a_j [i<j, i+j=k]); same bounds as mul."""
    batch = a.shape[1:]
    a2 = a + a
    if _mosaic_safe_on():
        # shift form: block i contributes [a_i^2, 2*a_i*a_{i+1..}] at
        # offset 2i; zero-padded full-tile adds (see _conv_shift)
        parts = []
        for i in range(NLIMB):
            sq = jnp.broadcast_to(a[i] * a[i], batch)[None]
            if i + 1 < NLIMB:
                cross = jnp.broadcast_to(
                    a2[i] * a[i + 1 :], (NLIMB - 1 - i,) + batch
                )
                block = jnp.concatenate([sq, cross], axis=0)
            else:
                block = sq
            top, rows = 2 * i, NLIMB - i
            parts.append(jnp.pad(block, ((top, 2 * NLIMB + 2 - top - rows), (0, 0))))
        while len(parts) > 1:
            parts = [
                parts[j] + parts[j + 1] if j + 1 < len(parts) else parts[j]
                for j in range(0, len(parts), 2)
            ]
        return _reduce_512(parts[0])
    c = jnp.zeros((2 * NLIMB + 2,) + batch, a.dtype)
    for i in range(NLIMB):
        c = c.at[2 * i].add(a[i] * a[i])
        if i + 1 < NLIMB:
            c = c.at[2 * i + 1 : i + NLIMB].add(a2[i] * a[i + 1 :])
    return _reduce_512(c)


def sqr_n(a: jnp.ndarray, n: int) -> jnp.ndarray:
    """n successive squarings via fori_loop (body traced once). Plain XLA
    keeps `mul(x, x)` (its scatter form; the w4 programs' HLO is pinned);
    a Pallas body takes the true squaring, 55% of the multiplies."""
    step = sqr if _mosaic_safe_on() else (lambda x: mul(x, x))
    return lax.fori_loop(0, n, lambda _, x: step(x), a)


def select(mask: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Per-item select: mask (B,) bool -> a where True else b."""
    return jnp.where(mask[None, :], a, b)


# ---------------------------------------------------------------------------
# Fixed-exponent chains (ref10 addition chains; fori_loop keeps HLO small)
# ---------------------------------------------------------------------------


def _chain_250(z: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (z^(2^250 - 1), z^11) — the shared prefix of invert/pow2523."""
    z2 = sqr(z)
    z8 = sqr_n(z2, 2)
    z9 = mul(z, z8)
    z11 = mul(z2, z9)
    z22 = sqr(z11)
    z_5_0 = mul(z9, z22)  # 2^5 - 1
    z_10_0 = mul(sqr_n(z_5_0, 5), z_5_0)  # 2^10 - 1
    z_20_0 = mul(sqr_n(z_10_0, 10), z_10_0)  # 2^20 - 1
    z_40_0 = mul(sqr_n(z_20_0, 20), z_20_0)  # 2^40 - 1
    z_50_0 = mul(sqr_n(z_40_0, 10), z_10_0)  # 2^50 - 1
    z_100_0 = mul(sqr_n(z_50_0, 50), z_50_0)  # 2^100 - 1
    z_200_0 = mul(sqr_n(z_100_0, 100), z_100_0)  # 2^200 - 1
    z_250_0 = mul(sqr_n(z_200_0, 50), z_50_0)  # 2^250 - 1
    return z_250_0, z11


def invert(z: jnp.ndarray) -> jnp.ndarray:
    """z^(p-2) = z^(2^255 - 21): multiplicative inverse (0 -> 0)."""
    z_250_0, z11 = _chain_250(z)
    return mul(sqr_n(z_250_0, 5), z11)


def pow2523(z: jnp.ndarray) -> jnp.ndarray:
    """z^((p-5)/8) = z^(2^252 - 3): the square-root exponent."""
    z_250_0, _ = _chain_250(z)
    return mul(sqr_n(z_250_0, 2), z)


# ---------------------------------------------------------------------------
# Exact canonicalisation (value mod p, limbs in [0, 255])
# ---------------------------------------------------------------------------


def _seq_carry(c: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact sequential carry over 32 limbs; returns (limbs in [0,256),
    carry_out (B,)). fori_loop, 32 iterations."""

    def body(i, state):
        limbs, carry = state
        t = lax.dynamic_index_in_dim(limbs, i, axis=0, keepdims=False) + carry
        hi = jnp.floor(t * (1.0 / RADIX))
        lo = t - hi * RADIX
        limbs = lax.dynamic_update_index_in_dim(limbs, lo, i, axis=0)
        return limbs, hi

    carry0 = jnp.zeros(c.shape[1:], c.dtype)
    return lax.fori_loop(0, NLIMB, body, (c, carry0))


def _cond_sub_p(x: jnp.ndarray) -> jnp.ndarray:
    """One conditional subtraction of p (x < 2^256, limbs canonical)."""
    t = x + P_COMPLEMENT  # x + (2^256 - p)
    t, carry = _seq_carry(t)
    ge_p = carry >= 1.0  # carry out of 2^256 <=> x >= p
    return select(ge_p, t, x)


def canonical(x: jnp.ndarray) -> jnp.ndarray:
    """Reduce a normalized (limbs <= ~600) element to THE canonical
    representative: limbs in [0, 255], value in [0, p)."""
    x, carry = _seq_carry(x)
    x = x.at[0].add(carry * 38.0)  # fold 2^256 overflow
    x, carry = _seq_carry(x)
    x = x.at[0].add(carry * 38.0)  # second fold can leave limb 0 in [256,293]
    x, _ = _seq_carry(x)  # value < 2^256 here, so the carry-out is 0
    x = _cond_sub_p(x)
    x = _cond_sub_p(x)
    return x


def eq_canonical(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(B,) bool equality of two canonical elements."""
    return jnp.all(a == b, axis=0)


def parity(x_canonical: jnp.ndarray) -> jnp.ndarray:
    """(B,) f32 in {0,1}: low bit of the canonical value (sign of x)."""
    return x_canonical[0] - 2.0 * jnp.floor(x_canonical[0] * 0.5)
