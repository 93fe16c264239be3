"""Batched ed25519 verification on TPU — the north-star kernel.

Replaces the reference's CPU `ed25519_dalek` batch paths
(`Signature::verify_batch` crypto/src/lib.rs:194-207, used by `QC::verify`
consensus/src/messages.rs:197; `verify_batch_alt` crypto/src/lib.rs:209-220,
the mempool workload mempool/src/core.rs:135-148) with a single jitted
SPMD kernel over the batch:

    for each item i:  valid_i  <=>  enc([s_i]B - [h_i]A_i) == R_i
    with h_i = SHA-512(R_i || A_i || M_i) mod L

which is the strict (cofactorless) verification equation — per-item masks
come for free, strictly stronger than the reference's all-or-nothing batch.

TPU mapping:
  * All field math is `ops.field` (32, B)-limb f32 vectors: batch on lanes.
  * The double-scalar multiply is a shared-doubling (Straus) ladder with
    4-bit windows: 64 groups of [4 doublings; add of a multiple of the
    constant base point B; add of a multiple of the per-item -A_i] under
    `lax.fori_loop` — fixed trip count, no data-dependent control flow,
    table lookups by one-hot instead of branches (SIMD over the batch).
  * Point decompression (sqrt via x^((p-5)/8)) and final compression
    (inverse via x^(p-2)) run on-device with ref10 addition chains.
  * SHA-512 and the mod-L scalar reductions are host-side (cheap, byte-
    oriented; the EC math is >99% of the work and all on TPU).

Curve ops use the extended-coordinate formulas for a = -1 twisted Edwards
(dbl-2008-hwcd / madd-2008-hwcd-3): unified mixed addition handles identity
and doubling inputs, so the ladder needs no special cases.
"""

from __future__ import annotations

import collections
import hashlib
import logging
import threading
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import field as f
from . import timeline
from .pipeline import ChunkTask, DispatchPipeline
from ..utils import metrics

log = logging.getLogger("hotstuff.ops")

# Stage-tracing handles (names match tools/profile_e2e.py's phase rows; see
# the COMPONENTS.md metric table). `readback_s` times the single
# device->host mask fetch, which in the pipelined path also drains the
# device compute queue — profile_e2e.py separates compute from readback by
# probing phases in isolation, which an in-process span cannot.
_M_STAGE = metrics.histogram("verifier.stage_s")
_M_UPLOAD = metrics.histogram("verifier.upload_s")
_M_DISPATCH = metrics.histogram("verifier.dispatch_s")
_M_READBACK = metrics.histogram("verifier.readback_s")
_M_E2E = metrics.histogram("verifier.e2e_s")
# What the dispatch pipeline's `stage` and `readback` spans also feed
# (ChunkTask.hists). Under deferred readback a chunk's readback returns
# its handle at once: the one real fetch is timed in _materialize_deferred.
_CHUNK_HISTS = {"stage": _M_STAGE, "readback": _M_READBACK}
_DEFER_HISTS = {"stage": _M_STAGE}
_M_BATCH_SIZE = metrics.histogram("verifier.batch_size", metrics.SIZE_BUCKETS)
_M_SIGS = metrics.counter("verifier.sigs")
_M_BATCHES = metrics.counter("verifier.batches")
_M_CHUNKS = metrics.counter("verifier.chunks")
# Committee-residency accounting: the generic kernels re-decompress every
# lane's public key and rebuild its 16-entry -A window table per chunk
# (decompressions / table_builds); the committee path gathers precomputed
# tables by validator index and increments NEITHER — the acceptance check
# for steady-state zero-rebuild batches.
_M_DECOMPRESSIONS = metrics.counter("verifier.decompressions")
_M_TABLE_BUILDS = metrics.counter("verifier.table_builds")
# Lanes shipped only to fill a bucket (width - occupancy), summed per chunk.
# A mesh verifier's buckets are never narrower than lane * ndev, so small
# quorum batches inflate this counter — the visibility hook behind the
# mesh-aware committee_crossover (sub-alignment batches belong on host CPU).
_M_PAD_LANES = metrics.counter("verifier.pad_lanes")
_M_COMMITTEE_BATCHES = metrics.counter("verifier.committee_batches")
_M_COMMITTEE_SIGS = metrics.counter("verifier.committee_sigs")
_M_COMMITTEE_REGS = metrics.counter("verifier.committee_registrations")
_M_COMMITTEE_SIZE = metrics.gauge("verifier.committee_size")

P = f.P
L_ORDER = 2**252 + 27742317777372353535851937790883648493

# --- curve constants (host Python ints -> limb arrays) ---------------------
D_INT = (-121665 * pow(121666, P - 2, P)) % P
D2_INT = (2 * D_INT) % P
SQRTM1_INT = pow(2, (P - 1) // 4, P)

BY_INT = (4 * pow(5, P - 2, P)) % P
_u = (BY_INT * BY_INT - 1) % P
_v = (D_INT * BY_INT * BY_INT + 1) % P
_x2 = (_u * pow(_v, P - 2, P)) % P
BX_INT = pow(_x2, (P + 3) // 8, P)
if (BX_INT * BX_INT - _x2) % P != 0:
    BX_INT = (BX_INT * SQRTM1_INT) % P
if BX_INT % 2 != 0:
    BX_INT = P - BX_INT
assert (BX_INT * BX_INT - _x2) % P == 0

D = f.limbs_of_int(D_INT)
D2 = f.limbs_of_int(D2_INT)
SQRTM1 = f.limbs_of_int(SQRTM1_INT)
# Precomputed affine base point for mixed addition: (y+x, y-x, 2*d*x*y).
BASE_YPX = f.limbs_of_int((BY_INT + BX_INT) % P)
BASE_YMX = f.limbs_of_int((BY_INT - BX_INT) % P)
BASE_XY2D = f.limbs_of_int((D2_INT * BX_INT * BY_INT) % P)

Point = tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]  # X,Y,Z,T


def point_identity(batch: int, dtype=jnp.float32) -> Point:
    zero = jnp.zeros((f.NLIMB, batch), dtype)
    one = jnp.concatenate([jnp.ones((1, batch), dtype), zero[1:]], axis=0)
    return zero, one, one, zero


def point_dbl(p: Point, with_t: bool = True) -> Point:
    """dbl-2008-hwcd for a=-1 (complete for doubling, identity included).

    Doubling never READS the input T, so a doubling whose consumer is
    another doubling can skip producing it (`with_t=False`, one field mul
    saved — 3 of every 4 ladder doublings qualify, ~5% of kernel ops);
    the returned T is zeros then, and must not feed an addition."""
    X, Y, Z, _ = p
    xx = f.sqr(X)
    yy = f.sqr(Y)
    zz = f.sqr(Z)
    zz2 = f.add(zz, zz)
    aa = f.sqr(f.add(X, Y))
    yp = f.add(yy, xx)  # Y' = Y^2 - a*X^2 = Y^2 + X^2
    zp = f.sub(yy, xx)
    xp = f.sub(aa, yp)  # = 2XY
    tp = f.sub(zz2, zp)
    t_out = f.mul(xp, yp) if with_t else jnp.zeros_like(xp)
    return f.mul(xp, tp), f.mul(yp, zp), f.mul(zp, tp), t_out


def point_madd(p: Point, q_ypx, q_ymx, q_xy2d, with_t: bool = True) -> Point:
    """Unified mixed addition (madd-2008-hwcd-3): P + affine precomp Q.
    `with_t=False` skips producing T (valid when the consumer is a doubling
    or the final compress, neither of which reads it)."""
    X1, Y1, Z1, T1 = p
    a = f.mul(f.add(Y1, X1), q_ypx)
    b = f.mul(f.sub(Y1, X1), q_ymx)
    c = f.mul(T1, q_xy2d)
    d2z = f.add(Z1, Z1)
    x3 = f.sub(a, b)
    y3 = f.add(a, b)
    z3 = f.add(d2z, c)
    t3 = f.sub(d2z, c)
    t_out = f.mul(x3, y3) if with_t else jnp.zeros_like(x3)
    return f.mul(x3, t3), f.mul(y3, z3), f.mul(z3, t3), t_out


def point_add_cached(p: Point, q_ypx, q_ymx, q_z, q_t2d, with_t: bool = True) -> Point:
    """Unified addition with a cached point (Y2+X2, Y2-X2, Z2, 2d*T2)
    (add-2008-hwcd-3). Cached identity is (1, 1, 1, 0). `with_t=False`
    skips producing T (valid when the consumer is a doubling or the final
    compress, neither of which reads it)."""
    X1, Y1, Z1, T1 = p
    a = f.mul(f.add(Y1, X1), q_ypx)
    b = f.mul(f.sub(Y1, X1), q_ymx)
    c = f.mul(T1, q_t2d)
    zz = f.mul(Z1, q_z)
    d2z = f.add(zz, zz)
    x3 = f.sub(a, b)
    y3 = f.add(a, b)
    z3 = f.add(d2z, c)
    t3 = f.sub(d2z, c)
    t_out = f.mul(x3, y3) if with_t else jnp.zeros_like(x3)
    return f.mul(x3, t3), f.mul(y3, z3), f.mul(z3, t3), t_out


# --- 4-bit windowed ladder -------------------------------------------------
#
# Straus with 4-bit windows: 64 groups of [4 doublings; add T_B[digit_s];
# add T_A[digit_h]] where T_B is a shared 16-entry table of k*B (host
# precomputed, canonical) and T_A is a per-item 16-entry table of k*(-A)
# built on device. Entry 0 is the identity, absorbed by the unified
# addition formulas — zero digits cost nothing extra and need no selects.

WINDOW = 4
NGROUPS = 64  # ceil(256/4); scalars < 2^253 so top digits are small


def _edwards_add_int(p1, p2):
    """Exact affine Edwards addition over Python ints (host precompute)."""
    (x1, y1), (x2, y2) = p1, p2
    dxy = D_INT * x1 * x2 % P * y1 * y2 % P
    x3 = (x1 * y2 + x2 * y1) * pow(1 + dxy, P - 2, P) % P
    y3 = (y1 * y2 + x1 * x2) * pow(1 - dxy, P - 2, P) % P
    return x3, y3


def _base_table_np() -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(32, 16) f32 tables of k*B in precomp-affine form, k = 0..15."""
    pts = [(0, 1)]
    for _ in range(15):
        pts.append(_edwards_add_int(pts[-1], (BX_INT, BY_INT)))
    cols = lambda vals: np.concatenate(
        [f.limbs_of_int(v) for v in vals], axis=1
    )
    ypx = cols([(y + x) % P for x, y in pts])
    ymx = cols([(y - x) % P for x, y in pts])
    xy2d = cols([D2_INT * x * y % P for x, y in pts])
    return ypx, ymx, xy2d


BASE_TABLE = _base_table_np()


def _lookup_shared(table: np.ndarray, onehot: jnp.ndarray) -> jnp.ndarray:
    """(32,16) canonical table x (16,B) one-hot -> (32,B). bf16 MXU matmul:
    one-hot entries and canonical limbs (<=255) are bf16-exact, and exactly
    one product per output is nonzero, so the f32 accumulation is exact."""
    return jax.lax.dot(
        jnp.asarray(table, jnp.bfloat16),
        onehot.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )


def _lookup_per_item(table: jnp.ndarray, onehot: jnp.ndarray) -> jnp.ndarray:
    """(16,32,B) per-item table x (16,B) one-hot -> (32,B) (VPU masked sum).

    HIGHEST precision is load-bearing: per-item table limbs reach ~590
    (beyond bf16-exact integers), so a default-precision einsum lowered to
    bf16 MXU passes on real TPU would corrupt limbs and verification masks.
    """
    return jnp.einsum(
        "elb,eb->lb", table, onehot, precision=jax.lax.Precision.HIGHEST
    )


def _build_neg_a_table(x_neg, a_y):
    """16-entry cached table of k*(-A), stacked (16, 32, B) per component."""
    # k=0: identity (1,1,1,0); k=1: (-A) itself with Z=1, T=x*y
    na_ypx = f.add(a_y, x_neg)
    na_ymx = f.sub(a_y, x_neg)
    na_xy2d = f.mul(D2, f.mul(x_neg, a_y))
    batch = a_y.shape[1]
    pts = [point_identity(batch)]
    cur = (
        x_neg,
        a_y,
        jnp.broadcast_to(jnp.asarray(f.ONE), a_y.shape),
        f.mul(x_neg, a_y),
    )
    pts.append(cur)
    for _ in range(14):
        cur = point_madd(cur, na_ypx, na_ymx, na_xy2d)
        pts.append(cur)
    ypx = jnp.stack([f.add(p[1], p[0]) for p in pts])
    ymx = jnp.stack([f.sub(p[1], p[0]) for p in pts])
    z = jnp.stack([p[2] for p in pts])
    t2d = jnp.stack([f.mul(D2, p[3]) for p in pts])
    return ypx, ymx, z, t2d


def _verify_kernel_w4(a_y, a_sign, r_enc, s_digits, h_digits):
    """(32,B) a_y, (B,) a_sign, (32,B) r_enc, (64,B) s/h digits -> (B,) bool.

    Computes enc([s]B + [h](-A)) and compares to the signature's R bytes;
    byte equality against a canonical re-encoding also enforces canonical R
    (the reference's verify_strict semantics, crypto/src/lib.rs:186-192).
    Digits are f32 4-bit windows, most-significant window last (row 63)."""
    x_a, xneg_a, valid = decompress(a_y, a_sign)
    ta_ypx, ta_ymx, ta_z, ta_t2d = _build_neg_a_table(xneg_a, a_y)
    b_ypx, b_ymx, b_xy2d = BASE_TABLE

    batch = a_y.shape[1]

    def body(g, acc: Point) -> Point:
        row = NGROUPS - 1 - g
        # Only the LAST doubling needs T (the madd reads it); the group-
        # final cached add skips T too (its consumer is the next group's
        # doubling, or compress — neither reads T).
        for i in range(WINDOW):
            acc = point_dbl(acc, with_t=i == WINDOW - 1)
        sd = lax.dynamic_index_in_dim(s_digits, row, 0, keepdims=False)
        hd = lax.dynamic_index_in_dim(h_digits, row, 0, keepdims=False)
        s_oh = jax.nn.one_hot(sd.astype(jnp.int32), 16, axis=0, dtype=a_y.dtype)
        h_oh = jax.nn.one_hot(hd.astype(jnp.int32), 16, axis=0, dtype=a_y.dtype)
        acc = point_madd(
            acc,
            _lookup_shared(b_ypx, s_oh),
            _lookup_shared(b_ymx, s_oh),
            _lookup_shared(b_xy2d, s_oh),
        )
        acc = point_add_cached(
            acc,
            _lookup_per_item(ta_ypx, h_oh),
            _lookup_per_item(ta_ymx, h_oh),
            _lookup_per_item(ta_z, h_oh),
            _lookup_per_item(ta_t2d, h_oh),
            with_t=False,
        )
        return acc

    result = lax.fori_loop(0, NGROUPS, body, point_identity(batch))
    enc = compress(result)
    return valid & jnp.all(enc == r_enc, axis=0)


# --- committee-resident key precomputation --------------------------------
#
# The protocol's hot path verifies signatures from a FIXED set of <= ~100
# validator keys, yet the generic kernel re-decompresses each lane's key
# (sqrt addition chain, ~250 field ops) and rebuilds its 16-entry -A window
# table (14 cached adds) on device EVERY batch. A CommitteeTable pays that
# once per committee on the host with exact integer math and keeps the
# result device-resident; committee lanes then GATHER their table by
# validator index — zero per-batch decompressions or table builds, the
# per-verification amortization lever of "Performance of EdDSA and BLS
# Signatures in Committee-Based Consensus" (PAPERS.md).
#
# Host precompute yields AFFINE table entries (canonical limbs <= 255), so
# the per-item adds become mixed additions (madd-2008-hwcd-3) — one field
# mul per add cheaper than the generic path's cached adds, on top of the
# skipped decompress/build.


def _decompress_int(key: bytes) -> tuple[int, int] | None:
    """Exact host decompression of a 32-byte compressed point.

    Matches the device `decompress` semantics bit for bit: y is reduced
    mod p (non-canonical encodings are NOT rejected, mirroring the field-
    element decode of the device limbs and of ed25519_dalek), x = 0 absorbs
    either sign, and None is returned only when no square root exists."""
    enc = int.from_bytes(key, "little")
    sign = enc >> 255
    y = (enc & ((1 << 255) - 1)) % P
    u = (y * y - 1) % P
    v = (D_INT * y * y + 1) % P
    x2 = u * pow(v, P - 2, P) % P
    x = pow(x2, (P + 3) // 8, P)
    if (x * x - x2) % P != 0:
        x = x * SQRTM1_INT % P
    if (x * x - x2) % P != 0:
        return None
    if x % 2 != sign:
        x = (P - x) % P
    return x, y


class CommitteeTable:
    """Device-resident per-validator -A window tables, built once per
    committee.

    Layout (N = committee size):
      ta_ypx / ta_ymx / ta_xy2d : (16, 32, N) f32 — affine precomp of
          k*(-A_i) for k = 0..15 (row 0 is the madd identity (1, 1, 0))
      valid   : (N,) bool — False for keys with no valid decompression
          (their lanes always fail, matching the generic kernel)
      keys_u8 : (32, N) u8 — raw key bytes, gathered on device by the
          device-hash kernel for h = SHA-512(R||A||M)

    `index` maps raw 32-byte key -> validator index for host-side routing.

    `put` overrides device placement of the finished arrays: the mesh
    verifier passes a replicated `NamedSharding` transfer so every chip in
    the mesh holds its own copy of the tables (built once, at registration
    — the sharded kernels take them as replicated shard_map operands).
    """

    def __init__(self, keys: Sequence[bytes], put=None) -> None:
        import jax as _jax

        if put is None:
            put = _jax.device_put
        keys = [bytes(k) for k in keys]
        if not keys:
            raise ValueError("committee must have at least one key")
        self.keys = keys
        self.index: dict[bytes, int] = {}
        for i, k in enumerate(keys):
            self.index.setdefault(k, i)
        n = len(keys)
        ypx = np.zeros((16, f.NLIMB, n), np.float32)
        ymx = np.zeros_like(ypx)
        xy2d = np.zeros_like(ypx)
        valid = np.zeros(n, bool)
        keys_u8 = np.zeros((32, n), np.uint8)
        for i, kb in enumerate(keys):
            keys_u8[:, i] = np.frombuffer(kb, np.uint8)
            ypx[0, 0, i] = 1.0  # madd identity: (ypx, ymx, xy2d) = (1, 1, 0)
            ymx[0, 0, i] = 1.0
            pt = _decompress_int(kb)
            if pt is None:
                continue
            valid[i] = True
            x, y = pt
            neg = ((P - x) % P, y)
            cur = (0, 1)
            for k in range(1, 16):
                cur = _edwards_add_int(cur, neg)
                cx, cy = cur
                ypx[k, :, i] = f.limbs_of_int((cy + cx) % P)[:, 0]
                ymx[k, :, i] = f.limbs_of_int((cy - cx) % P)[:, 0]
                xy2d[k, :, i] = f.limbs_of_int(D2_INT * cx * cy % P)[:, 0]
        self.ta_ypx = put(ypx)
        self.ta_ymx = put(ymx)
        self.ta_xy2d = put(xy2d)
        self.valid = put(valid)
        self.keys_u8 = put(keys_u8)
        self.size = n


def _verify_kernel_w4_committee(
    ta_ypx, ta_ymx, ta_xy2d, valid, idx, r_enc, s_digits, h_digits
):
    """Committee variant of `_verify_kernel_w4`: lanes gather their -A
    window table from the device-resident committee precompute by validator
    index — no decompression, no `_build_neg_a_table`. Affine tables make
    the per-item adds mixed additions."""
    g_ypx = jnp.take(ta_ypx, idx, axis=2)
    g_ymx = jnp.take(ta_ymx, idx, axis=2)
    g_xy2d = jnp.take(ta_xy2d, idx, axis=2)
    b_ypx, b_ymx, b_xy2d = BASE_TABLE
    batch = idx.shape[0]
    dtype = r_enc.dtype

    def body(g, acc: Point) -> Point:
        row = NGROUPS - 1 - g
        for i in range(WINDOW):
            acc = point_dbl(acc, with_t=i == WINDOW - 1)
        sd = lax.dynamic_index_in_dim(s_digits, row, 0, keepdims=False)
        hd = lax.dynamic_index_in_dim(h_digits, row, 0, keepdims=False)
        s_oh = jax.nn.one_hot(sd.astype(jnp.int32), 16, axis=0, dtype=dtype)
        h_oh = jax.nn.one_hot(hd.astype(jnp.int32), 16, axis=0, dtype=dtype)
        acc = point_madd(
            acc,
            _lookup_shared(b_ypx, s_oh),
            _lookup_shared(b_ymx, s_oh),
            _lookup_shared(b_xy2d, s_oh),
        )
        acc = point_madd(
            acc,
            _lookup_per_item(g_ypx, h_oh),
            _lookup_per_item(g_ymx, h_oh),
            _lookup_per_item(g_xy2d, h_oh),
            with_t=False,
        )
        return acc

    result = lax.fori_loop(0, NGROUPS, body, point_identity(batch))
    enc = compress(result)
    return jnp.take(valid, idx) & jnp.all(enc == r_enc, axis=0)


def _verify_kernel_w4_committee_packed96(
    ta_ypx, ta_ymx, ta_xy2d, valid, idx, packed
):
    """(96, B) u8 wire rows (R, S, host-computed h) + (B,) i32 indices."""
    r_b, s_b, h_b = packed[0:32], packed[32:64], packed[64:96]
    return _verify_kernel_w4_committee(
        ta_ypx,
        ta_ymx,
        ta_xy2d,
        valid,
        idx,
        r_b.astype(jnp.float32),
        _device_nibbles(s_b),
        _device_nibbles(h_b),
    )


def _verify_kernel_w4_committee_packed96_dh(
    ta_ypx, ta_ymx, ta_xy2d, valid, keys_u8, idx, packed
):
    """Device-hash committee variant: rows 64-95 carry the 32-byte MESSAGE;
    the key bytes for h = SHA-512(R||A||M) are gathered on device from the
    committee-resident `keys_u8`, so the host ships neither keys nor h."""
    from . import sha512

    r_b, s_b, m_b = packed[0:32], packed[32:64], packed[64:96]
    a_b = jnp.take(keys_u8, idx, axis=1)
    return _verify_kernel_w4_committee(
        ta_ypx,
        ta_ymx,
        ta_xy2d,
        valid,
        idx,
        r_b.astype(jnp.float32),
        _device_nibbles(s_b),
        sha512.h_digits_on_device(r_b, a_b, m_b),
    )


# --- packed (u8) wire format ----------------------------------------------
#
# The f32 kernel arguments are 772 B/signature (a_y, r_enc 128 B each;
# s/h_digits 256 B each) — 6.3 MB at batch 8192 of host->device transfer
# per dispatch. The packed path ships the raw 32-byte u8 rows (a, R, s, h =
# 128 B/signature, a 6x reduction) and unpacks to limbs/digits on device (a
# handful of VPU byte ops, free next to the 253-step ladder).


def _device_nibbles(b: jnp.ndarray) -> jnp.ndarray:
    """(32, B) u8 -> (64, B) f32 of 4-bit little-endian digits (row 2k = low
    nibble of byte k), matching the host-side `_nibbles` layout."""
    lo = (b & 0x0F).astype(jnp.float32)
    hi = (b >> 4).astype(jnp.float32)
    return jnp.stack((lo, hi), axis=1).reshape(2 * b.shape[0], b.shape[1])


def _unpack_ars(a_bytes, r_bytes, s_bytes):
    """u8 (32, B) A/R/S wire rows -> (a_y, a_sign, r_enc, s_digits)."""
    top = a_bytes[31]
    a_y = a_bytes.astype(jnp.float32).at[31].set(
        (top & 0x7F).astype(jnp.float32)
    )
    a_sign = (top >> 7).astype(jnp.float32)
    r_enc = r_bytes.astype(jnp.float32)
    return a_y, a_sign, r_enc, _device_nibbles(s_bytes)


def unpack_packed_inputs(a_bytes, r_bytes, s_bytes, h_bytes):
    """u8 (32, B) wire arrays -> the standard f32 kernel arguments."""
    return *_unpack_ars(a_bytes, r_bytes, s_bytes), _device_nibbles(h_bytes)


def unpack_packed_inputs_dh(packed):
    """(128, B) device-hash wire array (rows 96-127 = 32-byte message) ->
    the standard f32 kernel arguments, with h = SHA-512(R||A||M) mod L
    computed on device (ops.sha512)."""
    from . import sha512

    a_b, r_b, s_b, m_b = split_packed128(packed)
    return *_unpack_ars(a_b, r_b, s_b), sha512.h_digits_on_device(
        r_b, a_b, m_b
    )


def split_packed128(packed: jnp.ndarray) -> tuple:
    """(128, B) u8 wire array -> (a, r, s, h) (32, B) row groups."""
    return packed[0:32], packed[32:64], packed[64:96], packed[96:128]


def _verify_kernel_w4_packed128(packed):
    return _verify_kernel_w4(*unpack_packed_inputs(*split_packed128(packed)))


def _verify_kernel_w4_packed128_dh(packed):
    """Device-hash variant: rows 96-127 carry the 32-byte MESSAGE instead
    of a host-computed h; the device computes h = SHA-512(R||A||M) mod L
    itself (ops.sha512), so host staging is reduced to byte concatenation.
    Only valid for 32-byte messages — the protocol's hot path (votes, QCs
    and payloads all sign digests; messages.py `Vote.digest`)."""
    return _verify_kernel_w4(*unpack_packed_inputs_dh(packed))


def decompress(y_limbs: jnp.ndarray, sign: jnp.ndarray, pow2523=f.pow2523):
    """Compressed y (+ sign of x) -> affine (x, -x, y) + validity mask.

    Follows the ref10 recipe: x = u*v^3 * (u*v^7)^((p-5)/8) with
    u = y^2-1, v = d*y^2+1; multiply by sqrt(-1) when v*x^2 == -u; invalid
    when v*x^2 != +-u (no square root exists). Returns canonical x and p-x
    so the caller can pick either A or -A cheaply. `pow2523` is the
    square-root exponentiation: the jnp chain, or the Pallas program's own.
    """
    yy = f.sqr(y_limbs)
    u = f.sub(yy, f.ONE)
    v = f.add(f.mul(D, yy), f.ONE)
    v3 = f.mul(f.sqr(v), v)
    v7 = f.mul(f.sqr(v3), v)
    w = pow2523(f.mul(u, v7))
    r = f.mul(f.mul(u, v3), w)
    chk = f.canonical(f.mul(v, f.sqr(r)))
    u_c = f.canonical(u)
    negu_c = f.canonical(f.sub(f.ZERO, u))
    is_pos = f.eq_canonical(chk, u_c)
    is_neg = f.eq_canonical(chk, negu_c) & ~is_pos
    valid = is_pos | is_neg
    x = f.select(is_neg, f.mul(r, SQRTM1), r)
    x_c = f.canonical(x)
    xneg_c = f.canonical(f.sub(f.ZERO, x_c))
    flip = f.parity(x_c) != sign
    x_final = f.select(flip, xneg_c, x_c)
    xneg_final = f.select(flip, x_c, xneg_c)
    return x_final, xneg_final, valid


def compress(p: Point, invert=f.invert) -> jnp.ndarray:
    """Point -> canonical 32-limb encoding (y with sign bit of x in bit 255).
    `invert` as `decompress`'s `pow2523`."""
    zinv = invert(p[2])
    x_c = f.canonical(f.mul(p[0], zinv))
    y_c = f.canonical(f.mul(p[1], zinv))
    return y_c.at[f.NLIMB - 1].add(128.0 * f.parity(x_c))


# The jnp verify programs, under the names `Ed25519TpuVerifier.program_name`
# returns: what a process runs where it was told to use the CPU, and the
# committee family on every platform (ops/pallas_ladder.py holds the chip's
# two). KERNELS are the traceable functions (the mesh verifier wraps them in
# shard_map), PROGRAMS their jitted forms, one per process.
KERNELS = {
    "w4p128": _verify_kernel_w4_packed128,
    "w4p128dh": _verify_kernel_w4_packed128_dh,
    "w4c96": _verify_kernel_w4_committee_packed96,
    "w4c96dh": _verify_kernel_w4_committee_packed96_dh,
}
PROGRAMS = {name: jax.jit(fn) for name, fn in KERNELS.items()}
# The f32-argument reference the tests and __graft_entry__ compare against;
# no verifier dispatches it.
_verify_w4_jit = jax.jit(_verify_kernel_w4)


# ---------------------------------------------------------------------------
# Host glue: bytes -> limb/bit arrays, hashing, mod-L reduction, bucketing
# ---------------------------------------------------------------------------


def prepare_batch(
    messages: Sequence[bytes],
    keys: Sequence[bytes],
    signatures: Sequence[bytes],
    allow_native: bool = True,
) -> dict:
    """numpy staging of a batch. keys: 32-byte pks; signatures: 64 bytes.

    Dispatches to the C++ staging plane (crypto/native_staging) when built —
    the Python path below is the reference implementation and fallback.
    """
    if allow_native:
        from ..crypto import native_staging

        staged = native_staging.stage_batch(messages, keys, signatures)
        if staged is not None:
            return staged
    n = len(messages)
    a = np.frombuffer(b"".join(keys), np.uint8).reshape(n, 32)
    sig = np.frombuffer(b"".join(signatures), np.uint8).reshape(n, 64)
    r, s = sig[:, :32], sig[:, 32:]

    a_y = a.astype(np.float32).T.copy()
    a_y[31] = (a[:, 31] & 0x7F).astype(np.float32)
    a_sign = (a[:, 31] >> 7).astype(np.float32)
    r_enc = r.astype(np.float32).T.copy()

    s_ok, h_bytes = _stage_scalars(messages, a, r, s)

    return dict(
        a_y=a_y,
        a_sign=a_sign,
        r_enc=r_enc,
        s_digits=_nibbles(s),
        h_digits=_nibbles(h_bytes),
        s_ok=s_ok,
    )


def prepare_batch_packed(
    messages: Sequence[bytes],
    keys: Sequence[bytes],
    signatures: Sequence[bytes],
    allow_native: bool = True,
) -> dict:
    """Packed (wire-format) staging: dict(packed=(128, B) u8, s_ok=(B,) bool).

    Rows 0-31 = A, 32-63 = R, 64-95 = S, 96-127 = h (SHA-512(R||A||M) mod L).
    128 B/signature on the host->device link — 6x less than the f32 form of
    `prepare_batch`; the kernel unpacks on device (`split_packed128` +
    `unpack_packed_inputs`, a handful of VPU byte ops next to the ladder).
    """
    if allow_native:
        from ..crypto import native_staging

        staged = native_staging.stage_batch_packed(messages, keys, signatures)
        if staged is not None:
            return staged
    n = len(messages)
    a = np.frombuffer(b"".join(keys), np.uint8).reshape(n, 32)
    sig = np.frombuffer(b"".join(signatures), np.uint8).reshape(n, 64)
    r, s = sig[:, :32], sig[:, 32:]
    s_ok, h_bytes = _stage_scalars(messages, a, r, s)
    packed = np.ascontiguousarray(np.vstack([a.T, r.T, s.T, h_bytes.T]))
    return dict(packed=packed, s_ok=s_ok)


_L_BE = np.frombuffer(L_ORDER.to_bytes(32, "big"), np.uint8)


def _s_canonical_mask(s: np.ndarray) -> np.ndarray:
    """(B, 32) little-endian s rows -> (B,) bool s < L, vectorized (no
    per-item Python bigint loop)."""
    diff = s[:, ::-1].astype(np.int16) - _L_BE.astype(np.int16)
    nz = diff != 0
    first = nz.argmax(axis=1)
    return nz.any(axis=1) & (diff[np.arange(len(s)), first] < 0)


def prepare_batch_packed_dh(
    messages: Sequence[bytes],
    keys: Sequence[bytes],
    signatures: Sequence[bytes],
) -> dict:
    """Device-hash staging: dict(packed=(128, B) u8, s_ok=(B,) bool).

    Rows 0-31 = A, 32-63 = R, 64-95 = S, 96-127 = the 32-byte MESSAGE —
    h = SHA-512(R||A||M) mod L is computed ON DEVICE (ops.sha512), so the
    host does no per-item hashing at all: staging is numpy concatenation
    plus a vectorized s < L check. Requires every message to be exactly
    32 bytes (the protocol signs digests; `Ed25519TpuVerifier` falls back
    to `prepare_batch_packed` otherwise)."""
    n = len(messages)
    a = np.frombuffer(b"".join(keys), np.uint8).reshape(n, 32)
    sig = np.frombuffer(b"".join(signatures), np.uint8).reshape(n, 64)
    m = np.frombuffer(b"".join(messages), np.uint8).reshape(n, 32)
    r, s = sig[:, :32], sig[:, 32:]
    packed = np.ascontiguousarray(np.vstack([a.T, r.T, s.T, m.T]))
    return dict(packed=packed, s_ok=_s_canonical_mask(s))


def prepare_rows_packed_dh(
    messages: np.ndarray, keys: np.ndarray, signatures: np.ndarray
) -> dict:
    """`prepare_batch_packed_dh`, byte for byte, of a columnar batch: the
    (n, 32) message, (n, 32) key and (n, 64) signature uint8 columns of the
    sidecar's wire rows (crypto/backend.py `row_columns`, any strides).
    Three strided copies into the (128, n) wire array and the vectorized
    s < L check: no Python object per signature, no join."""
    packed = np.empty((128, len(messages)), np.uint8)
    packed[0:32] = keys.T
    packed[32:96] = signatures.T
    packed[96:128] = messages.T
    return dict(packed=packed, s_ok=_s_canonical_mask(signatures[:, 32:]))


def prepare_batch_committee(
    messages: Sequence[bytes],
    key_bytes: Sequence[bytes],
    indices: Sequence[int],
    signatures: Sequence[bytes],
) -> dict:
    """Committee host-hash staging: dict(packed=(96, B) u8, idx=(B,) i32,
    s_ok=(B,) bool). Rows 0-31 = R, 32-63 = S, 64-95 = h; `key_bytes` are
    the resolved committee key rows, needed only to compute h on host —
    they are NOT shipped to the device."""
    n = len(messages)
    sig = np.frombuffer(b"".join(signatures), np.uint8).reshape(n, 64)
    r, s = sig[:, :32], sig[:, 32:]
    a = np.frombuffer(b"".join(key_bytes), np.uint8).reshape(n, 32)
    s_ok, h_bytes = _stage_scalars(messages, a, r, s)
    packed = np.ascontiguousarray(np.vstack([r.T, s.T, h_bytes.T]))
    return dict(packed=packed, idx=np.asarray(indices, np.int32), s_ok=s_ok)


def prepare_batch_committee_dh(
    messages: Sequence[bytes],
    indices: Sequence[int],
    signatures: Sequence[bytes],
) -> dict:
    """Committee device-hash staging: dict(packed=(96, B) u8, idx, s_ok).

    Rows 64-95 carry the 32-byte MESSAGE; the device gathers the key bytes
    from the committee-resident table and hashes on device — host staging
    is byte concatenation plus the vectorized s < L check, and the wire
    cost drops to 96 B + 4 B index per signature (no key row at all)."""
    n = len(messages)
    sig = np.frombuffer(b"".join(signatures), np.uint8).reshape(n, 64)
    m = np.frombuffer(b"".join(messages), np.uint8).reshape(n, 32)
    r, s = sig[:, :32], sig[:, 32:]
    packed = np.ascontiguousarray(np.vstack([r.T, s.T, m.T]))
    return dict(
        packed=packed,
        idx=np.asarray(indices, np.int32),
        s_ok=_s_canonical_mask(s),
    )


def _stage_scalars(messages, a, r, s) -> tuple[np.ndarray, np.ndarray]:
    """Python staging of the per-item scalar work shared by both wire
    formats: the s<L canonicality mask and h = SHA-512(R||A||M) mod L."""
    n = len(messages)
    s_ok = np.empty(n, bool)
    h_bytes = np.empty((n, 32), np.uint8)
    for i in range(n):
        s_ok[i] = int.from_bytes(s[i].tobytes(), "little") < L_ORDER
        hd = hashlib.sha512(r[i].tobytes() + a[i].tobytes() + messages[i]).digest()
        h = int.from_bytes(hd, "little") % L_ORDER
        h_bytes[i] = np.frombuffer(h.to_bytes(32, "little"), np.uint8)
    return s_ok, h_bytes


def _nibbles(b: np.ndarray) -> np.ndarray:
    """(B, 32) u8 -> (64, B) f32 of 4-bit little-endian digits (row d has
    significance 16^d)."""
    n = b.shape[0]
    out = np.empty((n, 64), np.float32)
    out[:, 0::2] = b & 0x0F
    out[:, 1::2] = b >> 4
    return out.T.copy()


def _pad(arr: np.ndarray, width: int) -> np.ndarray:
    pad = width - arr.shape[-1]
    if pad == 0:
        return arr
    cfg = [(0, 0)] * (arr.ndim - 1) + [(0, pad)]
    return np.pad(arr, cfg)


def _checked_kernel(kernel: str) -> str:
    """The two flavours of the generic family: the jnp ladder or the Pallas
    one. Both take the same arguments, 4-bit digits included."""
    if kernel not in ("w4", "pallas"):
        raise ValueError(f"kernel must be 'w4' or 'pallas', not {kernel!r}")
    return kernel


class Ed25519TpuVerifier:
    """Bucketed, pipelined dispatcher for the jitted verify programs.

    `programs` is the table of what this verifier can dispatch: four
    callables under the names `program_name` returns, the generic pair of
    its `kernel` ("w4": the jnp programs `w4p128` / `w4p128dh`; "pallas":
    the chip's `pallas_p128` / `pallas_p128dh`) and the committee pair
    `w4c96` / `w4c96dh`, with "dh" where the device hashes (32-byte
    messages). A chunk dispatches `programs[program_name(...)]`.

    Both families ride ONE chunk loop (`_run`). Batches are padded up to
    power-of-two lane widths (>= 128 so the lane dimension is full) to
    bound the number of XLA compilations; oversize batches are split at
    `chunk` and ride an owned `DispatchPipeline` (ops/pipeline.py): each
    chunk ships as a packed u8 wire array ((128, W) generic; (96, W) plus a
    (W,) index vector committee) packed into a REUSED staging buffer,
    uploaded + dispatched from the pipeline's FIFO upload worker while the
    NEXT chunk stages, and its mask is fetched on the streaming readback
    worker while the next chunk dispatches — a bounded window of
    `pipeline_depth` chunks (default 2 = double buffering) is in flight
    between staging and readback. `pipeline_depth=1` is the serial/inline
    mode: no worker threads, deterministic order (the chaos rule,
    COMPONENTS.md §5.5i). A family contributes only how a chunk is staged
    and which device-resident operands precede the staged ones.
    """

    # Committee-resident fast path (set_committee /
    # verify_batch_mask_committee). The mesh subclass inherits it with
    # shard_map-wrapped kernels and per-chip replicated tables; verifier
    # types with genuinely no committee path set this False.
    supports_committee = True

    def __init__(
        self,
        min_bucket: int = 128,
        max_bucket: int = 8192,
        kernel: str = "w4",
        chunk: int | None = None,
        pipeline_depth: int | None = None,
    ):
        self.kernel = _checked_kernel(kernel)
        if kernel == "pallas":
            # the pallas grid tiles the batch in BLOCK-lane programs
            from .pallas_ladder import BLOCK

            min_bucket = -(-max(min_bucket, BLOCK) // BLOCK) * BLOCK
            max_bucket = max(BLOCK, max_bucket // BLOCK * BLOCK)
        self.min_bucket = min_bucket
        self.max_bucket = max_bucket
        self.chunk = min(chunk or 4096, max_bucket)
        # The owned dispatch pipeline (ops/pipeline.py): bounded in-flight
        # window, pooled staging buffers, streamed per-chunk readback.
        # Lazy threads — constructing a verifier spawns nothing; close()
        # (or GC, or atexit) reaps whatever a run created.
        self.pipeline = DispatchPipeline(
            depth=pipeline_depth, name=f"ed25519-{kernel}"
        )
        # This process imports jax, so its spans can sit on the profiler's
        # clock: a TraceMe, free unless a profiler session is open.
        timeline.set_annotator(jax.profiler.TraceAnnotation)
        # Placement hooks, all plain transfers on one chip: 2-D wire
        # arrays, 1-D lane vectors (committee indices), resident tables.
        # The mesh verifier shards the first two on the batch axis (so a
        # jitted shard_map never reshards a device-0 array) and replicates
        # the third.
        self._put = self._put_lanes = self._replicate = jax.device_put
        # Deferred readback (multi-process mesh, parallel/mesh.py): the
        # per-chunk readback returns the raw device handle and the chunk
        # loop materializes ALL handles in one end-of-batch
        # `_materialize` call — a single allgather instead of one
        # collective per chunk, the pre-pipeline multihost shape. Stage
        # then pads into FRESH buffers (jax keeps the host array alive
        # through the async transfer) because nothing blocks per chunk to
        # mark a pooled buffer reusable.
        self._defer_readback = False
        # Chunks dispatched per program name (`program_name`): how a run
        # shows WHICH kernel checked its signatures — TpuBackend.report()
        # and chip_smoke.py read it.
        self.dispatched: collections.Counter[str] = collections.Counter()
        self._dispatched_lock = threading.Lock()
        # Device-resident committee precompute (set_committee). The
        # committee path always rides the w4 jnp kernel: the pallas ladder
        # has no committee variant yet, and skipping decompress + table
        # build dominates the flavour difference at committee batch sizes.
        self._committee: CommitteeTable | None = None
        self.programs: dict = self._program_table()

    def _program_table(self) -> dict:
        """Name -> callable for the four programs `program_name` can
        return (the mesh verifier supplies the same names shard_map-
        wrapped)."""
        if self.kernel == "pallas":
            from . import pallas_ladder

            generic = pallas_ladder.PROGRAMS
        else:
            generic = {n: PROGRAMS[n] for n in ("w4p128", "w4p128dh")}
        return {**generic, "w4c96": PROGRAMS["w4c96"], "w4c96dh": PROGRAMS["w4c96dh"]}

    # -- committee-resident fast path ---------------------------------------

    @property
    def committee(self) -> "CommitteeTable | None":
        return self._committee

    def set_committee(self, keys: Sequence[bytes]) -> CommitteeTable:
        """Install (or rebuild) the device-resident committee table.

        An identical key sequence is a no-op (same table object); a changed
        key set INVALIDATES the previous table and rebuilds — the
        reconfiguration contract. Returns the active table."""
        if not self.supports_committee:
            raise NotImplementedError(
                f"{type(self).__name__} has no committee-resident path"
            )
        keys = [bytes(k) for k in keys]
        if self._committee is not None and self._committee.keys == keys:
            return self._committee
        # Built once per registration and placed by `_replicate`: on a mesh
        # every chip gets its own device-resident copy of the window tables /
        # validity mask / key bytes, so the sharded committee programs take
        # them as replicated shard_map operands with zero per-batch movement.
        self._committee = CommitteeTable(keys, put=self._replicate)
        _M_COMMITTEE_REGS.inc()
        _M_COMMITTEE_SIZE.set(self._committee.size)
        return self._committee

    def verify_batch_mask_committee(
        self,
        messages: Sequence[bytes],
        indices: Sequence[int],
        signatures: Sequence[bytes],
        table: "CommitteeTable | None" = None,
    ) -> np.ndarray:
        """Committee fast path: items carry validator INDICES into the
        registered table — steady-state batches perform zero on-device
        decompressions or window-table builds.

        `table` pins the CommitteeTable the indices were resolved against:
        a concurrent re-registration (epoch reconfiguration) must not swap
        the table under an in-flight batch, or lanes would gather another
        validator's precompute. Defaults to the currently registered one.
        """
        ct = table or self._committee
        if ct is None:
            raise RuntimeError(
                "no committee registered (call set_committee first)"
            )
        n = len(messages)
        if n == 0:
            return np.empty(0, bool)
        _M_BATCHES.inc()
        _M_SIGS.inc(n)
        _M_BATCH_SIZE.record(n)
        _M_COMMITTEE_BATCHES.inc()
        _M_COMMITTEE_SIGS.inc(n)
        with metrics.span(_M_E2E):
            # 32-byte messages (the protocol's digests) hash on device. A
            # failure of that program raises like any other device error:
            # nothing reruns the batch through the host-hash twin.
            device_hash = all(len(m) == 32 for m in messages)
            indices = list(indices)

            def stage_chunk(lo: int, hi: int):
                idx_chunk = indices[lo:hi]
                if device_hash:
                    staged = prepare_batch_committee_dh(
                        messages[lo:hi], idx_chunk, signatures[lo:hi]
                    )
                else:
                    staged = prepare_batch_committee(
                        messages[lo:hi],
                        [ct.keys[i] for i in idx_chunk],
                        idx_chunk,
                        signatures[lo:hi],
                    )
                return (staged["idx"], staged["packed"]), staged["s_ok"]

            # `ct`'s arrays stay PINNED through `_run`'s closure — a
            # concurrent epoch re-registration cannot swap tables under an
            # in-flight chunk (the §5.5c contract), they are never re-read
            # from self.
            resident = (ct.ta_ypx, ct.ta_ymx, ct.ta_xy2d, ct.valid)
            if device_hash:
                resident += (ct.keys_u8,)
            return self._run(
                n, self.program_name(True, device_hash), stage_chunk, resident
            )

    def close(self) -> None:
        """Drain the owned dispatch pipeline's worker threads. Safe to
        call more than once; a closed verifier keeps working (every
        subsequent batch runs the serial inline path). Un-closed
        verifiers are reaped by GC/atexit — tests may construct and drop
        verifiers freely without leaking threads."""
        self.pipeline.close()

    def _bucket(self, n: int) -> int:
        b = self.min_bucket
        while b < n:
            b *= 2
        return min(b, self.max_bucket)

    def _note_dispatch(self, program: str) -> None:
        # submit legs of concurrent batches run on several threads when the
        # pipeline is inline (depth 1); `+=` on a dict entry is not atomic
        with self._dispatched_lock:
            self.dispatched[program] += 1

    def program_name(self, committee: bool, device_hash: bool) -> str:
        """Stable name of the jitted program a chunk dispatches to (the
        keys of `dispatched`). The committee family always rides the w4
        jnp kernel: the pallas ladder has no committee variant."""
        if committee:
            base = "w4c96"
        else:
            base = "pallas_p128" if self.kernel == "pallas" else "w4p128"
        return base + ("dh" if device_hash else "")

    def verify_batch_mask(
        self,
        messages: Sequence[bytes],
        keys: Sequence[bytes],
        signatures: Sequence[bytes],
    ) -> np.ndarray:
        n = len(messages)
        if n == 0:
            return np.empty(0, bool)
        _M_BATCHES.inc()
        _M_SIGS.inc(n)
        _M_BATCH_SIZE.record(n)
        with metrics.span(_M_E2E):
            return self._verify_batch_mask(messages, keys, signatures)

    def _verify_batch_mask(self, messages, keys, signatures) -> np.ndarray:
        # Device-hash path: when every message is a 32-byte digest (the
        # protocol hot path), h is computed on device and host staging is
        # pure byte concatenation. Other lengths ride the host-hash twin,
        # a separate program compiled on first use. A device-hash failure
        # raises like any other device error — no rerun with host hashing.
        # A columnar batch (three uint8 column arrays) is 32-byte messages
        # by its shape.
        if isinstance(messages, np.ndarray):
            device_hash, stage_fn = True, prepare_rows_packed_dh
        elif all(len(m) == 32 for m in messages):
            device_hash, stage_fn = True, prepare_batch_packed_dh
        else:
            device_hash, stage_fn = False, prepare_batch_packed

        def stage_chunk(lo: int, hi: int):
            # The generic kernel decompresses every lane's key and
            # rebuilds its -A window table on device — the per-batch
            # cost the committee path amortizes away.
            _M_TABLE_BUILDS.inc()
            _M_DECOMPRESSIONS.inc(hi - lo)
            staged = stage_fn(messages[lo:hi], keys[lo:hi], signatures[lo:hi])
            return (staged["packed"],), staged["s_ok"]

        return self._run(
            len(messages), self.program_name(False, device_hash), stage_chunk
        )

    def _run(self, n: int, program: str, stage_chunk, resident: tuple = ()):
        """The chunk loop of both families: split `n` lanes at `chunk`,
        and per chunk stage, pad into the pooled buffers, upload, dispatch
        `programs[program]`, read back and apply the host's s < L mask.

        `stage_chunk(lo, hi)` -> (the chunk's arrays to ship, lanes last,
        in the program's operand order; its (hi - lo,) s_ok). `resident`
        are device arrays that precede them in every call. The upload
        worker runs `submit`: each verifier's DispatchPipeline has ONE, so
        chunks of one verifier upload and dispatch in FIFO order while the
        caller's thread stages the next chunk; sibling backends (cross-chip
        work stealing, §5.5i) run their uploads in parallel on their own
        workers."""
        fn = self.programs[program]
        tl_batch = timeline.batch_id()
        defer = self._defer_readback
        hists = _DEFER_HISTS if defer else _CHUNK_HISTS
        # Deferred readback never blocks per chunk, so no point marks a
        # pooled buffer reusable — fresh buffers, jax holds them through
        # the async upload.
        pad = _pad if defer else self.pipeline.pool.pad
        oks: list = []

        def make_task(ci: int, lo: int, hi: int) -> ChunkTask:
            # the chunk's (batch, chunk, n) key of its four spans
            # (ops/timeline.py: ring, histogram, profiler annotation)
            tlkey = (tl_batch, ci, hi - lo)
            release: list = []

            def stage():
                _M_CHUNKS.inc()
                arrays, s_ok = stage_chunk(lo, hi)
                width = self._bucket(hi - lo)
                _M_PAD_LANES.inc(width - (hi - lo))
                oks.append((lo, hi, s_ok))
                padded = [pad(a, width) for a in arrays]
                if not defer:
                    release.extend(padded)
                return padded

            def submit(padded):
                self._note_dispatch(program)
                with timeline.span("upload", *tlkey, hist=_M_UPLOAD):
                    dev = [
                        (self._put_lanes if a.ndim == 1 else self._put)(a)
                        for a in padded
                    ]
                with timeline.span("dispatch", *tlkey, hist=_M_DISPATCH):
                    return fn(*resident, *dev)

            def readback(handle):
                if defer:
                    return handle
                return self._materialize([handle])

            return ChunkTask(
                stage=stage, submit=submit, readback=readback, tlkey=tlkey,
                release=release, hists=hists,
            )

        hosts = self.pipeline.run(
            make_task(ci, lo, min(lo + self.chunk, n))
            for ci, lo in enumerate(range(0, n, self.chunk))
        )
        if defer:
            hosts = self._materialize_deferred(hosts, n, tl_batch)
        out = np.empty(n, bool)
        for (lo, hi, ok), host in zip(oks, hosts):
            out[lo:hi] = host[: hi - lo] & ok
        return out

    def _materialize(self, masks) -> np.ndarray:
        """Device mask handles -> one host bool array (overridden by the
        mesh verifier: a multi-process mesh needs an allgather first)."""
        if len(masks) == 1:
            return np.asarray(masks[0])
        return np.asarray(jnp.concatenate(masks))

    def _materialize_deferred(self, handles: list, n: int, batch: int) -> list:
        """Deferred-readback tail (`_defer_readback`, multi-process
        mesh): ONE `_materialize` over every chunk's device handle — a
        single end-of-batch allgather, the pre-pipeline multihost shape
        ('per-transfer latency is paid once, not per chunk') — split
        back into per-chunk host arrays on the deterministic bucket
        widths. The one real readback of the batch: timed under its last
        chunk (the per-chunk readbacks returned handles and fed no
        histogram, `_DEFER_HISTS`)."""
        with timeline.span(
            "readback", batch, len(handles) - 1, n, hist=_M_READBACK
        ):
            full = self._materialize(handles)
        out, off = [], 0
        for lo in range(0, n, self.chunk):
            width = self._bucket(min(lo + self.chunk, n) - lo)
            out.append(full[off:off + width])
            off += width
        return out


def kernel_args(staged: dict, width: int, kernel: str = "w4") -> tuple:
    """`prepare_batch`'s staging as the padded f32 arguments of the
    reference `_verify_kernel_w4` (the Pallas `_verify_kernel_pallas` takes
    the same 4-bit digits)."""
    _checked_kernel(kernel)
    return tuple(
        _pad(staged[k], width)
        for k in ("a_y", "a_sign", "r_enc", "s_digits", "h_digits")
    )
