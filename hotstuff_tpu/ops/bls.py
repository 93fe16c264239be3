"""BLS12-381 G1 committee-aggregation kernels (§5.5o).

The aggregate-certificate plane (consensus/messages.py AggQC/AggTC)
verifies ONE aggregate signature per certificate against the SUM of the
bitmap members' G1 public keys. The pairing itself is a per-certificate
constant, but the key sum is O(committee): at 256 validators the exact
host backend (crypto/aggsig._FP_OPS.add_affine) burns a field inversion
per added key. This module moves that sum onto the accelerator:

  * Fp in radix-2^12 uint32 limbs (32 limbs x 12 bits = 384 >= 381).
    BLS12-381's p is NOT pseudo-Mersenne, so the GF(2^255-19) fold trick
    (ops/field12.py) does not apply; multiplication is word-serial
    Montgomery (CIOS over 12-bit digits): the 64-digit schoolbook
    product, then 32 rounds of m = c_i * (-p^-1 mod 2^12) & MASK,
    c += m * p << 12i. Every accumulator stays uint32-exact:
    products <= 32 * 8191^2 < 2^31, reduction adds < 2^29, carries
    < 2^19 — sum < 2^31.6 < 2^32.
  * Residues live in [0, 2p) (Montgomery form, R = 2^384): with
    8p < R, a mul of a [0,2p) by a [0,4p) operand lands back in
    [0, 2p), so add/sub need only a conditional 2p-subtraction.
  * Jacobian points with Z = 0 as the identity; point_add is fully
    branchless — generic add-2007-bl, doubling, and the four identity/
    inverse cases resolved by masked selects — so a masked committee
    table tree-reduces in log2(N) vectorized adds with no host
    round-trips.

A CommitteeTable (mirroring ops/ed25519.CommitteeTable) pays the exact
host decompression of each registered 48-byte pk once per committee and
keeps Montgomery-affine limbs device-resident; `aggregate_bitmaps` then
turns certificate bitmaps into aggregate public keys in one batched
kernel launch. On hosts without jax the same API degrades to the exact
integer backend (`bls.host_fallbacks` counts it) — the chaos plane and
graftlint never import this module (it is lazy in ops/__init__), so the
dependency gate only matters for direct callers.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from ..crypto import aggsig
from ..utils import metrics

try:  # CPU fallback: the module stays importable with no jax at all.
    import jax
    import jax.numpy as jnp
    from jax import lax

    HAVE_JAX = True
except Exception:  # pragma: no cover - exercised on jax-less hosts
    jax = jnp = lax = None
    HAVE_JAX = False

P = aggsig.P
NLIMB = 32
BITS = 12
RADIX = 1 << BITS
MASK = RADIX - 1
R_MONT = (1 << (BITS * NLIMB)) % P  # 2^384 mod p
PINV12 = (-pow(P, -1, RADIX)) % RADIX  # -p^-1 mod 2^12 (CIOS digit factor)

_M_TABLE_BUILDS = metrics.counter("bls.table_builds")
_M_AGGREGATIONS = metrics.counter("bls.aggregations")
_M_POINTS = metrics.counter("bls.points_aggregated")
_M_FALLBACKS = metrics.counter("bls.host_fallbacks")


def limbs_of_int(x: int, n: int = NLIMB) -> np.ndarray:
    assert 0 <= x < (1 << (BITS * n))
    out = np.zeros((n, 1), np.uint32)
    for i in range(n):
        out[i, 0] = (x >> (BITS * i)) & MASK
    return out


def int_of_limbs(limbs) -> list[int]:
    arr = np.asarray(limbs, np.uint64)
    flat = arr.reshape(arr.shape[0], -1)
    return [
        sum(int(flat[i, b]) << (BITS * i) for i in range(flat.shape[0]))
        for b in range(flat.shape[1])
    ]


def to_mont(x: int) -> int:
    return x * R_MONT % P


def from_mont(x: int) -> int:
    # x / R mod p, exact-integer (host-side only, per fetched result).
    return x * pow(R_MONT, P - 2, P) % P


P_LIMBS = limbs_of_int(P)
TWOP_LIMBS = limbs_of_int(2 * P)
TWOP_COMPLEMENT = limbs_of_int((1 << (BITS * NLIMB)) - 2 * P)


if HAVE_JAX:
    U32 = jnp.uint32

    def _seq_carry(c):
        """Sequential full carry: limbs < 2^32 -> limbs < 2^12 exactly
        (unique digit representation; required by the value-equality
        masks in point_add). Carry out of limb 31 must be zero — every
        caller's value fits 384 bits."""

        def body(i, state):
            limbs, cin = state
            t = lax.dynamic_index_in_dim(limbs, i, 0, keepdims=False) + cin
            lo = t & U32(MASK)
            return (
                lax.dynamic_update_index_in_dim(limbs, lo, i, 0),
                t >> BITS,
            )

        out, _ = lax.fori_loop(
            0, NLIMB, body, (c, jnp.zeros(c.shape[1:], U32))
        )
        return out

    def _cond_sub_2p(x):
        """x in [0, 4p), limbs normalized -> [0, 2p). Adds 2^384 - 2p;
        a carry out of the top limb means x >= 2p and the wrapped sum IS
        x - 2p."""
        t = x + jnp.asarray(TWOP_COMPLEMENT, U32).reshape(
            (NLIMB,) + (1,) * (x.ndim - 1)
        )

        def body(i, state):
            limbs, cin = state
            v = lax.dynamic_index_in_dim(limbs, i, 0, keepdims=False) + cin
            return (
                lax.dynamic_update_index_in_dim(limbs, v & U32(MASK), i, 0),
                v >> BITS,
            )

        t, cout = lax.fori_loop(
            0, NLIMB, body, (t, jnp.zeros(x.shape[1:], U32))
        )
        return jnp.where((cout >= 1)[None], t, x)

    def add_mod(a, b):
        """(a + b) brought back to [0, 2p), limbs normalized."""
        return _cond_sub_2p(_seq_carry(a + b))

    def sub_mod(a, b):
        """a - b in [0, 2p): sequential-borrow subtraction mod 2^384,
        then a conditional 2p add-back on the lanes that went negative.
        No bias headroom needed — p spans 381 of the 384 limb bits, so
        the field12 bias-with-floors trick has no room here. Inputs
        normalized in [0, 2p)."""
        batch = jnp.broadcast_shapes(a.shape[1:], b.shape[1:])
        a = jnp.broadcast_to(a, (NLIMB,) + batch)
        b = jnp.broadcast_to(b, (NLIMB,) + batch)

        def borrow_body(i, state):
            limbs, borrow = state
            ai = lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
            bi = lax.dynamic_index_in_dim(b, i, 0, keepdims=False)
            t = ai + U32(RADIX) - bi - borrow  # in [1, 2^13)
            return (
                lax.dynamic_update_index_in_dim(limbs, t & U32(MASK), i, 0),
                U32(1) - (t >> BITS),
            )

        diff, borrow = lax.fori_loop(
            0,
            NLIMB,
            borrow_body,
            (jnp.zeros((NLIMB,) + batch, U32), jnp.zeros(batch, U32)),
        )
        twop = jnp.asarray(TWOP_LIMBS, U32).reshape(
            (NLIMB,) + (1,) * len(batch)
        )
        return _seq_carry(diff + borrow[None] * twop)

    def mont_mul(a, b):
        """Montgomery product a*b/R mod p, output in [0, 2p) normalized.
        Inputs: values < 2p x < 4p with limbs <= 2^13 (one lazy add on
        one operand is admissible; both normalized is the common case)."""
        batch = jnp.broadcast_shapes(a.shape[1:], b.shape[1:])
        a = jnp.broadcast_to(a, (NLIMB,) + batch)
        b = jnp.broadcast_to(b, (NLIMB,) + batch)
        c = jnp.zeros((2 * NLIMB,) + batch, U32)

        def prod(i, c):
            ai = lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
            cur = lax.dynamic_slice_in_dim(c, i, NLIMB, 0)
            return lax.dynamic_update_slice_in_dim(c, cur + ai[None] * b, i, 0)

        c = lax.fori_loop(0, NLIMB, prod, c)
        p_limbs = jnp.asarray(P_LIMBS, U32).reshape(
            (NLIMB,) + (1,) * len(batch)
        )

        def reduce(i, c):
            ci = lax.dynamic_index_in_dim(c, i, 0, keepdims=False)
            m = (ci * U32(PINV12)) & U32(MASK)
            cur = lax.dynamic_slice_in_dim(c, i, NLIMB, 0)
            cur = cur + m[None] * p_limbs
            # Digit i is now ≡ 0 mod 2^12; retire it into digit i+1.
            cur = cur.at[1].add(cur[0] >> BITS)
            cur = cur.at[0].set(U32(0))
            return lax.dynamic_update_slice_in_dim(c, cur, i, 0)

        c = lax.fori_loop(0, NLIMB, reduce, c)
        return _seq_carry(lax.dynamic_slice_in_dim(c, NLIMB, NLIMB, 0))

    def mont_sqr(a):
        return mont_mul(a, a)

    def is_zero_mod_p(a):
        """Value ≡ 0 (mod p) for a normalized [0, 2p) residue: the digit
        string is exactly 0 or exactly p's."""
        p_limbs = jnp.asarray(P_LIMBS, U32).reshape(
            (NLIMB,) + (1,) * (a.ndim - 1)
        )
        return jnp.all(a == 0, axis=0) | jnp.all(a == p_limbs, axis=0)

    def _select(mask, a, b):
        return jnp.where(mask[None], a, b)

    def point_identity(batch: tuple):
        one = jnp.broadcast_to(
            jnp.asarray(limbs_of_int(to_mont(1)), U32).reshape(
                (NLIMB,) + (1,) * len(batch)
            ),
            (NLIMB,) + batch,
        )
        return one, one, jnp.zeros((NLIMB,) + batch, U32)

    def dbl_mod(a):
        return add_mod(a, a)

    def point_dbl(pt):
        """Jacobian doubling (dbl-2007-bl shape, a = 0). Y = 0 (outside
        the prime-order subgroup) degenerates to Z3 = 0 = identity with
        no special case."""
        X, Y, Z = pt
        A = mont_sqr(X)
        B = mont_sqr(Y)
        C = mont_sqr(B)
        D = dbl_mod(sub_mod(sub_mod(mont_sqr(add_mod(X, B)), A), C))
        E = add_mod(dbl_mod(A), A)
        X3 = sub_mod(sub_mod(mont_sqr(E), D), D)
        Y3 = sub_mod(mont_mul(E, sub_mod(D, X3)), dbl_mod(dbl_mod(dbl_mod(C))))
        Z3 = dbl_mod(mont_mul(Y, Z))
        return X3, Y3, Z3

    def point_add(p1, p2):
        """Branchless Jacobian addition (add-2007-bl) with the identity,
        doubling, and inverse cases resolved by lane masks — the shape a
        masked tree reduction needs."""
        X1, Y1, Z1 = p1
        X2, Y2, Z2 = p2
        Z1Z1 = mont_sqr(Z1)
        Z2Z2 = mont_sqr(Z2)
        U1 = mont_mul(X1, Z2Z2)
        U2 = mont_mul(X2, Z1Z1)
        S1 = mont_mul(mont_mul(Y1, Z2), Z2Z2)
        S2 = mont_mul(mont_mul(Y2, Z1), Z1Z1)
        H = sub_mod(U2, U1)
        Rr = dbl_mod(sub_mod(S2, S1))
        I = mont_sqr(dbl_mod(H))
        J = mont_mul(H, I)
        V = mont_mul(U1, I)
        X3 = sub_mod(sub_mod(mont_sqr(Rr), J), dbl_mod(V))
        Y3 = sub_mod(
            mont_mul(Rr, sub_mod(V, X3)), dbl_mod(mont_mul(S1, J))
        )
        Z3 = dbl_mod(mont_mul(mont_mul(Z1, Z2), H))

        inf1 = is_zero_mod_p(Z1)
        inf2 = is_zero_mod_p(Z2)
        eq_x = is_zero_mod_p(H)
        eq_y = is_zero_mod_p(sub_mod(S2, S1))
        dX, dY, dZ = point_dbl(p1)
        iX, iY, iZ = point_identity(X1.shape[1:])

        # Lane resolution, later selects win: doubling and inverse-pair
        # first (H = 0 is also true on identity lanes — U1 = U2 = 0 —
        # so the identity selects must come after), then p1-identity
        # -> p2, then p2-identity -> p1. Both-identity lands on p1,
        # whose Z ≡ 0 already encodes the identity.
        def pick(m, a, b):
            return tuple(_select(m, x, y) for x, y in zip(a, b))

        out = pick(eq_x & eq_y, (dX, dY, dZ), (X3, Y3, Z3))
        out = pick(eq_x & ~eq_y, (iX, iY, iZ), out)
        out = pick(inf1, (X2, Y2, Z2), out)
        out = pick(inf2, (X1, Y1, Z1), out)
        return out

    def masked_tree_aggregate(tx, ty, mask):
        """Sum the masked committee points: tx/ty (NLIMB, N) Montgomery
        affine limbs, mask (B, N) bool -> one Jacobian point per batch
        row, in ceil(log2 N) vectorized point adds."""
        B, N = mask.shape
        one = jnp.asarray(limbs_of_int(to_mont(1)), U32).reshape(NLIMB, 1, 1)
        X = jnp.broadcast_to(tx[:, None, :], (NLIMB, B, N))
        Y = jnp.broadcast_to(ty[:, None, :], (NLIMB, B, N))
        Z = jnp.where(mask[None], jnp.broadcast_to(one, (NLIMB, B, N)), 0)
        pt = (X, Y, Z)
        n = N
        while n > 1:
            half = (n + 1) // 2
            if n % 2:
                pad = point_identity((B, 1))
                pt = tuple(
                    jnp.concatenate([c, p], axis=2) for c, p in zip(pt, pad)
                )
            lo = tuple(c[:, :, :half] for c in pt)
            hi = tuple(c[:, :, half:] for c in pt)
            pt = point_add(lo, hi)
            n = half
        return tuple(c[:, :, 0] for c in pt)


# --------------------------------------------------------------------------
# Committee-resident aggregate-key table + host conversions.


class CommitteeTable:
    """Device-resident Montgomery-affine G1 limbs for one committee's
    registered aggregate keys, built once per epoch (the per-certificate
    amortization lever — same shape as ops/ed25519.CommitteeTable).

    `keys` are 48-byte compressed G1 public keys in bitmap order
    (aggsig registry values resolved over Committee.sorted_keys()).
    Un-decompressable or infinity keys occupy identity lanes and are
    reported in `invalid` — their bits contribute nothing to a sum,
    matching the exact backend's verify failure for such members (the
    caller rejects certificates whose bitmap selects an invalid lane).
    """

    def __init__(self, keys: Sequence[bytes], put=None) -> None:
        keys = [bytes(k) for k in keys]
        if not keys:
            raise ValueError("committee must have at least one key")
        n = len(keys)
        self.keys = keys
        self.index: dict[bytes, int] = {}
        for i, k in enumerate(keys):
            self.index.setdefault(k, i)
        self.points: list[tuple[int, int] | None] = []
        tx = np.zeros((NLIMB, n), np.uint32)
        ty = np.zeros((NLIMB, n), np.uint32)
        present = np.zeros(n, bool)
        invalid = np.zeros(n, bool)
        for i, kb in enumerate(keys):
            try:
                pt = aggsig.decompress_g1(kb)
            except ValueError:
                pt = None
                invalid[i] = True
            self.points.append(pt)
            if pt is None:
                continue
            present[i] = True
            tx[:, i] = limbs_of_int(to_mont(pt[0]))[:, 0]
            ty[:, i] = limbs_of_int(to_mont(pt[1]))[:, 0]
        self.size = n
        self.invalid = invalid
        if HAVE_JAX:
            if put is None:
                put = jax.device_put
            self.tx = put(tx)
            self.ty = put(ty)
            self.present = put(present)
        else:
            self.tx, self.ty, self.present = tx, ty, present
        _M_TABLE_BUILDS.inc()

    # -- host fallback ----------------------------------------------------

    def _aggregate_host(self, masks: np.ndarray):
        ops = aggsig._FP_OPS
        out = []
        for row in masks:
            acc = None
            for i in np.flatnonzero(row):
                acc = ops.add_affine(acc, self.points[i])
            out.append(acc)
        return out

    def aggregate_masks(self, masks) -> list[tuple[int, int] | None]:
        """(B, N) bool mask rows -> affine integer G1 sums (None = the
        identity). Masked lanes whose key was invalid contribute the
        identity — callers gate on `invalid` first."""
        masks = np.asarray(masks, bool)
        if masks.ndim == 1:
            masks = masks[None]
        if masks.shape[1] != self.size:
            raise ValueError(
                f"mask width {masks.shape[1]} != committee size {self.size}"
            )
        _M_AGGREGATIONS.inc(masks.shape[0])
        _M_POINTS.inc(int(masks.sum()))
        if not HAVE_JAX:
            _M_FALLBACKS.inc(masks.shape[0])
            return self._aggregate_host(masks)
        eff = jnp.asarray(masks) & self.present[None]
        X, Y, Z = _aggregate_jit(self.tx, self.ty, eff)
        xs = int_of_limbs(np.asarray(X))
        ys = int_of_limbs(np.asarray(Y))
        zs = int_of_limbs(np.asarray(Z))
        out = []
        for x, y, z in zip(xs, ys, zs):
            x, y, z = from_mont(x % P), from_mont(y % P), from_mont(z % P)
            if z == 0:
                out.append(None)
                continue
            zinv = pow(z, P - 2, P)
            zi2 = zinv * zinv % P
            out.append((x * zi2 % P, y * zinv % P * zi2 % P))
        return out

    def aggregate_bitmaps(
        self, bitmaps: Sequence[int]
    ) -> list[tuple[int, int] | None]:
        masks = np.zeros((len(bitmaps), self.size), bool)
        for b, bm in enumerate(bitmaps):
            if bm < 0 or bm >> self.size:
                raise ValueError(f"bitmap {bm:#x} exceeds committee")
            for i in range(self.size):
                masks[b, i] = bool(bm >> i & 1)
        return self.aggregate_masks(masks)

    def verify_aggregate(self, bitmap: int, msg: bytes, sig: bytes) -> bool:
        """One AggQC-shaped check: the device-summed aggregate key of
        `bitmap`, one pairing equation on the exact host backend. The
        bitmap must not select an invalid (un-decompressable) lane."""
        for i in range(self.size):
            if bitmap >> i & 1 and self.invalid[i]:
                return False
        apk = self.aggregate_bitmaps([bitmap])[0]
        if apk is None:
            return False
        try:
            s = aggsig.decompress_g2(sig)
        except ValueError:
            return False
        if s is None or not aggsig._g2_in_subgroup(s):
            return False
        return aggsig._pairings_are_one(
            [
                (aggsig._g1_neg(aggsig.G1_GEN), s),
                (apk, aggsig.hash_to_g2(msg)),
            ]
        )


if HAVE_JAX:
    _aggregate_jit = jax.jit(masked_tree_aggregate)
else:  # pragma: no cover - jax-less hosts take the host path above
    _aggregate_jit = None
