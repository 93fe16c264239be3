"""Pallas TPU kernel for the windowed double-scalar-mult ladder.

The jnp ladder (ops.ed25519._verify_kernel_w4) leaves XLA to schedule ~3.5k
field mults as separate HBM-roundtripping fusions per fori iteration. This
kernel runs the whole 64-group ladder VMEM-resident: one grid program per
128-lane batch block (`LADDER_BLOCK`) holds the accumulator point, both
digit arrays and the 16-entry tables (shared k*B and per-item k*(-A))
on-chip for all 256 doubling steps — the only HBM traffic is the initial
block load and the final point store. The per-item table is built there
too, in the kernel's prologue, from the decompressed key: in plain jnp its
~115 field multiplications cost 33 us each (3.9 ms a 4096-lane program) and
its four (16, 32, B) tables went out to HBM for the kernel to read back
(PERF.md §6, PR 32).

All arithmetic is ops.field on (32, block) f32 limb vectors (exact-integer
f32, see field.py). Table lookups are unrolled masked sums over the 16
entries (VPU fma chains — no gathers, which TPUs do poorly). Digit rows are
selected by an iota-mask reduction instead of dynamic slicing (supported +
cheap: 64 x block fma per group).

The two fixed-exponent chains around the ladder (decompress's square root,
compress's 1/Z: ~265 field multiplications each) run in a second kernel,
`chain_pallas`, for the same reason. They run once per batch, not per
ladder step, and were first left in plain jnp as "~15% of total work";
the device trace said otherwise: of 39.9 ms a 4096-lane program the ladder
took 17.4 ms and the chains' sixteen jnp `while` loops 16.5 ms, 33 us an
iteration against 6.3 us a multiplication in here (PERF.md §6, PR 30, has
the program by device op before and after). Unpacking, SHA-512, the few
single field operations of decompress and compress and the canonical
reductions stay in plain jnp around the two kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import field as f
from . import ed25519 as ed

# Lanes per grid program (multiple of 128) of the chain kernel, and what a
# batch is aligned to. One chain over 4096 lanes, alone on a v5e with the
# host's dispatch: 1.9 / 1.9 / 2.2 / 2.7 ms at 128 / 256 / 512 / 1024 lanes
# a program (a (66, B) product outgrows the 64 vregs past 256), VMEM
# exhausted at 2048 (PR 30).
BLOCK = 256
# The ladder's own: it keeps an accumulator point, the looked-up operands
# and a product live at once, and measured the same way reads 17.2 / 19.3 /
# 23.6 ms at 128 / 256 / 512 lanes a program, VMEM exhausted at 1024
# (PR 32, with the table prologue).
LADDER_BLOCK = 128


def _digit_row(digits: jnp.ndarray, row) -> jnp.ndarray:
    """digits (64, B), dynamic row index -> (B,) via iota-mask reduction."""
    rows = lax.broadcasted_iota(jnp.int32, digits.shape, 0)
    return jnp.sum(jnp.where(rows == row, digits, 0.0), axis=0)


def _lookup_shared(table: jnp.ndarray, digit: jnp.ndarray) -> jnp.ndarray:
    """table (16, 32) canonical, digit (B,) -> (32, B) masked-sum select."""
    acc = jnp.zeros((f.NLIMB, digit.shape[0]), jnp.float32)
    for e in range(16):
        m = (digit == e).astype(jnp.float32)
        acc = acc + table[e][:, None] * m[None, :]
    return acc


def _lookup_item(table_ref, digit: jnp.ndarray) -> jnp.ndarray:
    """table ref (16, 32, B) per-item, digit (B,) -> (32, B)."""
    acc = jnp.zeros(table_ref.shape[1:], jnp.float32)
    for e in range(16):
        m = (digit == e).astype(jnp.float32)
        acc = acc + table_ref[e] * m[None, :]
    return acc


def _neg_a_table_into(x_neg, a_y, d2, ypx_ref, ymx_ref, z_ref, t2d_ref):
    """The arithmetic of `ed._build_neg_a_table`, entry by entry into four
    (16, 32, B) refs: the cached multiples k*(-A), k = 0..15, as
    (Y+X, Y-X, Z, 2d*T). Every limb equals the jnp build's (exact integers
    in f32, the same operations on the same operands), so the ladder's
    bound on table limbs (~590) stands. The jnp build stays what it is, the
    `w4*` programs' and the tests' reference; this one differs where a
    Mosaic body must: `d2` is `ed.D2`, (32, 1), as an operand (a Pallas
    body captures no array constant) and always `mul`'s second factor
    (Mosaic broadcasts a column over lanes, not one limb of it over a whole
    tile); the fourteen additions are one rolled loop; entries 0 and 1 take
    2d*T from what is at hand (0, and the madd operand 2d*x*y). A lane
    whose key did not decompress carries some canonical x and flows on
    like any other."""
    zero, one, _, _ = ed.point_identity(a_y.shape[1])
    xy = f.mul(x_neg, a_y)
    na = (f.add(a_y, x_neg), f.sub(a_y, x_neg), f.mul(xy, d2))

    def store(k, p, t2d):
        ypx_ref[k] = f.add(p[1], p[0])
        ymx_ref[k] = f.sub(p[1], p[0])
        z_ref[k] = p[2]
        t2d_ref[k] = t2d

    store(0, (zero, one, one, zero), zero)
    first = (x_neg, a_y, one, xy)
    store(1, first, na[2])

    def entry(k, cur):
        cur = ed.point_madd(cur, *na)
        store(k, cur, f.mul(cur[3], d2))
        return cur

    lax.fori_loop(2, 16, entry, first)


def _ladder_kernel(
    sd_ref,
    hd_ref,
    bypx_ref,
    bymx_ref,
    bxy2d_ref,
    d2_ref,
    xneg_ref,
    ay_ref,
    x_out,
    y_out,
    z_out,
    t_out,
    *ta_refs,
):
    sd = sd_ref[:]
    hd = hd_ref[:]
    b_ypx, b_ymx, b_xy2d = bypx_ref[:], bymx_ref[:], bxy2d_ref[:]

    def group(g, acc):
        # T-skip schedule: see ed._verify_kernel_w4.body — only the last
        # doubling (feeding the madd) produces T; the cached add skips it.
        for i in range(ed.WINDOW):
            acc = ed.point_dbl(acc, with_t=i == ed.WINDOW - 1)
        row = ed.NGROUPS - 1 - g
        sdg = _digit_row(sd, row)
        hdg = _digit_row(hd, row)
        acc = ed.point_madd(
            acc,
            _lookup_shared(b_ypx, sdg),
            _lookup_shared(b_ymx, sdg),
            _lookup_shared(b_xy2d, sdg),
        )
        acc = ed.point_add_cached(
            acc, *(_lookup_item(ref, hdg) for ref in ta_refs), with_t=False
        )
        return acc

    with f.mosaic_safe():
        _neg_a_table_into(xneg_ref[:], ay_ref[:], d2_ref[:], *ta_refs)
        X, Y, Z, T = lax.fori_loop(
            0, ed.NGROUPS, group, ed.point_identity(sd.shape[1])
        )
    x_out[:] = X
    y_out[:] = Y
    z_out[:] = Z
    t_out[:] = T


@functools.partial(jax.jit, static_argnames=("interpret",))
def ladder_pallas(s_digits, h_digits, x_neg, a_y, interpret: bool = False):
    """(64,B) digits + the decompressed key's -x and y, (32,B) canonical
    limbs each -> ladder result Point [s]B + [h](-A). The kernel builds
    the per-item table of k*(-A) in VMEM scratch before its first group
    (`_neg_a_table_into`); nothing of it touches HBM.
    `interpret` runs the kernel body in the Pallas interpreter (the only
    way it executes without a TPU; the tests pass it, nothing else does)."""
    batch = s_digits.shape[1]
    block = LADDER_BLOCK
    assert batch % block == 0, f"batch {batch} must be a multiple of {block}"
    grid = (batch // block,)

    digit_spec = pl.BlockSpec(
        (ed.NGROUPS, block), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    shared_spec = pl.BlockSpec(
        (16, f.NLIMB), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    const_spec = pl.BlockSpec(
        (f.NLIMB, 1), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    elem_spec = pl.BlockSpec(
        (f.NLIMB, block), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    out_shape = jax.ShapeDtypeStruct((f.NLIMB, batch), jnp.float32)

    base = [np.ascontiguousarray(t.T) for t in ed.BASE_TABLE]  # (16, 32)
    x, y, z, t = pl.pallas_call(
        _ladder_kernel,
        grid=grid,
        in_specs=[digit_spec] * 2
        + [shared_spec] * 3
        + [const_spec]
        + [elem_spec] * 2,
        out_specs=[elem_spec] * 4,
        out_shape=[out_shape] * 4,
        scratch_shapes=[pltpu.VMEM((16, f.NLIMB, block), jnp.float32)] * 4,
        interpret=interpret,
    )(s_digits, h_digits, *base, ed.D2, x_neg, a_y)
    return x, y, z, t


_CHAINS = {"invert": f.invert, "pow2523": f.pow2523}


def _chain_kernel(z_ref, out_ref, *, power):
    with f.mosaic_safe():
        out_ref[:] = power(z_ref[:])


@functools.partial(jax.jit, static_argnames=("tail", "interpret"))
def chain_pallas(z, tail: str, interpret: bool = False):
    """(32, B) element -> z^(2^255-21) (`tail="invert"`) or z^(2^252-3)
    (`"pow2523"`): field.py's addition chain, all ~265 squarings and
    multiplications of it VMEM-resident in one grid program per BLOCK
    lanes, where the jnp form is sixteen `while` loops whose every
    iteration goes through HBM.

    Input bound: `f.mul`/`f.sqr`'s (limbs <= 700). Both callers hand it a
    `f.mul` output (u*v^7 in decompress, the ladder's Z in compress), i.e.
    normalized limbs <= ~295. 0 -> 0, so an invalid key's lane flows on.
    `interpret` is for the tests, as in `ladder_pallas`."""
    batch = z.shape[1]
    assert batch % BLOCK == 0, f"batch {batch} must be a multiple of {BLOCK}"
    spec = pl.BlockSpec(
        (f.NLIMB, BLOCK), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    return pl.pallas_call(
        functools.partial(_chain_kernel, power=_CHAINS[tail]),
        grid=(batch // BLOCK,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(z.shape, jnp.float32),
        interpret=interpret,
        name=f"chain_pallas_{tail}",
    )(z)


pow2523_pallas = functools.partial(chain_pallas, tail="pow2523")
invert_pallas = functools.partial(chain_pallas, tail="invert")


def _verify_kernel_pallas(a_y, a_sign, r_enc, s_digits, h_digits):
    """Full verification with the ladder in pallas; same contract as
    ed._verify_kernel_w4. The scopes are metadata only: stable names for
    the program's stages in a device trace, whatever the HLO ops are called."""
    with jax.named_scope("decompress"):
        x_a, xneg_a, valid = ed.decompress(a_y, a_sign, pow2523=pow2523_pallas)
    with jax.named_scope("ladder"):  # the -A table is the kernel's prologue
        result = ladder_pallas(s_digits, h_digits, xneg_a, a_y)
    with jax.named_scope("compress"):
        enc = ed.compress(result, invert=invert_pallas)
    return valid & jnp.all(enc == r_enc, axis=0)


def _verify_kernel_pallas_packed128(packed):
    """(128, B) u8 wire array (see ed.prepare_batch_packed) -> (B,) bool."""
    return _verify_kernel_pallas(
        *ed.unpack_packed_inputs(*ed.split_packed128(packed))
    )


def _verify_kernel_pallas_packed128_dh(packed):
    """Device-hash wire format: rows 96-127 are the 32-byte message; h is
    computed on device (ops.sha512) in plain jnp around the pallas ladder."""
    with jax.named_scope("unpack"):
        inputs = ed.unpack_packed_inputs_dh(packed)
    return _verify_kernel_pallas(*inputs)


# The chip's generic pair, named and tabled as ops/ed25519.py's KERNELS /
# PROGRAMS are.
KERNELS = {
    "pallas_p128": _verify_kernel_pallas_packed128,
    "pallas_p128dh": _verify_kernel_pallas_packed128_dh,
}
PROGRAMS = {name: jax.jit(fn) for name, fn in KERNELS.items()}
