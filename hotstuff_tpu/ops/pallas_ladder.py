"""Pallas TPU kernel for the windowed double-scalar-mult ladder.

The jnp ladder (ops.ed25519._verify_kernel_w4) leaves XLA to schedule ~3.5k
field mults as separate HBM-roundtripping fusions per fori iteration. This
kernel runs the whole 64-group ladder VMEM-resident: one grid program per
256-lane batch block holds the accumulator point, both digit arrays and the
16-entry tables (shared k*B and per-item k*(-A)) on-chip for all 256
doubling steps — the only HBM traffic is the initial block load and the
final point store.

All arithmetic is ops.field on (32, BLOCK) f32 limb vectors (exact-integer
f32, see field.py). Table lookups are unrolled masked sums over the 16
entries (VPU fma chains — no gathers, which TPUs do poorly). Digit rows are
selected by an iota-mask reduction instead of dynamic slicing (supported +
cheap: 64xBLOCK fma per group).

The two fixed-exponent chains around the ladder (decompress's square root,
compress's 1/Z: ~265 field multiplications each) run in a second kernel,
`chain_pallas`, for the same reason. They run once per batch, not per
ladder step, and were first left in plain jnp as "~15% of total work";
the device trace said otherwise: of 39.9 ms a 4096-lane program the ladder
took 17.4 ms and the chains' sixteen jnp `while` loops 16.5 ms, 33 us an
iteration against 6.3 us a multiplication in here (PERF.md §6, PR 30, has
the program by device op before and after). Table construction, SHA-512
and the canonical reductions stay in plain jnp around the two kernels.
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

# Lanes per grid program (multiple of 128), of both kernels. One chain over
# 4096 lanes, alone on a v5e with the host's dispatch: 1.9 / 1.9 / 2.2 /
# 2.7 ms at 128 / 256 / 512 / 1024 lanes a program (a (66, B) product
# outgrows the 64 vregs past 256), VMEM exhausted at 2048 (PR 30).
BLOCK = 256


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


def _lookup_item(table: jnp.ndarray, digit: jnp.ndarray) -> jnp.ndarray:
    """table (16, 32, B) per-item, digit (B,) -> (32, B)."""
    acc = jnp.zeros(table.shape[1:], jnp.float32)
    for e in range(16):
        m = (digit == e).astype(jnp.float32)
        acc = acc + table[e] * m[None, :]
    return acc


def _ladder_kernel(
    sd_ref,
    hd_ref,
    bypx_ref,
    bymx_ref,
    bxy2d_ref,
    ta_ypx_ref,
    ta_ymx_ref,
    ta_z_ref,
    ta_t2d_ref,
    x_out,
    y_out,
    z_out,
    t_out,
):
    sd = sd_ref[:]
    hd = hd_ref[:]
    b_ypx, b_ymx, b_xy2d = bypx_ref[:], bymx_ref[:], bxy2d_ref[:]
    ta_ypx, ta_ymx, ta_z, ta_t2d = (
        ta_ypx_ref[:],
        ta_ymx_ref[:],
        ta_z_ref[:],
        ta_t2d_ref[:],
    )

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
            acc,
            _lookup_item(ta_ypx, hdg),
            _lookup_item(ta_ymx, hdg),
            _lookup_item(ta_z, hdg),
            _lookup_item(ta_t2d, hdg),
            with_t=False,
        )
        return acc

    with f.mosaic_safe():
        X, Y, Z, T = lax.fori_loop(
            0, ed.NGROUPS, group, ed.point_identity(sd.shape[1])
        )
    x_out[:] = X
    y_out[:] = Y
    z_out[:] = Z
    t_out[:] = T


@functools.partial(jax.jit, static_argnames=("interpret",))
def ladder_pallas(
    s_digits, h_digits, ta_ypx, ta_ymx, ta_z, ta_t2d, interpret: bool = False
):
    """(64,B) digits + per-item tables (16,32,B) -> ladder result Point.
    `interpret` runs the kernel body in the Pallas interpreter (the only
    way it executes without a TPU; the tests pass it, nothing else does)."""
    batch = s_digits.shape[1]
    assert batch % BLOCK == 0, f"batch {batch} must be a multiple of {BLOCK}"
    grid = (batch // BLOCK,)

    digit_spec = pl.BlockSpec(
        (ed.NGROUPS, BLOCK), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    shared_spec = pl.BlockSpec(
        (16, f.NLIMB), lambda i: (0, 0), memory_space=pltpu.VMEM
    )
    item_spec = pl.BlockSpec(
        (16, f.NLIMB, BLOCK), lambda i: (0, 0, i), memory_space=pltpu.VMEM
    )
    out_spec = pl.BlockSpec(
        (f.NLIMB, BLOCK), lambda i: (0, i), memory_space=pltpu.VMEM
    )
    out_shape = jax.ShapeDtypeStruct((f.NLIMB, batch), jnp.float32)

    base = [np.ascontiguousarray(t.T) for t in ed.BASE_TABLE]  # (16, 32)
    x, y, z, t = pl.pallas_call(
        _ladder_kernel,
        grid=grid,
        in_specs=[digit_spec, digit_spec] + [shared_spec] * 3 + [item_spec] * 4,
        out_specs=[out_spec] * 4,
        out_shape=[out_shape] * 4,
        interpret=interpret,
    )(s_digits, h_digits, *base, ta_ypx, ta_ymx, ta_z, ta_t2d)
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
    with jax.named_scope("table"):
        ta = ed._build_neg_a_table(xneg_a, a_y)
    with jax.named_scope("ladder"):
        result = ladder_pallas(s_digits, h_digits, *ta)
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
