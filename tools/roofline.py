"""Arithmetic cost model + roofline for the ed25519 verification kernel.

Counts f32 VPU ops per signature for the w4 windowed ladder
(ops/ed25519._verify_kernel_w4 path) from the field-op formulas in
ops/field.py, then relates the measured device rate to the implied
op throughput and the chip's VPU/MXU ceilings.

The model counts every f32 scalar op (mul, add, sub, floor, select,
compare) as 1 op — the VPU issues them at the same rate — and is derived
directly from the source structure:

  field.mul : 32x32 schoolbook conv (1024 mul + 992 add) +
              _reduce_512 (3 no-wrap carry passes over 66 rows, fold,
              _carry32 = 3 wrap passes over 32 rows)
  field.sqr : symmetric conv (~528 mul + ~528 add) + same reduction
  field.sub : add bias + _carry32
  dbl       : 4 sqr + 4 mul + 1 add + 3 sub + 2 small adds
  madd      : 7 mul + 2 add + 2 sub + small
  cached add: 8 mul + 2 add + 2 sub + small

Usage: python tools/roofline.py [--rate SIGS_PER_SEC]
"""

from __future__ import annotations

import argparse

# --- per-op costs (f32 scalar ops per batch lane) --------------------------

CARRY_PASS_66 = 66 * 4  # hi=floor(c/256): mul+floor; lo: mul+sub; merge add
CARRY_PASS_32 = 32 * 4
REDUCE_512 = 3 * CARRY_PASS_66 + (32 * 2 + 4) + 3 * CARRY_PASS_32  # fold+carries

MUL = 1024 + 992 + REDUCE_512  # conv + reduction
SQR = 528 + 528 + 32 + REDUCE_512  # sym conv (+a2) + reduction
ADD = 32
SUB = 32 + 32 + 3 * CARRY_PASS_32  # +bias, -b, carry

SEQ_CARRY = 32 * 6  # fori: index, add, floor-mul, sub, update, carry
CANONICAL = 3 * SEQ_CARRY + 2 * (32 + SEQ_CARRY + 32)  # 3 passes + 2 cond-sub

DBL = 4 * SQR + 4 * MUL + 1 * ADD + 3 * SUB + 2 * ADD
# T-skip schedule (round 4): a doubling feeding another doubling skips the
# T-coordinate mul (3 of 4 per group), as does the group-final cached add.
DBL_NO_T = DBL - MUL
MADD = 7 * MUL + 2 * ADD + 2 * SUB + 2 * ADD
CADD = 8 * MUL + 2 * ADD + 2 * SUB + 2 * ADD
CADD_NO_T = CADD - MUL

# pow chains (ref10): ~254 squarings + ~12 muls each
POW_CHAIN = 254 * SQR + 12 * MUL

# --- kernel phases ---------------------------------------------------------

NGROUPS, WINDOW = 64, 4

LOOKUP_SHARED = 3 * 16 * 32 * 2  # 3 tables x 16 masked fma rows
LOOKUP_ITEM = 4 * 16 * 32 * 2
DIGIT_ROW = 2 * 64 * 3

LADDER = NGROUPS * (
    (WINDOW - 1) * DBL_NO_T
    + DBL
    + MADD
    + CADD_NO_T
    + LOOKUP_SHARED
    + LOOKUP_ITEM
    + DIGIT_ROW
)
TABLE_BUILD = 14 * MADD + 3 * MUL + 4 * ADD  # _build_neg_a_table
DECOMPRESS = (
    POW_CHAIN + 5 * MUL + 3 * SQR + 2 * SUB + 2 * ADD + 4 * CANONICAL + 200
)
COMPRESS = POW_CHAIN + 2 * MUL + 2 * CANONICAL + 64  # invert + encode
SHA_MODL = 12_000  # device-hash: ~80 rounds x ~60 u32 ops + limb folds

TOTAL = LADDER + TABLE_BUILD + DECOMPRESS + COMPRESS + SHA_MODL

# --- chip ceilings (TPU v5e, public figures) -------------------------------
# MXU: 197 TFLOP/s bf16. VPU: 8 sublanes x 128 lanes x 4 ALUs x 1.67 GHz
# x 2 (FMA counted as 2) ~= 13.7 T f32 op/s; non-FMA ops issue at half
# that, so a realistic mixed-op ceiling is ~7-13 T op/s.

V5E_VPU_OPS = 8 * 128 * 4 * 1.67e9  # 6.8e12 single-op issue rate
V5E_MXU_BF16 = 197e12


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--rate",
        type=float,
        required=True,
        help="device sigs/s measured on the chip for today's code (PERF.md "
        "says where the current figure comes from; none is assumed)",
    )
    args = ap.parse_args()

    rows = [
        ("ladder (256 dbl + 64+64 adds)", LADDER),
        ("per-item table build", TABLE_BUILD),
        ("decompress (sqrt chain)", DECOMPRESS),
        ("compress (invert chain)", COMPRESS),
        ("sha512 + mod L (device hash)", SHA_MODL),
    ]
    print(f"{'phase':<34}{'f32 ops/sig':>14}{'share':>9}")
    for name, ops in rows:
        print(f"{name:<34}{ops:>14,}{ops / TOTAL:>8.1%}")
    print(f"{'TOTAL':<34}{TOTAL:>14,}")
    print()
    tput = args.rate * TOTAL
    print(f"measured rate:        {args.rate:>12,.0f} sigs/s")
    print(f"implied op throughput:{tput / 1e12:>12.2f} T f32 op/s")
    print(
        f"VPU issue ceiling:    {V5E_VPU_OPS / 1e12:>12.2f} T op/s "
        f"-> {tput / V5E_VPU_OPS:.1%} of VPU"
    )
    print(
        f"MXU bf16 ceiling:     {V5E_MXU_BF16 / 1e12:>12.2f} TFLOP/s "
        f"-> {tput / V5E_MXU_BF16:.2%} of MXU (structurally idle: exact "
        f"integer limb products)"
    )
    print(
        "\nheadroom notes: VPU utilization below ~50% is scheduling/"
        "fusion slack, not arithmetic necessity; the 8-bit limb radix is "
        "forced by f32-exact accumulation (k*2^(2b) < 2^24), so fewer-"
        "limb variants need int32 (v5e int ops run at reduced rate) or "
        "pair-wise f32 accumulators (~2x op count per product)."
    )


if __name__ == "__main__":
    main()
