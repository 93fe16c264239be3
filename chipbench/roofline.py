"""Work of one ed25519 verification, counted from the algorithm and not from
this repo's kernels, and the least time a chip could take for it.

The count is the textbook verification of RFC 8032, [s]B = R + [h]A checked
as compress([s]B - [h]A) == R, in field multiplications over GF(2^255-19):

  decompress A, decompress R      x = u v^3 (u v^7)^((p-5)/8): one power of
                                  2^252-3 by the standard chain (251
                                  squarings, 11 multiplications) and 9
                                  multiplications and squarings around it
                                  (y^2, d y^2, v^3, v^7, u v^3, u v^7, the
                                  product, the check v x^2, the sqrt(-1) fix)
  A + B precomputed once          1 addition
  256-step double-scalar ladder   per step 1 doubling (4 multiplications +
                                  4 squarings) and 1 addition (extended
                                  coordinates: 8 multiplications + 1 by 2d)
  compress the result             1 inversion, z^(p-2): 254 squarings, 11
                                  multiplications; then x/z and y/z

A squaring counts as a multiplication. One field multiplication is the
schoolbook product of two 255-bit numbers in 32 limbs of 8 bits, 32 x 32
multiply-adds, 2 operations each; reduction and carries are not counted, nor
is SHA-512 over (R, A, M). Bytes: 32 message + 32 key + 64 signature in, 1 out.

The peak it is held against is the published bf16 peak, the MXU's. A ladder
on the VPU reads well under 1 % of it; the number is a bound that reads the
same work whatever implements it, not a mark for one kernel.
"""

from __future__ import annotations

import json
import os

POW_2_252_3 = 251 + 11  # squarings + multiplications of the standard chain
DECOMPRESS = POW_2_252_3 + 9
POINT_DOUBLE = 4 + 4
POINT_ADD = 8 + 1
LADDER_STEPS = 256
INVERT = 254 + 11
COMPRESS = INVERT + 2

FIELD_MULS_PER_SIG = (
    2 * DECOMPRESS + POINT_ADD + LADDER_STEPS * (POINT_DOUBLE + POINT_ADD) + COMPRESS
)
LIMBS = 32  # 255 bits in 8-bit limbs
OPS_PER_FIELD_MUL = 2 * LIMBS * LIMBS
OPS_PER_SIG = FIELD_MULS_PER_SIG * OPS_PER_FIELD_MUL
BYTES_PER_SIG = 32 + 32 + 64 + 1


def peaks(device_kind: str) -> dict:
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")
    with open(path) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no published peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def least_seconds(sigs: int, device_kind: str) -> float:
    """The larger of operations over peak FLOP/s and bytes over peak HBM
    rate, for `sigs` real signatures."""
    p = peaks(device_kind)
    return max(
        sigs * OPS_PER_SIG / p["bf16_flops_per_s"],
        sigs * BYTES_PER_SIG / p["hbm_bytes_per_s"],
    )
