"""The least time the chip could take for the REAL signatures it checked in
the window (pad lanes are no work), over the device time of the verify
programs dispatched in the window: both counts are between the same two
snapshots of the sidecar, the time of one program is the trace's median.
See chipbench/roofline.py."""
from chipbench import arith, roofline


def read(src):
    ps, dev = arith.program_seconds(src), src.get("device")
    sigs = arith.backend_delta(src, "tpu_sigs")
    if ps is None or not dev or not sigs or sigs <= 0:
        return None
    return 100.0 * roofline.least_seconds(sigs, dev["kind"]) / ps[0]
