"""95th percentile of send time minus due time over the window's bursts."""
from chipbench import arith


def read(src):
    late = [max(0.0, r[3] - r[2]) for r in arith.window_records(src)]
    v = arith.percentile(late, 0.95)
    return None if v is None else 1000.0 * v
