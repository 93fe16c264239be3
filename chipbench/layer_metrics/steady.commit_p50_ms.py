"""Median over ALL samples due in the window of commit time minus due time.
Not judged: over six runs it spreads by 19-27 % of itself (PERF.md), far
more than the 95th percentile does."""
from chipbench import arith


def read(src):
    lat, _failed = arith.sample_latencies(src)
    v = arith.percentile(lat, 0.50)
    return None if v is None else 1000.0 * v
