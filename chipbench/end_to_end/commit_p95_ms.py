"""95th percentile over ALL samples due in the window of commit time minus
due time; a sample that never committed counts with its wait to the end."""
from chipbench import arith


def read(src):
    lat, _failed = arith.sample_latencies(src)
    v = arith.percentile(lat, 0.95)
    return None if v is None else 1000.0 * v
