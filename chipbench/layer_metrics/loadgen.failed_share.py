"""Share of the window's samples that their client's node never committed."""
from chipbench import arith


def read(src):
    lat, failed = arith.sample_latencies(src)
    return None if not lat else 100.0 * failed / len(lat)
