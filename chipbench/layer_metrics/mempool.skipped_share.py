"""Workload signatures skipped over skipped plus verified, worst node."""
from chipbench import arith


def read(src):
    shares = arith.verified_shares(src)
    if not shares or any(s is None for s in shares):
        return None
    return 100.0 * (1.0 - min(shares))
