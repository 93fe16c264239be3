"""Committed transactions of the window per second, times the share of all
the workload signature verifications they made due, over all nodes, that
were done and not skipped under load (`arith.verified_share`)."""
from chipbench import arith


def read(src):
    return arith.verified_tx_per_s(src)
