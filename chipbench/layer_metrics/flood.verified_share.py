"""Of all the workload verifications the window's payloads made due, on every
node, the share that was done and not skipped: the factor `verified_tx_per_s`
multiplies committed throughput by (`arith.verified_share`).

`better: higher` holds at a FIXED offer, where the share rises and falls with
`verified_tx_per_s`. Its level is a property of the cell, not of the program:
over 85 % the offer has stopped flooding the verify plane and the judged
number is the offer's (README, "When a flood cell is re-anchored"); a rise
toward 100 % across a change of the offer is no gain."""
from chipbench import arith


def read(src):
    share = arith.verified_share(src)
    return None if share is None else 100.0 * share
