"""Timeout certificates a live node assembled a second in the window, the
live nodes' mean (counter `consensus.tcs`, `consensus/aggregator.py`, between
the METRICS snapshots that bracket the window): the view changes that moved
the committee past a round that no QC ended."""
from chipbench import spans


def read(src):
    return spans.counter_rate(src, "nodes", "consensus.tcs")
