"""Share of the window's offer that the nodes' front ports shed (drop-oldest
admission control, `mempool.front_dropped`): of the transactions due in the
window that no node had committed when the run ended, those that the counter
of their node explains (`judge._attempted_failed`). A flood does not count
them as attempted; they are what a burst after a stall costs."""
from chipbench import arith


def read(src):
    offered = arith.attempted(src)
    return None if not offered or "shed_at_front" not in src else 100.0 * src["shed_at_front"] / offered
