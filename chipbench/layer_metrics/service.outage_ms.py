"""Time without service: the median, pooled over the live nodes, of the
stretches between two consecutive block commits of a node that are longer
than `timeout_delay` and overlap the window (`arith.outages`, the nodes'
`Committed B` lines on the host's clock). With one member dead each
rotation of the leader holds one, about two timeouts long. None where the
window holds no such stretch (a committee with no fault)."""
from chipbench import arith


def read(src):
    v = arith.median([b - a for a, b, _t in arith.outages(src)])
    return None if v is None else 1000.0 * v
