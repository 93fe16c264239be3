"""Share of the signatures arriving at the sidecar in the window whose request
stayed columnar: one array from the socket read to the staging buffer, no
Python object a signature (`sidecar.columnar_sigs` over
`sidecar.request_sigs`, between the two METRICS snapshots that bracket the
window). A program older than the counter reads 0; None where the snapshots
do not bracket the window or no signature arrived."""
from chipbench import collect


def read(src):
    w = src["window"]
    snapshots = src["sidecar"]["snapshots"]
    sigs = collect.counter_delta(snapshots, w["t0"], w["t1"], "sidecar.request_sigs")
    columnar = collect.counter_delta(snapshots, w["t0"], w["t1"], "sidecar.columnar_sigs")
    return 100.0 * columnar / sigs if sigs else None
