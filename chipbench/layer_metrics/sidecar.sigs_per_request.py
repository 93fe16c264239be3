"""Signatures in one request as the sidecar parsed it, mean of the window
(`sidecar.request_sigs` over `sidecar.requests`, between the two METRICS
snapshots that bracket the window; the probe's requests of 320 among them):
what the sidecar's per-request cost (`sidecar.loop_us_per_sig`) is spread
over. None where the snapshots do not bracket the window or no request
arrived in it."""
from chipbench import arith


def read(src):
    sigs = arith.sidecar_delta(src, "sidecar.request_sigs")
    requests = arith.sidecar_delta(src, "sidecar.requests")
    return sigs / requests if requests else None
