"""Signatures the device checked over those the nodes (and the probe) sent
to the sidecar in the window; the rest its verified-signature cache answered
or, under the crossover, its own CPU."""
from chipbench import arith


def read(src):
    dev, sent = arith.backend_delta(src, "tpu_sigs"), arith.remote_sigs(src)
    if dev is None or not sent:
        return None
    return 100.0 * dev / sent
