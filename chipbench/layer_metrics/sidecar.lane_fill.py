"""Real signatures over lanes dispatched: device signatures of the window
over chunks dispatched times the chunk's width."""
from chipbench import arith


def read(src):
    dev = arith.backend_delta(src, "tpu_sigs")
    chunks = arith.backend_delta(src, "dispatched")
    if dev is None or not chunks:
        return None
    return 100.0 * dev / (chunks * src["config"]["sidecar"]["chunk"])
