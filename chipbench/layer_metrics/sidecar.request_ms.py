"""One request inside the sidecar, body read to reply drained, mean of the
window (`sidecar.request_s`)."""
from chipbench import spans


def read(src):
    return spans.window_mean_ms(src, "sidecar", "sidecar.request_s")
