"""The readback worker's block on one chunk's mask, the program's run included,
mean of the window (`verifier.readback_s`)."""
from chipbench import spans


def read(src):
    return spans.window_mean_ms(src, "sidecar", "verifier.readback_s")
