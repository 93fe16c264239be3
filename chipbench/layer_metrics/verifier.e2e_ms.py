"""One verifier call on the host's clock, prepare to mask in hand, mean of the
window (`verifier.e2e_s`). From PR 28 on; through PR 27 this read the
histogram's p50 since boot, warm-up included."""
from chipbench import spans


def read(src):
    return spans.window_mean_ms(src, "sidecar", "verifier.e2e_s")
