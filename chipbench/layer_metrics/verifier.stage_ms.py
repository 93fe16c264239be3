"""Host staging of one chunk (pack the wire rows, pad into the pooled buffer),
mean of the window (`verifier.stage_s`)."""
from chipbench import spans


def read(src):
    return spans.window_mean_ms(src, "sidecar", "verifier.stage_s")
