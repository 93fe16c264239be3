"""One dispatch's way back on the service's event loop (mask scatter, cache
inserts, futures), mean of the window (`service.scatter_s`)."""
from chipbench import spans


def read(src):
    return spans.window_mean_ms(src, "sidecar", "service.scatter_s")
