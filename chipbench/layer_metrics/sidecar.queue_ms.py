"""Submit to dequeue in the sidecar's scheduler, per workload group, mean of
the window (`scheduler.queue_mempool_s`)."""
from chipbench import spans


def read(src):
    return spans.window_mean_ms(src, "sidecar", "scheduler.queue_mempool_s")
