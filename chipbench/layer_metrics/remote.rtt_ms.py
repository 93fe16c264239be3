"""One round trip of a node's request to the sidecar, `sendall` to mask received,
mean of the window over all nodes (`crypto.remote_rtt_s`)."""
from chipbench import spans


def read(src):
    return spans.window_mean_ms(src, "nodes", "crypto.remote_rtt_s")
