"""How long a workload batch held one of its node's 8 pipeline slots, mean of
the window over all nodes (`mempool.verify_rtt_s`): slots x batch over this
is the plateau of the verified throughput."""
from chipbench import spans


def read(src):
    return spans.window_mean_ms(src, "nodes", "mempool.verify_rtt_s")
