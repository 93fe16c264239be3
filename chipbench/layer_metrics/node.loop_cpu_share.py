"""Share of one core that a node's event-loop thread used in the window
(`runtime.loop_cpu_s`), busiest node."""
from chipbench import spans


def read(src):
    return spans.loop_cpu_share(src, "nodes")
