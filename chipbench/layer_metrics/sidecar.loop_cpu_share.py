"""Share of one core that the sidecar's event-loop thread used in the window
(`runtime.loop_cpu_s`): near 100 % that one Python thread is the limit."""
from chipbench import spans


def read(src):
    return spans.loop_cpu_share(src, "sidecar")
