"""Share of the window in which no verify program ran on the device: 1 -
(chunks dispatched in the window x the trace's median program time) over the
seconds the count spans. The traced stretch alone (0.4 s, `device.busy_s` and
`window_s` of the result line) holds ten program runs or so and read 1.5 to
50 % within one cell; the count is over the whole window."""
from chipbench import arith


def read(src):
    ps = arith.program_seconds(src)
    if ps is None:
        return None
    return 100.0 * (1.0 - ps[0] / ps[1])
