"""Median propose-to-commit over blocks a node created in the window and
committed itself (`Created` and `Committed` lines of the same log)."""
from chipbench import arith


def read(src):
    w = src["window"]
    out = []
    for node in src["nodes"]:
        done = {}
        for t, _r, d in node["blocks"]:
            done.setdefault(d, t)
        for t, _r, d in node["created"]:
            if w["t0"] <= t < w["t1"] and d in done:
                out.append(done[d] - t)
    v = arith.median(out)
    return None if v is None else 1000.0 * v
