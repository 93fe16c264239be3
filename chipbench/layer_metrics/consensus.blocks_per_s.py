"""Blocks committed in the window per second, mean over the nodes."""


def read(src):
    w = src["window"]
    per = [
        sum(1 for t, _r, _d in node["blocks"] if w["t0"] <= t < w["t1"])
        for node in src["nodes"]
    ]
    return None if not per or not sum(per) else sum(per) / len(per) / w["seconds"]
