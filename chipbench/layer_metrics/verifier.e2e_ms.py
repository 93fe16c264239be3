"""Median of the sidecar's `verifier.e2e_s` histogram (host clock around one
verifier call). The histogram runs from boot, warm-up included."""


def read(src):
    snaps = src["sidecar"]["snapshots"]
    if not snaps:
        return None
    h = snaps[-1][1]["histograms"].get("verifier.e2e_s")
    if not h or not h["count"]:
        return None
    return 1000.0 * h["p50"]
