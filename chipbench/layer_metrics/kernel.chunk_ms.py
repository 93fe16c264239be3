"""Median device time of one verify program (one chunk) in the trace."""


def read(src):
    tr = src.get("trace")
    if not tr or not tr.get("program_ms"):
        return None
    return tr["program_ms"]["median"]
