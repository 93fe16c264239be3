"""Reads what a run left behind into one `sources` dict: the load
generator's records, the nodes' and the sidecar's logs (commit, payload and
METRICS lines), the shim's replies. Metric readers and the judge take only
this dict; none of them opens a file of its own.
"""

from __future__ import annotations

import calendar
import json
import os
import re

_LINE = re.compile(r"^\[(\d{4})-(\d\d)-(\d\d)T(\d\d):(\d\d):(\d\d)\.(\d{3})Z (\w+) ([\w.]+)\] (.*)$")
_COMMIT = re.compile(r"^Committed B(\d+)\((\S+?)\)(?: -> (\S+))?$")
_CREATED = re.compile(r"^Created B(\d+)\((\S+?)\)$")
_PAYLOAD = re.compile(r"^Payload (\S+) contains (\d+) B$")
_SAMPLE = re.compile(r"^Payload (\S+) contains sample tx (\d+)$")
_SHED = re.compile(r"^payload maker shedding: (\d+) transactions dropped")
_VERIFY = re.compile(r"^Verifying (OWN|OTHER) transaction batch\. Size: (\d+)$")
_TIMEOUT = re.compile(r"^Timeout reached for round (\d+)$")


def _stamp(m) -> float:
    y, mo, d, h, mi, s, ms = (int(m.group(i)) for i in range(1, 8))
    return calendar.timegm((y, mo, d, h, mi, s)) + ms / 1000.0


def parse_log(path: str) -> dict:
    out = {
        "blocks": [],  # (t, round, block digest)
        "payload_commits": [],  # (t, round, payload digest)
        "created": [],  # (t, round, block digest)
        "timeouts": [],  # (t, round): the pacemaker fired (`Timeout reached`)
        "own_payloads": {},  # payload digest -> bytes
        "samples": {},  # sample id -> payload digest
        "verify": [],  # (t, kind, sigs)
        "snapshots": [],  # (t, METRICS object)
        "verify_failed": 0,
        "maker_shed": 0,  # by the payload maker's warning (every 10,000th): no counter
        "warnings": 0,
        "errors": [],
    }
    try:
        f = open(path, errors="replace")
    except OSError:
        return out
    with f:
        for line in f:
            m = _LINE.match(line.rstrip("\n"))
            if not m:
                continue
            level, msg = m.group(8), m.group(10)
            if level == "WARNING":
                out["warnings"] += 1
                k = _SHED.match(msg)
                if k:
                    out["maker_shed"] = int(k.group(1))
                k = _TIMEOUT.match(msg)
                if k:
                    out["timeouts"].append((_stamp(m), int(k.group(1))))
            elif level in ("ERROR", "CRITICAL"):
                out["errors"].append(msg[:200])
                if "synthetic batch verification failed" in msg:
                    out["verify_failed"] += 1
            c = msg[0] if msg else ""
            if c == "C":
                k = _COMMIT.match(msg)
                if k:
                    if k.group(3):
                        out["payload_commits"].append((_stamp(m), int(k.group(1)), k.group(3)))
                    else:
                        out["blocks"].append((_stamp(m), int(k.group(1)), k.group(2)))
                    continue
                k = _CREATED.match(msg)
                if k:
                    out["created"].append((_stamp(m), int(k.group(1)), k.group(2)))
            elif c == "P":
                k = _SAMPLE.match(msg)
                if k:
                    out["samples"][int(k.group(2))] = k.group(1)
                    continue
                k = _PAYLOAD.match(msg)
                if k:
                    out["own_payloads"][k.group(1)] = int(k.group(2))
            elif c == "V":
                k = _VERIFY.match(msg)
                if k:
                    out["verify"].append((_stamp(m), k.group(1), int(k.group(2))))
            elif c == "M" and msg.startswith("METRICS "):
                try:
                    out["snapshots"].append((_stamp(m), json.loads(msg[8:])))
                except ValueError:
                    pass
    return out


def read_records(path: str) -> list:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def read_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def gather(work: str, n: int) -> dict:
    logs = os.path.join(work, "logs")
    return {
        "nodes": [parse_log(os.path.join(logs, f"node-{i}.log")) for i in range(n)],
        "sidecar": parse_log(os.path.join(logs, "sidecar.log")),
        "trace_done": read_json(os.path.join(work, "trace.done")),
        "device": read_json(os.path.join(work, "device.json")),
        "trace": read_json(os.path.join(work, "trace.reduced.json")),
    }


# -- snapshots: counts inside the window -----------------------------------


def bracket(snapshots, t0: float, t1: float):
    """The last snapshot at or before t0 and the last at or before t1; None
    where the log has none (then the window cannot be read)."""
    first = last = None
    for t, obj in snapshots:
        if t <= t0:
            first = obj
        if t <= t1:
            last = obj
    return first, last


def bracket_seconds(snapshots, t0: float, t1: float):
    """Seconds between the two snapshots that `bracket` picks: what a count
    between them has to be divided by to be a rate (the snapshots come once
    a second and do not fall on the window's edges)."""
    first = last = None
    for t, _obj in snapshots:
        if t <= t0:
            first = t
        if t <= t1:
            last = t
    if first is None or last is None or last <= first:
        return None
    return last - first


def counter_delta(snapshots, t0, t1, name: str):
    a, b = bracket(snapshots, t0, t1)
    if a is None or b is None:
        return None
    return b["counters"].get(name, 0) - a["counters"].get(name, 0)


def hist_sum_delta(snapshots, t0, t1, name: str):
    a, b = bracket(snapshots, t0, t1)
    if a is None or b is None:
        return None
    ha, hb = a["histograms"].get(name), b["histograms"].get(name)
    if ha is None or hb is None:
        return None
    return hb["sum"] - ha["sum"]
