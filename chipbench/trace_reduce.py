"""From a profiler trace (`.xplane.pb`) to the numbers the per-layer metrics
read. Runs as a process of its own, after the sidecar has let go of the
chip, with JAX held to the CPU: it only parses a file.

    python -m chipbench.trace_reduce <trace-dir> <out.json> [--summary out.txt]

On a TPU the device is the plane `/device:TPU:0`. Its line `XLA Ops` holds
one event per device operation and `XLA Modules` one per program run; busy
time is the union of the op intervals, a program's time the duration of its
module event. Without a TPU plane (a CPU rehearsal) the host plane's XLA
lines stand in, which says nothing about a device and is named `cpu` by the
harness.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from collections import defaultdict


def find_xplane(trace_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return paths[-1] if paths else None


def union_seconds(intervals) -> tuple[float, list[tuple[float, float]]]:
    """(seconds covered, merged intervals) of (start_ns, end_ns) pairs."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged) / 1e9, [(a, b) for a, b in merged]


def _quantile(sorted_vals, q):
    import math

    return sorted_vals[max(0, math.ceil(q * len(sorted_vals)) - 1)]


def short_name(hlo: str) -> str:
    """`%while.6301 = (s32[]...) while(...)` -> `while.6301`: an op's event
    name is its whole HLO line, thousands of characters for a wide tuple."""
    return hlo.split(" = ", 1)[0].lstrip("%")[:80]


def load(path: str):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def plane_lines(plane) -> dict:
    """line name -> list of (name, start_ns, duration_ns)."""
    out = defaultdict(list)
    for line in plane.lines:
        for ev in line.events:
            out[line.name].append((ev.name, ev.start_ns, ev.duration_ns))
    return out


def reduce(data) -> dict:
    planes = list(data.planes)
    device = [p for p in planes if p.name.startswith("/device:TPU:")]
    # Only the first core's plane: one chip, one plane (the check below the
    # call names what else the trace held).
    on_tpu = bool(device)
    chosen = sorted(device, key=lambda p: p.name)[:1] if on_tpu else [
        p for p in planes if p.name == "/host:CPU"
    ]
    out = {
        "planes": [p.name for p in planes],
        "device_plane": chosen[0].name if chosen else None,
        "on_tpu": on_tpu,
    }
    if not chosen:
        return out
    lines = plane_lines(chosen[0])
    out["lines"] = {k: len(v) for k, v in lines.items()}
    if on_tpu:
        ops = lines.get("XLA Ops") or [e for k, v in lines.items() if "Ops" in k for e in v]
        mods = lines.get("XLA Modules") or []
    else:
        ops = [e for k, v in lines.items() if k.startswith("tf_XLA") for e in v]
        mods = [e for e in ops if e[0] == "ThunkExecutor::Execute"]
    if not ops:
        return out
    busy_s, merged = union_seconds((s, s + d) for _n, s, d in ops if d > 0)
    out["busy_s"] = busy_s
    out["span_s"] = (merged[-1][1] - merged[0][0]) / 1e9 if merged else 0.0
    by_op = defaultdict(float)
    for n, _s, d in ops:
        by_op[short_name(n)] += d / 1e9
    out["device_ops"] = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(
        ((merged[i + 1][0] - merged[i][1]) / 1e9 for i in range(len(merged) - 1)),
        reverse=True,
    )
    out["idle_gaps"] = [["unattributed", g] for g in gaps[:10]]
    out["gap_count"] = len(gaps)
    if mods:
        by_mod = defaultdict(list)
        for n, _s, d in mods:
            by_mod[n.split("(")[0]].append(d / 1e6)
        name, runs = max(by_mod.items(), key=lambda kv: sum(kv[1]))
        runs.sort()
        out["program_ms"] = {
            "name": name,
            "count": len(runs),
            "median": _quantile(runs, 0.5),
            "p95": _quantile(runs, 0.95),
        }
        out["program_s"] = sum(d for _n, _s, d in mods) / 1e9
        out["modules"] = {k: [len(v), sum(v) / 1e3] for k, v in by_mod.items()}
    return out


def stretch_seconds(clocked_s: float, reduced: dict) -> float:
    """The traced stretch's length, `device.window_s` of the result line. The
    profiler records device operations from a little before the shim's clock
    says the stretch began to a little after it says it ended (0.4048 s of
    operations in a stretch clocked at 0.4001 s, PR 28), so the stretch is at
    least as long as from the first recorded operation's start to the last
    one's end: `busy_s` can never read over it."""
    return max(clocked_s, reduced.get("span_s", 0.0))


def summary_text(data, top: int = 12) -> str:
    rows = []
    for p in data.planes:
        rows.append(f"plane {p.name!r}")
        for line in p.lines:
            evs = list(line.events)
            by = defaultdict(lambda: [0, 0.0])
            for ev in evs:
                by[ev.name][0] += 1
                by[ev.name][1] += ev.duration_ns / 1e6
            rows.append(f"  line {line.name!r}: {len(evs)} events, {len(by)} names")
            for n, (c, ms) in sorted(by.items(), key=lambda kv: -kv[1][1])[:top]:
                rows.append(f"    {ms:12.3f} ms {c:7d} x {n[:120]}")
    return "\n".join(rows) + "\n"


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    trace_dir, out_path = argv[0], argv[1]
    path = find_xplane(trace_dir)
    if path is None:
        print(f"no .xplane.pb under {trace_dir}", file=sys.stderr)
        return 1
    data = load(path)
    out = reduce(data)
    out["xplane_bytes"] = os.path.getsize(path)
    with open(out_path, "w") as f:
        json.dump(out, f)
    if "--summary" in argv:
        with open(argv[argv.index("--summary") + 1], "w") as f:
            f.write(summary_text(data))
    return 0


if __name__ == "__main__":
    sys.exit(main())
