#!/usr/bin/env python3
"""Live/offline telemetry dashboard: per-node commit rate, lane queueing,
device occupancy, and SLO burn alerts — one renderer for both sources.

    # live: scrape N running nodes (node run --telemetry-port exposes
    # the framed-JSON endpoint)
    python tools/telemetry_dash.py --poll 127.0.0.1:9090,127.0.0.1:9091

    # offline: the same dashboard out of a chaos report's embedded
    # per-node telemetry section (tools/chaos_run.py --report)
    python tools/telemetry_dash.py --report chaos.json

    # scenario-matrix artifact (tools/chaos_run.py --matrix): one row per
    # cell — verdict, commit rate, fleet lane p99s, worst-node occupancy,
    # regression markers against the artifact's recorded baseline
    python tools/telemetry_dash.py --matrix CHAOS_MATRIX_r01.json

    # per-peer network observatory: one row per directed link — RTT
    # EWMA/p50, frames/bytes, backoff drops, and the RTT class inferred
    # from this node's vantage (gap clustering, network/net.py)
    python tools/telemetry_dash.py --report chaos.json --peers

    # incident ledger (utils/incidents.py §5.5r): one row per fault
    # window — attributed alerts, MTTD/MTTR, residual flags — plus the
    # burn-budget rows and any unattributed alerts (report-only: the
    # ledger is a run-level artifact, not a live scrape)
    python tools/telemetry_dash.py --report chaos.json --incidents

    # machine-readable (same normalized records either way)
    python tools/telemetry_dash.py --report chaos.json --json

Both inputs normalize into one per-node record shape before rendering, so
a node scraped live and the same node's section read out of a report show
IDENTICAL numbers (the acceptance contract: a TelemetryServer can serve a
report's telemetry entry verbatim and this tool cannot tell the
difference). Reports without a telemetry section degrade to the
scheduler/commit-times sections, so any chaos report renders something.

Exit codes: 0 = rendered, 2 = a poll target was unreachable, 3 = usage /
unusable input.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def node_record(label: object, dump: dict) -> dict:
    """Normalize one node's telemetry dump (live scrape response or a
    report's `telemetry[<node>]` entry) into the record the renderer
    consumes. Pure function of the dump — the live/offline equivalence
    the harness test pins."""
    snaps = dump.get("snapshots") or []
    span = (
        float(snaps[-1]["t"]) - float(snaps[0]["t"]) if len(snaps) >= 2 else 0.0
    )
    commits = int(dump.get("commits") or 0)
    alerts = list(dump.get("alerts") or [])
    lanes = {
        lane: {
            "count": int(s.get("count", 0)),
            "p50_ms": float(s.get("p50_ms", 0.0)),
            "p99_ms": float(s.get("p99_ms", 0.0)),
        }
        for lane, s in (dump.get("lanes") or {}).items()
    }
    device = dump.get("device") or {}
    return {
        "node": str(dump.get("node") if dump.get("node") is not None else label),
        "snapshots": len(snaps),
        "span_s": round(span, 3),
        "commits": commits,
        "commit_rate": round(commits / span, 3) if span > 0 else 0.0,
        "lanes": lanes,
        "occupancy": device.get("occupancy"),
        "overlap_headroom": device.get("overlap_headroom"),
        "active_alerts": list(dump.get("active_alerts") or []),
        "alerts_fired": sum(1 for a in alerts if a.get("event") == "fired"),
        "alerts_cleared": sum(1 for a in alerts if a.get("event") == "cleared"),
        "alerts": alerts,
    }


def peer_record(label: object, links: dict) -> dict:
    """Normalize one node's per-peer link ledger (a live dump's or chaos
    report's `peers[<node>]` section) into the peer-table record. Pure
    function of the section — the same live/offline equivalence contract
    as node_record. The `rtt_class` column is the per-vantage gap
    clustering (network/net.py rtt_classes) over this node's measured
    EWMAs; links that never closed a probe loop class as '-'."""
    from hotstuff_tpu.network.net import rtt_classes

    rtts = {
        peer: float(snap["rtt_ewma_ms"])
        for peer, snap in (links or {}).items()
        if (snap or {}).get("rtt_ewma_ms") is not None
    }
    classes = rtt_classes(rtts)
    rows = []
    for peer, snap in sorted((links or {}).items()):
        snap = snap or {}
        rows.append(
            {
                "peer": str(peer),
                "rtt_ewma_ms": snap.get("rtt_ewma_ms"),
                "rtt_p50_ms": snap.get("rtt_p50_ms"),
                "rtt_samples": int(snap.get("rtt_samples", 0)),
                "rtt_class": classes.get(peer),
                "frames_sent": int(snap.get("frames_sent", 0)),
                "bytes_sent": int(snap.get("bytes_sent", 0)),
                "backoff_drops": int(snap.get("backoff_drops", 0)),
                "send_failures": int(snap.get("send_failures", 0)),
                "probes_sent": int(snap.get("probes_sent", 0)),
                "pongs_received": int(snap.get("pongs_received", 0)),
            }
        )
    return {
        "node": str(label),
        "links": rows,
        "rtt_classes": max(classes.values()) + 1 if classes else 0,
    }


def peer_records_from_report(report: dict) -> list[dict]:
    """Per-node peer records from a chaos report: the top-level `peers`
    section (chaos/orchestrator.py, present without telemetry), falling
    back to each telemetry dump's embedded `peers`."""
    peers = report.get("peers") or {}
    if not peers:
        peers = {
            label: dump.get("peers") or {}
            for label, dump in sorted((report.get("telemetry") or {}).items())
        }
    return [
        peer_record(label, links)
        for label, links in sorted(peers.items())
        if links
    ]


def records_from_report(report: dict) -> list[dict]:
    """Per-node records from a chaos report. Prefers the embedded
    `telemetry` section; degrades to scheduler/commit_times so reports
    from telemetry-less scenarios still render."""
    telem = report.get("telemetry") or {}
    if telem:
        return [node_record(label, dump) for label, dump in sorted(telem.items())]
    out = []
    span = float(report.get("virtual_seconds") or 0.0)
    sched = report.get("scheduler") or {}
    commit_times = report.get("commit_times") or {}
    for label in sorted(set(sched) | set(commit_times)):
        commits = len(commit_times.get(label, ()))
        pseudo = {
            "node": label,
            "snapshots": [],
            "commits": commits,
            "lanes": (sched.get(label) or {}).get("queue_delay", {}),
            "alerts": [],
            "active_alerts": [],
        }
        rec = node_record(label, pseudo)
        rec["span_s"] = round(span, 3)
        rec["commit_rate"] = round(commits / span, 3) if span > 0 else 0.0
        out.append(rec)
    return out


def records_from_poll(
    targets: list[str], timeout: float, peers: bool = False
) -> tuple[list[dict], list[str]]:
    from hotstuff_tpu.utils.telemetry import scrape_sync

    records, errors = [], []
    for target in targets:
        host, _, port = target.rpartition(":")
        if not host or not port.isdigit():
            errors.append(f"{target}: expected host:port")
            continue
        try:
            dump = scrape_sync((host, int(port)), timeout=timeout)
        except Exception as e:
            errors.append(f"{target}: {type(e).__name__}: {e}")
            continue
        if peers:
            label = dump.get("node") if dump.get("node") is not None else target
            records.append(peer_record(label, dump.get("peers") or {}))
        else:
            records.append(node_record(target, dump))
    return records, errors


def cell_record(cell: dict, regression: dict) -> dict:
    """Normalize one matrix cell (+ the artifact's regression section)
    into the grid-row record: the cell's identity/verdict, the fleet
    rollup's headline numbers, and this cell's regression markers."""
    rollup = cell.get("rollup") or {}
    commits = rollup.get("commits") or {}
    lanes = rollup.get("lanes") or {}
    occ = rollup.get("occupancy") or {}
    alerts = rollup.get("alerts") or {}
    name = cell.get("cell", "?")
    return {
        "cell": name,
        "scenario": cell.get("scenario"),
        "seed": cell.get("seed"),
        "n": cell.get("n"),
        "crypto": cell.get("crypto_mode", "?"),
        "green": bool(cell.get("green")),
        "commits": int(commits.get("total") or 0),
        "commit_rate": float(commits.get("rate_per_s") or 0.0),
        "consensus_p99_ms": (lanes.get("consensus") or {}).get("p99_ms"),
        "worst_occupancy": occ.get("worst"),
        "alerts_fired": int(alerts.get("fired") or 0),
        "truncated": bool(rollup.get("fault_trace_truncated")),
        "newly_red": name in (regression.get("newly_red") or ()),
        "rate_delta_pct": (regression.get("commit_rate_deltas") or {}).get(name),
        "violations": cell.get("violations") or {},
        # Measurement-gated columns: None means UNMEASURED (partial/no
        # RTT coverage, or a region-less run) and renders as '-' — never
        # a fabricated count (utils/telemetry.fleet_rollup's coverage
        # gate, §5.5p satellite).
        "rtt_region_count": (rollup.get("peer_rtt") or {}).get("region_count"),
        "pivot_hops_per_commit": (rollup.get("election") or {}).get(
            "hops_per_commit"
        ),
    }


def render_matrix(artifact: dict) -> str:
    regression = artifact.get("regression") or {}
    records = [
        cell_record(c, regression) for c in artifact.get("cells") or ()
    ]
    summary = artifact.get("summary") or {}
    lines = [
        f"### Scenario matrix ({summary.get('green', '?')} green / "
        f"{summary.get('red', '?')} red of {summary.get('cells', '?')} "
        f"cells; baseline: {regression.get('baseline') or '-'})\n",
        "| cell | crypto | verdict | commits | commit/s | rate Δ | "
        "consensus p99 (ms) | worst occupancy | alerts | trace | "
        "regions | pivot hops |",
        "|---|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for r in records:
        verdict = "GREEN" if r["green"] else "RED"
        if r["newly_red"]:
            verdict = "RED (regression)"
        delta = (
            f"{r['rate_delta_pct']:+.1f}%"
            if isinstance(r["rate_delta_pct"], (int, float))
            else "-"
        )
        p99 = (
            f"{r['consensus_p99_ms']:.1f}"
            if isinstance(r["consensus_p99_ms"], (int, float))
            else "-"
        )
        regions = r["rtt_region_count"]
        hops = r["pivot_hops_per_commit"]
        lines.append(
            f"| {r['cell']} | {r['crypto']} | {verdict} | {r['commits']} "
            f"| {r['commit_rate']:.1f} | {delta} | {p99} "
            f"| {_fmt_pct(r['worst_occupancy'])} | {r['alerts_fired']} "
            f"| {'TRUNCATED' if r['truncated'] else 'full'} "
            f"| {regions if regions is not None else '-'} "
            f"| {f'{hops:.3f}' if isinstance(hops, (int, float)) else '-'} |"
        )
    problems = [
        f"- {r['cell']}: {kind}: {msg}"
        for r in records
        if not r["green"]
        for kind, msgs in sorted(r["violations"].items())
        for msg in msgs
    ]
    if problems:
        lines += ["", "#### Red-cell violations", *problems]
    return "\n".join(lines)


def _fmt_pct(v) -> str:
    return f"{v * 100:.1f}%" if isinstance(v, (int, float)) else "-"


def _lane_p99(rec: dict, lane: str) -> str:
    s = rec["lanes"].get(lane)
    return f"{s['p99_ms']:.1f}" if s else "-"


def render_markdown(records: list[dict], mode: str) -> str:
    lines = [
        f"### Telemetry dashboard ({mode}, {len(records)} node(s))\n",
        "| node | commits | commit/s | snaps | crit p99 (ms) | mempool p99 (ms) "
        "| occupancy | headroom | active alerts | fired/cleared |",
        "|---|---|---|---|---|---|---|---|---|---|",
    ]
    for rec in records:
        active = ", ".join(rec["active_alerts"]) or "-"
        lines.append(
            f"| {rec['node']} | {rec['commits']} | {rec['commit_rate']:.2f} "
            f"| {rec['snapshots']} | {_lane_p99(rec, 'consensus')} "
            f"| {_lane_p99(rec, 'mempool')} | {_fmt_pct(rec['occupancy'])} "
            f"| {_fmt_pct(rec['overlap_headroom'])} | {active} "
            f"| {rec['alerts_fired']}/{rec['alerts_cleared']} |"
        )
    alert_lines = []
    for rec in records:
        for a in rec["alerts"]:
            alert_lines.append(
                f"- node {rec['node']}: {a.get('slo', '?')} "
                f"{a.get('event', '?')} at t={a.get('t', '?')} "
                f"(burn {a.get('burn_short', '?')}x short / "
                f"{a.get('burn_long', '?')}x long)"
            )
    if alert_lines:
        lines += ["", "#### SLO burn alerts", *alert_lines]
    return "\n".join(lines)


def _fmt_ms(v) -> str:
    return f"{v:.2f}" if isinstance(v, (int, float)) else "-"


def render_peers(records: list[dict], mode: str) -> str:
    lines = [
        f"### Peer observatory ({mode}, {len(records)} node(s))\n",
        "| node | peer | rtt ewma (ms) | rtt p50 (ms) | samples | class "
        "| frames | bytes | backoff drops | probes sent | pongs |",
        "|---|---|---|---|---|---|---|---|---|---|---|",
    ]
    for rec in records:
        for link in rec["links"]:
            cls = link["rtt_class"]
            lines.append(
                f"| {rec['node']} | {link['peer']} "
                f"| {_fmt_ms(link['rtt_ewma_ms'])} "
                f"| {_fmt_ms(link['rtt_p50_ms'])} | {link['rtt_samples']} "
                f"| {cls if cls is not None else '-'} "
                f"| {link['frames_sent']} | {link['bytes_sent']} "
                f"| {link['backoff_drops']} | {link['probes_sent']} "
                f"| {link['pongs_received']} |"
            )
    return "\n".join(lines)


def _fmt_s(v) -> str:
    return f"{v:.3f}" if isinstance(v, (int, float)) else "-"


def render_incidents(ledger: dict) -> str:
    """The incident-ledger view of one chaos report: fault windows with
    their attributed alerts and MTTD/MTTR, fleet percentiles per fault
    class, burn-budget rows, and the unattributed alerts called out —
    pure function of the report's `incidents` section."""
    health = ledger.get("health") or {}
    verdict = "GREEN" if health.get("ok") else "NOT GREEN"
    lines = [
        f"### Incident ledger ({health.get('incidents', 0)} incident(s), "
        f"health {verdict})\n",
        "| kind | window (s) | nodes | alerts | classes | MTTD (s) "
        "| MTTR (s) | residual |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for row in ledger.get("incidents") or ():
        end = "open" if row["end"] is None else f"{row['end']:.3f}"
        nodes = (
            "fleet"
            if row["nodes"] is None
            else ",".join(str(n) for n in row["nodes"])
        )
        classes = (
            ", ".join(
                f"{k}×{v}" for k, v in sorted(row["alert_classes"].items())
            )
            or "-"
        )
        lines.append(
            f"| {row['kind']} | {row['start']:.3f}-{end} | {nodes} "
            f"| {row['alerts']} | {classes} | {_fmt_s(row['mttd_s'])} "
            f"| {_fmt_s(row['mttr_s'])} "
            f"| {'RESIDUAL' if row['residual'] else '-'} |"
        )
    fleet = []
    for label, section in (("MTTD", "mttd"), ("MTTR", "mttr")):
        for kind, s in sorted((health.get(section) or {}).items()):
            fleet.append(
                f"- {label} {kind}: p50 {s['p50_ms']:.0f} ms, "
                f"p99 {s['p99_ms']:.0f} ms over {s['count']} node-sample(s) "
                f"(worst node {s['worst_node']})"
            )
    if fleet:
        lines += ["", "#### Fleet detection/recovery percentiles", *fleet]
    burn = health.get("burn") or {}
    if burn:
        lines += [
            "",
            "#### Burn budget",
            "| SLO | burned (s) | budget (s) | verdict |",
            "|---|---|---|---|",
        ]
        for slo, b in sorted(burn.items()):
            if b["within_budget"] is None:
                v = "unjudged"
            else:
                v = "within" if b["within_budget"] else "OVER"
            lines.append(
                f"| {slo} | {b['burn_s']:.3f} | {_fmt_s(b['budget_s'])} "
                f"| {v} |"
            )
    unattributed = ledger.get("unattributed") or ()
    if unattributed:
        lines += [
            "",
            f"#### UNATTRIBUTED alerts ({len(unattributed)}) — no injected "
            "fault explains these",
            *(
                f"- {u['class']} {u['name']} (node "
                f"{u['node'] if u['node'] is not None else 'global'}) fired "
                f"at t={u['fired']}"
                for u in unattributed
            ),
        ]
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="telemetry_dash", description=__doc__)
    src = ap.add_mutually_exclusive_group(required=True)
    src.add_argument(
        "--poll",
        default=None,
        help="comma-separated host:port scrape targets (live mode)",
    )
    src.add_argument(
        "--report",
        default=None,
        help="chaos report JSON with an embedded telemetry section (offline)",
    )
    src.add_argument(
        "--matrix",
        default=None,
        help="scenario-matrix artifact (tools/chaos_run.py --matrix) — "
        "renders the per-cell grid with regression markers",
    )
    ap.add_argument(
        "--json",
        action="store_true",
        help="emit the normalized per-node records as one JSON object "
        "instead of markdown",
    )
    ap.add_argument(
        "--peers",
        action="store_true",
        help="render the per-peer network observatory (RTT EWMA/p50, "
        "link accounting, per-vantage RTT class) instead of the node "
        "dashboard; needs --poll or --report",
    )
    ap.add_argument(
        "--incidents",
        action="store_true",
        help="render the incident ledger (fault windows, attributed "
        "alerts, MTTD/MTTR, burn budget; utils/incidents.py) — needs "
        "--report: the ledger is a run-level artifact, never scraped live",
    )
    ap.add_argument("--timeout", type=float, default=5.0)
    args = ap.parse_args(argv)

    errors: list[str] = []
    if args.incidents and not args.report:
        print(
            "--incidents reads a chaos report's `incidents` section; "
            "use it with --report",
            file=sys.stderr,
        )
        return 3
    if args.incidents and args.peers:
        print("--incidents and --peers are distinct views; pick one",
              file=sys.stderr)
        return 3
    if args.matrix and args.peers:
        print(
            "--peers renders per-node link tables; matrix artifacts only "
            "carry fleet rollups — use --report/--poll",
            file=sys.stderr,
        )
        return 3
    if args.matrix:
        try:
            with open(args.matrix) as f:
                artifact = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"{args.matrix}: {e}", file=sys.stderr)
            return 3
        if artifact.get("kind") != "chaos_matrix":
            print(
                f"{args.matrix}: not a scenario-matrix artifact "
                "(expected kind=chaos_matrix from chaos_run.py --matrix)",
                file=sys.stderr,
            )
            return 3
        regression = artifact.get("regression") or {}
        if args.json:
            print(
                json.dumps(
                    {
                        "mode": "matrix",
                        "cells": [
                            cell_record(c, regression)
                            for c in artifact.get("cells") or ()
                        ],
                        "summary": artifact.get("summary") or {},
                        "regression": regression,
                    },
                    indent=2,
                    sort_keys=True,
                )
            )
        else:
            print(render_matrix(artifact))
        return 0
    if args.poll:
        mode = "live"
        records, errors = records_from_poll(
            [t.strip() for t in args.poll.split(",") if t.strip()],
            args.timeout,
            peers=args.peers,
        )
    else:
        mode = "offline"
        try:
            with open(args.report) as f:
                report = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"{args.report}: {e}", file=sys.stderr)
            return 3
        if "scenarios" in report and "telemetry" not in report:
            print(
                f"{args.report}: multi-scenario sweep report; re-run "
                "tools/chaos_run.py with a single --scenario",
                file=sys.stderr,
            )
            return 3
        if args.incidents:
            ledger = report.get("incidents")
            if not isinstance(ledger, dict):
                print(
                    f"{args.report}: no `incidents` section — the report "
                    "predates the incident ledger (re-run the scenario)",
                    file=sys.stderr,
                )
                return 3
            if args.json:
                print(json.dumps(ledger, indent=2, sort_keys=True))
            else:
                print(render_incidents(ledger))
            return 0
        records = (
            peer_records_from_report(report)
            if args.peers
            else records_from_report(report)
        )

    if args.json:
        print(
            json.dumps(
                {"mode": mode, "nodes": records, "errors": errors},
                indent=2,
                sort_keys=True,
            )
        )
    else:
        print(render_peers(records, mode) if args.peers else render_markdown(records, mode))
        for e in errors:
            print(f"poll error: {e}", file=sys.stderr)
    return 2 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
