"""Log parsing: the metrics pipeline (reference benchmark/benchmark/logs.py).

Regex-scrapes node and client logs to compute:
  * consensus TPS/BPS and latency (block Created -> Committed)
  * end-to-end TPS/BPS and latency (client sample send -> commit), via the
    sample-tx -> payload-digest -> block-commit join (logs.py:102-104,173-182)
  * benchmark-workload verification throughput (the fork's
    "Verifying OWN/OTHER transaction batch. Size: N" lines -- the
    votes-verified/sec north-star metric)

Raises ParseError if any log contains a traceback, actor crash, or an
ERROR-severity line, like the reference raising on `Error`/`panic` matches
(logs.py:71-72,88-89). Per-log scraping runs in a multiprocessing Pool when
the host has cores to spare (reference logs.py:27-39) — at 20+ node log
volumes the regex pass is minutes of single-core work.
"""

from __future__ import annotations

import json
import os
import re
from datetime import datetime, timezone
from glob import glob
from multiprocessing import Pool
from os.path import join
from statistics import mean


class ParseError(Exception):
    pass


def _check_crash(text: str) -> None:
    if (
        "Traceback" in text
        or " ERROR " in text
        or "panic" in text
        or ("actor" in text and "crashed" in text)
    ):
        raise ParseError("node or client log contains a crash or error")


_TS = r"\[(\d{4}-\d{2}-\d{2}T\d{2}:\d{2}:\d{2}\.\d{3})Z"


def _to_posix(ts: str) -> float:
    return (
        datetime.strptime(ts, "%Y-%m-%dT%H:%M:%S.%f")
        .replace(tzinfo=timezone.utc)
        .timestamp()
    )


def _search_all(pattern: str, text: str) -> list[tuple]:
    return re.findall(pattern, text, re.MULTILINE)


def _parse_client(text: str) -> dict:
    """Scrape one client log (runs in a Pool worker)."""
    _check_crash(text)
    out: dict = {"size": 0, "rate": 0, "start": None, "samples": {}, "misses": 0}
    m = re.search(rf"{_TS}.*Transactions size: (\d+) B", text)
    if m:
        out["size"] = int(m.group(2))
    m = re.search(rf"{_TS}.*Transactions rate: (\d+) tx/s", text)
    if m:
        out["rate"] = int(m.group(2))
    m = re.search(rf"{_TS}.*Start sending transactions", text)
    if m:
        out["start"] = _to_posix(m.group(1))
    out["samples"] = {
        int(sid): _to_posix(ts)
        for ts, sid in _search_all(
            rf"{_TS}.*Sending sample transaction (\d+)", text
        )
    }
    out["misses"] = len(_search_all(r"rate too high", text))
    # Ingress load-generator result lines (hotstuff_tpu/ingress/loadgen.py
    # log_summary): open-loop offered/accepted/shed counts and
    # client-observed latency percentiles. Absent on Front-only runs.
    for pat, key, cast in [
        (r"Ingress offered: (\d+) transactions", "ingress_offered", int),
        (r"Ingress accepted: (\d+) transactions", "ingress_accepted", int),
        (r"Ingress shed: (\d+) transactions", "ingress_shed", int),
        (r"Ingress client latency p50: ([\d.]+) ms", "ingress_p50", float),
        (r"Ingress client latency p99: ([\d.]+) ms", "ingress_p99", float),
    ]:
        m = re.search(pat, text)
        out[key] = cast(m.group(1)) if m else None
    return out


def _parse_node(text: str) -> dict:
    """Scrape one node log (runs in a Pool worker)."""
    _check_crash(text)
    out: dict = {
        "proposals": {},
        "commits": {},
        "committed_payloads": {},
        "payload_sizes": {},
        "sample_to_payload": {},
        "verif_batches": [],
        "timeouts": 0,
    }
    for ts, rnd, digest in _search_all(rf"{_TS}.*Created B(\d+)\((\S+?)\)$", text):
        t = _to_posix(ts)
        out["proposals"][digest] = min(out["proposals"].get(digest, t), t)
    for ts, rnd, digest in _search_all(rf"{_TS}.*Committed B(\d+)\((\S+?)\)$", text):
        t = _to_posix(ts)
        out["commits"][digest] = min(out["commits"].get(digest, t), t)
    for ts, rnd, digest, payload in _search_all(
        rf"{_TS}.*Committed B(\d+)\((\S+?)\) -> (\S+)$", text
    ):
        t = _to_posix(ts)
        prev = out["committed_payloads"].get(payload)
        if prev is None or t < prev[1]:
            out["committed_payloads"][payload] = (digest, t)
    for ts, payload, size in _search_all(
        rf"{_TS}.*Payload (\S+) contains (\d+) B", text
    ):
        out["payload_sizes"][payload] = int(size)
    for ts, payload, sid in _search_all(
        rf"{_TS}.*Payload (\S+) contains sample tx (\d+)", text
    ):
        out["sample_to_payload"][int(sid)] = payload
    for ts, kind, n in _search_all(
        rf"{_TS}.*Verifying (OWN|OTHER) transaction batch\. Size: (\d+)", text
    ):
        out["verif_batches"].append((_to_posix(ts), int(n)))
    out["timeouts"] = len(_search_all(r"Timeout reached", text))
    # Cumulative count from the periodic saturation warning. The LAST
    # logged milestone is a LOWER BOUND on the node's total shed (the node
    # is killed by SIGTERM, so up to one 25k-milestone of tail sheds goes
    # unlogged); 0 when never saturated.
    shed = _search_all(r"(\d+) synthetic workload signatures skipped", text)
    # single-group findall yields plain strings
    out["workload_shed"] = int(shed[-1]) if shed else 0
    # Anomaly-watchdog firings (utils/tracing.py): reasons + dump paths.
    # A fired watchdog is the signal a run's numbers need the recorder
    # dump read before being believed.
    out["watchdog_fired"] = _search_all(
        r"anomaly watchdog fired: (\w+)", text
    )
    out["watchdog_dumps"] = _search_all(
        r"flight recorder dumped to (\S+)", text
    )
    # Live-telemetry lines (utils/telemetry.py): SLO burn alert
    # transitions and the periodic device-occupancy line. Occupancy is
    # cumulative over the timeline ring, so only the LAST line per node
    # matters.
    out["slo_fired"] = _search_all(r"SLO burn fired: (\S+)", text)
    out["slo_cleared"] = _search_all(r"SLO burn cleared: (\S+)", text)
    # Incident-ledger lines (utils/incidents.py §5.5r): the run-level
    # fault→alert→recovery summary and the burn-budget verdict. One
    # summary per ledger build; the LAST line wins (a rerun supersedes).
    inc = _search_all(
        r"Incident ledger: (\d+) incident\(s\), (\d+) alert\(s\) "
        r"attributed, (\d+) unattributed, (\d+) residual, "
        r"worst MTTR ([\d.]+) ms",
        text,
    )
    out["incident_ledger"] = (
        (
            int(inc[-1][0]),
            int(inc[-1][1]),
            int(inc[-1][2]),
            int(inc[-1][3]),
            float(inc[-1][4]),
        )
        if inc
        else None
    )
    burn = _search_all(
        r"Burn budget verdict: (ok|violated) "
        r"\((\d+) SLO row\(s\) over budget\)",
        text,
    )
    out["burn_verdict"] = (burn[-1][0], int(burn[-1][1])) if burn else None
    # Reconfiguration / catch-up lines (consensus/reconfig.py +
    # synchronizer.py + core.py): epoch switches with their activation
    # rounds, and range-sync start lag / fetched-block progress.
    out["epoch_switches"] = [
        (int(e), int(r))
        for e, r in _search_all(
            r"Epoch switch to (\d+) at activation round (\d+)", text
        )
    ]
    # Epoch-final handoff lines (consensus/reconfig.py §5.5j): one per
    # committed rotation with the commit-to-boundary slack, plus the
    # hard-invariant violation marker (which must normally never appear).
    out["handoffs"] = [
        (int(e), int(t_), int(b), int(s))
        for e, t_, b, s in _search_all(
            r"Epoch handoff to (\d+) committed at round (\d+) "
            r"\(boundary (\d+), slack (\d+) rounds\)",
            text,
        )
    ]
    out["handoff_violations"] = len(
        _search_all(r"Epoch handoff VIOLATION", text)
    )
    out["range_lags"] = [
        int(lag)
        for lag in _search_all(
            r"Range sync started for \S+: (\d+) rounds behind", text
        )
    ]
    out["range_blocks"] = sum(
        int(n) for n in _search_all(r"Range sync fetched (\d+) blocks", text)
    )
    # Aggregation-overlay lines (consensus/overlay.py + core.py): partial
    # bundles that completed a certificate, and gossip fallbacks fired
    # when a round stayed stalled past the fallback window.
    out["agg_quorums"] = [
        (kind, int(rnd), int(entries))
        for kind, rnd, entries in _search_all(
            r"Agg bundle quorum: (QC|TC) round (\d+) from (\d+) entries", text
        )
    ]
    out["agg_fallbacks"] = [
        (int(rnd), int(entries), int(peers))
        for rnd, entries, peers in _search_all(
            r"Agg fallback round (\d+): (\d+) entries to (\d+) peers", text
        )
    ]
    # Certificate-plane line (consensus/core.py _commit): cumulative
    # aggregate-vs-entry-list cert counts, the worst committed cert's
    # wire bytes, and the deepest aggregation merge tree seen. Cumulative
    # per node, so the LAST line wins.
    certs = _search_all(
        r"Cert plane: (\d+) aggregate / (\d+) entry-list certs committed, "
        r"worst cert (\d+) B, agg depth (\d+)",
        text,
    )
    out["cert_plane"] = (
        tuple(int(x) for x in certs[-1]) if certs else None
    )
    # Proof-plane line (proofs/server.py _serve): cumulative served /
    # subscription / shed counts and the worst served proof's wire bytes.
    # Cumulative per node, so the LAST line wins; absent on runs without
    # the commit-proof serving plane.
    served = _search_all(
        r"Proof served: (\d+) proofs served, (\d+) subscriptions, "
        r"(\d+) shed, worst proof (\d+) B",
        text,
    )
    out["proof_plane"] = (
        tuple(int(x) for x in served[-1]) if served else None
    )
    # Election-plane line (consensus/core.py _note_election_stats): the
    # per-node cumulative propose->certify pivot attribution — rounds
    # scored, co-located pivots, cross-region hops, and the in-run
    # round-robin counterfactual. Cumulative per node, so the LAST line
    # wins; absent (None, never zeros) when the run had no region map.
    elect = _search_all(
        r"Election plane: (\d+) round\(s\) committed, (\d+) co-located "
        r"pivot\(s\), (\d+) cross-region hop\(s\), (\d+) blind",
        text,
    )
    out["election"] = tuple(int(x) for x in elect[-1]) if elect else None
    # Network-observatory lines (consensus/core.py _log_peer_map): the
    # periodic per-vantage RTT map and cumulative probe counters. Both
    # are cumulative/monotone per node, so the LAST line wins — except
    # the worst EWMA, which keeps the max ever logged (a link that
    # degraded mid-run and recovered still counts as the worst seen).
    rtt_maps = _search_all(
        r"Peer RTT map: (\d+) peer\(s\) in (\d+) class\(es\), "
        r"worst EWMA ([\d.]+) ms",
        text,
    )
    out["peer_rtt"] = (
        (
            int(rtt_maps[-1][0]),
            int(rtt_maps[-1][1]),
            max(float(w) for _p, _c, w in rtt_maps),
        )
        if rtt_maps
        else None
    )
    probes = _search_all(r"Probe summary: (\d+) sent, (\d+) answered", text)
    out["probes"] = (int(probes[-1][0]), int(probes[-1][1])) if probes else None
    # Scenario-matrix result lines (tools/chaos_run.py --matrix): per-cell
    # verdicts, green->red regressions against the committed baseline
    # artifact, and the worst per-cell commit-rate delta.
    out["matrix_cells"] = [
        (cell, verdict)
        for cell, verdict in _search_all(
            r"MATRIX cell (\S+) (green|red) ", text
        )
    ]
    out["matrix_regressions"] = _search_all(
        r"MATRIX regression: (\S+) went red", text
    )
    out["matrix_worst"] = [
        (cell, float(pct))
        for cell, pct in _search_all(
            r"MATRIX worst regression: (\S+) commit rate ([+-]?[\d.]+)%", text
        )
    ]
    # Static-analysis summary line (tools/graftlint): deploy/CI recipes
    # run the lint before boot and tee its summary into the log. The
    # LAST line wins (a rerun supersedes); absent on unlinted runs.
    lint = _search_all(r"graftlint: (\d+) findings", text)
    out["graftlint_findings"] = int(lint[-1]) if lint else None
    occ = _search_all(
        r"TELEMETRY device occupancy ([\d.]+)% overlap headroom ([\d.]+)%",
        text,
    )
    out["occupancy"] = (
        (float(occ[-1][0]), float(occ[-1][1])) if occ else None
    )
    # METRICS snapshot lines (utils/metrics.py periodic emitter). Counters
    # are cumulative, so only the LAST well-formed snapshot per node
    # matters; a malformed blob (truncated by SIGTERM mid-line) is skipped,
    # never a ParseError — observability must not fail the run.
    out["metrics"] = _last_metrics(text)
    return out


def _last_metrics(text: str) -> dict | None:
    """The LAST well-formed `METRICS {json}` snapshot of one log."""
    for blob in reversed(_search_all(r"METRICS (\{.*\})\s*$", text)):
        try:
            snap = json.loads(blob)
        except json.JSONDecodeError:
            continue
        if isinstance(snap, dict):
            return snap
    return None


def _map_logs(fn, texts: list[str]) -> list[dict]:
    """Pool-parallel per-log scraping (reference logs.py:27-39); serial when
    the host is single-core or there is nothing to parallelise."""
    if len(texts) > 1 and (os.cpu_count() or 1) > 1:
        with Pool() as p:
            return p.map(fn, texts)
    return [fn(t) for t in texts]


class LogParser:
    def __init__(
        self,
        clients: list[str],
        nodes: list[str],
        faults: int = 0,
        sidecar: str | None = None,
    ) -> None:
        # The crypto sidecar's last METRICS snapshot (`--crypto tpu` runs):
        # `info.backend` is TpuBackend.report() — the device as JAX named
        # it, signatures routed to it, chunks dispatched per program. This,
        # not the nodes' "Verifying ... batch" lines, says what checked
        # the signatures.
        self.sidecar_metrics = _last_metrics(sidecar) if sidecar else None
        self.faults = faults
        self.committee_size = len(nodes) + faults

        # --- client logs ---
        self.size = 0
        self.rate = 0
        self.start = None
        # Steady-state window start: the LAST client's first send. On real
        # distributed hardware clients start within ~a second and this
        # equals `start`; on an oversubscribed single-core host client
        # interpreters can take minutes to boot, and measuring from the
        # FIRST client would fold the partial-load ramp into the TPS
        # denominator (deflating large committees arbitrarily).
        self.steady_start = None
        self.sent_samples: dict[tuple[int, int], float] = {}
        self.misses = 0
        self.ingress_offered = 0
        self.ingress_accepted = 0
        self.ingress_shed = 0
        # Percentiles are not mergeable across clients: keep per-client
        # values and report mean p50 / worst p99.
        self.ingress_p50s: list[float] = []
        self.ingress_p99s: list[float] = []
        for i, c in enumerate(_map_logs(_parse_client, clients)):
            self.size = self.size or c["size"]
            self.rate += c["rate"]
            if c["start"] is not None:
                self.start = (
                    c["start"] if self.start is None else min(self.start, c["start"])
                )
                self.steady_start = (
                    c["start"]
                    if self.steady_start is None
                    else max(self.steady_start, c["start"])
                )
            # Sample ids collide across clients; key by (client, id).
            for sid, t in c["samples"].items():
                self.sent_samples[(i, sid)] = t
            self.misses += c["misses"]
            self.ingress_offered += c.get("ingress_offered") or 0
            self.ingress_accepted += c.get("ingress_accepted") or 0
            self.ingress_shed += c.get("ingress_shed") or 0
            if c.get("ingress_p50") is not None:
                self.ingress_p50s.append(c["ingress_p50"])
            if c.get("ingress_p99") is not None:
                self.ingress_p99s.append(c["ingress_p99"])

        # --- node logs ---
        self.proposals: dict[str, float] = {}  # block digest -> earliest created
        self.commits: dict[str, float] = {}  # block digest -> earliest commit
        self.committed_payloads: dict[str, tuple[str, float]] = {}  # payload -> (block, t)
        self.payload_sizes: dict[str, int] = {}
        self.sample_to_payload: dict[int, str] = {}
        self.verif_batches: list[tuple[float, int]] = []  # (t, batch size)
        self.timeouts = 0
        self.workload_shed = 0
        self.watchdog_fired: list[str] = []  # anomaly reasons across nodes
        self.watchdog_dumps: list[str] = []  # recorder dump paths
        self.slo_fired: list[str] = []  # SLO burn alerts across nodes
        self.slo_cleared: list[str] = []
        # Incident-ledger fold (one summary line per ledger build): counts
        # sum across logs that carried one, worst MTTR takes the max, and
        # the burn verdict is 'violated' if ANY log said violated.
        self.incident_count = 0
        self.incident_attributed = 0
        self.incident_unattributed = 0
        self.incident_residual = 0
        self.incident_worst_mttr_ms = 0.0
        self.incident_ledgers = 0
        self.burn_verdict: str | None = None
        self.burn_over = 0
        # (epoch, activation round) per switch line across nodes, and the
        # per-range-sync start lags / fetched-block totals (catch-up).
        self.epoch_switches: list[tuple[int, int]] = []
        # (epoch, trigger round, boundary, slack) per committed handoff
        # across nodes, and the count of handoff VIOLATION lines (the
        # epoch-final hard invariant — must stay zero).
        self.handoffs: list[tuple[int, int, int, int]] = []
        self.handoff_violations = 0
        self.range_lags: list[int] = []
        self.range_blocks = 0
        # Aggregation-overlay scrapes: (kind, round, entries) per bundle
        # quorum and (round, entries, peers) per gossip fallback.
        self.agg_quorums: list[tuple[str, int, int]] = []
        self.agg_fallbacks: list[tuple[int, int, int]] = []
        # Certificate-plane fold (cumulative per-node lines): counts sum
        # across nodes; worst bytes / aggregation depth take the max.
        self.cert_agg = 0
        self.cert_legacy = 0
        self.cert_worst_bytes = 0
        self.cert_depth = 0
        self.cert_nodes = 0
        # Proof-plane fold (cumulative per-node lines, like the cert
        # plane): served/subscription/shed counts sum across nodes; the
        # worst proof's wire bytes take the max.
        self.proof_served = 0
        self.proof_subs = 0
        self.proof_shed = 0
        self.proof_worst_bytes = 0
        self.proof_nodes = 0
        # Election-plane fold (cumulative per-node lines, like the cert
        # plane): counts sum across nodes, with the contributing node
        # count kept so per-commit rates stay honest.
        self.elect_rounds = 0
        self.elect_matches = 0
        self.elect_hops = 0
        self.elect_hops_blind = 0
        self.elect_nodes = 0
        # Network-observatory scrapes: (peers, classes, worst EWMA ms) per
        # node that logged an RTT map, plus fleet probe send/answer totals.
        self.peer_rtts: list[tuple[int, int, float]] = []
        self.probes_sent = 0
        self.probes_answered = 0
        # Scenario-matrix lines: (cell, green|red) verdicts, newly-red
        # cell names, and (cell, pct) worst commit-rate deltas.
        self.matrix_cells: list[tuple[str, str]] = []
        self.matrix_regressions: list[str] = []
        self.matrix_worst: list[tuple[str, float]] = []
        # (occupancy %, overlap headroom %) per node that logged telemetry
        self.occupancies: list[tuple[float, float]] = []
        # Worst graftlint finding count across nodes; None when no node
        # log carried the summary line.
        self.graftlint_findings: int | None = None
        # Final METRICS snapshot per node (utils/metrics.py), and the
        # cross-node aggregate (counters summed, histogram count/sum summed).
        self.node_metrics: list[dict] = []
        self.configs = self._parse_configs(nodes[0] if nodes else "")
        for r in _map_logs(_parse_node, nodes):
            for digest, t in r["proposals"].items():
                self.proposals[digest] = min(self.proposals.get(digest, t), t)
            for digest, t in r["commits"].items():
                self.commits[digest] = min(self.commits.get(digest, t), t)
            for payload, (digest, t) in r["committed_payloads"].items():
                prev = self.committed_payloads.get(payload)
                if prev is None or t < prev[1]:
                    self.committed_payloads[payload] = (digest, t)
            self.payload_sizes.update(r["payload_sizes"])
            # Client index is unknown from node logs; samples are joined
            # per-id against every client that sent that id (logs.py:102).
            self.sample_to_payload.update(r["sample_to_payload"])
            self.verif_batches.extend(r["verif_batches"])
            self.timeouts += r["timeouts"]
            self.workload_shed += r["workload_shed"]
            self.watchdog_fired.extend(r.get("watchdog_fired", []))
            self.watchdog_dumps.extend(r.get("watchdog_dumps", []))
            self.slo_fired.extend(r.get("slo_fired", []))
            self.slo_cleared.extend(r.get("slo_cleared", []))
            if r.get("incident_ledger") is not None:
                n_inc, att, unatt, resid, worst = r["incident_ledger"]
                self.incident_count += n_inc
                self.incident_attributed += att
                self.incident_unattributed += unatt
                self.incident_residual += resid
                self.incident_worst_mttr_ms = max(
                    self.incident_worst_mttr_ms, worst
                )
                self.incident_ledgers += 1
            if r.get("burn_verdict") is not None:
                verdict, over = r["burn_verdict"]
                self.burn_over += over
                if self.burn_verdict != "violated":
                    self.burn_verdict = verdict
            self.epoch_switches.extend(r.get("epoch_switches", []))
            self.handoffs.extend(r.get("handoffs", []))
            self.handoff_violations += r.get("handoff_violations", 0)
            self.range_lags.extend(r.get("range_lags", []))
            self.range_blocks += r.get("range_blocks", 0)
            self.agg_quorums.extend(r.get("agg_quorums", []))
            self.agg_fallbacks.extend(r.get("agg_fallbacks", []))
            if r.get("cert_plane") is not None:
                n_agg, n_legacy, worst_b, depth = r["cert_plane"]
                self.cert_agg += n_agg
                self.cert_legacy += n_legacy
                self.cert_worst_bytes = max(self.cert_worst_bytes, worst_b)
                self.cert_depth = max(self.cert_depth, depth)
                self.cert_nodes += 1
            if r.get("proof_plane") is not None:
                p_served, p_subs, p_shed, p_worst = r["proof_plane"]
                self.proof_served += p_served
                self.proof_subs += p_subs
                self.proof_shed += p_shed
                self.proof_worst_bytes = max(self.proof_worst_bytes, p_worst)
                self.proof_nodes += 1
            if r.get("election") is not None:
                e_rounds, e_matches, e_hops, e_blind = r["election"]
                self.elect_rounds += e_rounds
                self.elect_matches += e_matches
                self.elect_hops += e_hops
                self.elect_hops_blind += e_blind
                self.elect_nodes += 1
            if r.get("peer_rtt") is not None:
                self.peer_rtts.append(r["peer_rtt"])
            if r.get("probes") is not None:
                self.probes_sent += r["probes"][0]
                self.probes_answered += r["probes"][1]
            self.matrix_cells.extend(r.get("matrix_cells", []))
            self.matrix_regressions.extend(r.get("matrix_regressions", []))
            self.matrix_worst.extend(r.get("matrix_worst", []))
            if r.get("occupancy") is not None:
                self.occupancies.append(r["occupancy"])
            if r.get("graftlint_findings") is not None:
                self.graftlint_findings = (
                    r["graftlint_findings"]
                    if self.graftlint_findings is None
                    else max(self.graftlint_findings, r["graftlint_findings"])
                )
            if r.get("metrics") is not None:
                self.node_metrics.append(r["metrics"])
        self.metrics = self._merge_metrics(self.node_metrics)

    @staticmethod
    def _merge_metrics(snapshots: list[dict]) -> dict:
        """Aggregate per-node snapshots: counters sum; histograms keep the
        summed count/sum (mean re-derived) and the max of max — percentiles
        are not mergeable across nodes and are dropped. Snapshots missing
        keys or carrying junk values are tolerated (scraped from logs)."""
        counters: dict[str, int] = {}
        histograms: dict[str, dict] = {}
        for snap in snapshots:
            for name, v in (snap.get("counters") or {}).items():
                if isinstance(v, (int, float)):
                    counters[name] = counters.get(name, 0) + v
            for name, h in (snap.get("histograms") or {}).items():
                if not isinstance(h, dict):
                    continue
                agg = histograms.setdefault(
                    name, {"count": 0, "sum": 0.0, "max": 0.0}
                )
                if isinstance(h.get("count"), (int, float)):
                    agg["count"] += h["count"]
                if isinstance(h.get("sum"), (int, float)):
                    agg["sum"] += h["sum"]
                if isinstance(h.get("max"), (int, float)):
                    agg["max"] = max(agg["max"], h["max"])
        return {"counters": counters, "histograms": histograms}

    @staticmethod
    def _parse_configs(text: str) -> dict:
        out = {}
        for pat, key in [
            (r"Timeout delay set to (\d+) ms", "timeout_delay"),
            (r"Sync retry delay set to (\d+) ms", "sync_retry_delay"),
            (r"Max payload size set to (\d+) B", "max_payload_size"),
            (r"Min block delay set to (\d+) ms", "min_block_delay"),
            (r"Queue capacity set to (\d+)", "queue_capacity"),
            (r"Probe interval set to (\d+) ms", "probe_interval"),
        ]:
            ms = re.findall(pat, text)
            if ms:
                out[key] = int(ms[0])
        return out

    # --- metrics (reference logs.py:149-182) ---

    # Boot skew below this is treated as synchronized-start (reference
    # semantics): genuine interpreter-boot skew on an oversubscribed host is
    # tens of seconds, while cross-machine NTP drift on a remote run is
    # sub-second — the threshold keeps the latter from shifting the window.
    _SKEW_THRESHOLD_S = 5.0

    def _steady_window_start(self) -> float | None:
        if self.start is None or self.steady_start is None:
            return self.start
        if self.steady_start - self.start > self._SKEW_THRESHOLD_S:
            return self.steady_start
        return self.start

    def _windowed_throughput(self, start: float) -> tuple[float, float, float]:
        """(TPS, BPS, duration) over [start, last commit]: only payloads
        committed inside the window count, so a ramp period excluded from
        the denominator is excluded from the numerator too. (Residual known
        bias: transactions QUEUED during the ramp but committed just after
        it drain as in-window commits — the readiness gate in
        benchmark/local.py keeps that backlog small by not starting the
        measured duration until every client is sending.)"""
        end = max(self.commits.values())
        duration = max(end - start, 1e-9)
        bytes_total = sum(
            self.payload_sizes.get(p, 0)
            for p, (_digest, t) in self.committed_payloads.items()
            if t >= start
        )
        bps = bytes_total / duration
        tps = bps / self.size if self.size else 0.0
        return tps, bps, duration

    def consensus_throughput(self) -> tuple[float, float, float]:
        """(TPS, BPS, duration). Bytes = sizes of committed payloads.
        The window opens at the first proposal, clamped to the
        steady-state start (see `steady_start`) so client boot skew on an
        oversubscribed host doesn't dilute the rate."""
        if not self.commits:
            return 0.0, 0.0, 0.0
        start = min(self.proposals.values()) if self.proposals else min(self.commits.values())
        steady = self._steady_window_start()
        if steady is not None:
            start = max(start, steady)
        return self._windowed_throughput(start)

    def consensus_latency(self) -> float:
        """Mean propose->commit time over blocks PROPOSED inside the
        steady-state window (ramp-period blocks ran against partial load
        and would bias the mean low)."""
        steady = self._steady_window_start() or 0.0
        lat = [
            self.commits[d] - self.proposals[d]
            for d in self.commits
            if d in self.proposals and self.proposals[d] >= steady
        ]
        return mean(lat) if lat else 0.0

    def end_to_end_throughput(self) -> tuple[float, float, float]:
        """Window opens when the LAST client starts sending (equals the
        first on real hardware; excludes the boot-skew ramp on an
        oversubscribed host)."""
        if not self.commits or self.start is None:
            return 0.0, 0.0, 0.0
        return self._windowed_throughput(self._steady_window_start())

    def end_to_end_latency(self) -> float:
        """Mean send->commit time over samples SENT inside the steady-state
        window (a ramp-period sample measures an uncontended system)."""
        steady = self._steady_window_start() or 0.0
        lat = []
        for (client, sid), sent in self.sent_samples.items():
            if sent < steady:
                continue
            payload = self.sample_to_payload.get(sid)
            if payload is None:
                continue
            hit = self.committed_payloads.get(payload)
            if hit is None:
                continue
            lat.append(hit[1] - sent)
        return mean(lat) if lat else 0.0

    def verification_throughput(self) -> tuple[float, int]:
        """(verified signatures/sec across the run, total verified) from the
        fork's batch log lines -- the votes-verified/sec metric."""
        if not self.verif_batches:
            return 0.0, 0
        times = [t for t, _ in self.verif_batches]
        total = sum(n for _, n in self.verif_batches)
        duration = max(max(times) - min(times), 1e-9)
        return total / duration, total

    def result(self) -> str:
        c_tps, c_bps, _ = self.consensus_throughput()
        c_lat = self.consensus_latency()
        e_tps, e_bps, _ = self.end_to_end_throughput()
        e_lat = self.end_to_end_latency()
        v_rate, v_total = self.verification_throughput()
        ingress = ""
        if self.ingress_offered:
            shed_pct = 100.0 * self.ingress_shed / self.ingress_offered
            p50 = mean(self.ingress_p50s) if self.ingress_p50s else 0.0
            p99 = max(self.ingress_p99s) if self.ingress_p99s else 0.0
            ingress = (
                " + INGRESS:\n"
                f" Offered: {self.ingress_offered:,} tx"
                f" ({self.ingress_accepted:,} accepted,"
                f" {self.ingress_shed:,} shed = {shed_pct:.1f} %)\n"
                f" Client latency p50 (mean across clients): {p50:,.1f} ms\n"
                f" Client latency p99 (worst client): {p99:,.1f} ms\n"
            )
        mtr = ""
        if self.metrics["counters"] or self.metrics["histograms"]:
            lines = [
                f" {name}: {value:,}"
                for name, value in sorted(self.metrics["counters"].items())
                if value
            ]
            # h_mean, NOT `mean`: that name is statistics.mean at module
            # scope, and shadowing it here made the whole function treat
            # the import as unbound (the PR 6 hand-computed-mean wart).
            for name, h in sorted(self.metrics["histograms"].items()):
                if h["count"]:
                    h_mean = h["sum"] / h["count"]
                    lines.append(
                        f" {name}: count={h['count']:,} mean={h_mean:.6g} "
                        f"max={h['max']:.6g}"
                    )
            if lines:
                mtr = (
                    f" + METRICS ({len(self.node_metrics)} node snapshots):\n"
                    + "\n".join(lines)
                    + "\n"
                )
        network = ""
        if self.peer_rtts or self.probes_sent:
            network = " + NETWORK:\n"
            if self.peer_rtts:
                # Worst link anywhere in the fleet; the class count from
                # the same vantage says whether that link crossed an RTT
                # class boundary (>= 2 classes: a cross-region hop).
                peers, classes, worst = max(
                    self.peer_rtts, key=lambda pcw: pcw[2]
                )
                network += (
                    f" Worst peer RTT EWMA: {worst:,.1f} ms"
                    f" ({peers} peer(s) in {classes} RTT class(es)"
                    " from that vantage)\n"
                )
            if self.probes_sent:
                lost = max(0, self.probes_sent - self.probes_answered)
                loss_pct = 100.0 * lost / self.probes_sent
                network += (
                    f" Probes: {self.probes_sent:,} sent,"
                    f" {self.probes_answered:,} answered"
                    f" ({lost:,} outstanding = {loss_pct:.1f} %)\n"
                )
        telemetry = ""
        if self.occupancies or self.slo_fired or self.slo_cleared:
            telemetry = " + TELEMETRY:\n"
            if self.occupancies:
                # Worst node = LOWEST device occupancy (the node whose
                # device sat idle the most is the one gap attribution
                # should start from).
                worst = min(self.occupancies, key=lambda oc: oc[0])
                telemetry += (
                    f" Worst-node device occupancy: {worst[0]:.1f} %"
                    f" (overlap headroom {worst[1]:.1f} %)\n"
                )
            if self.slo_fired or self.slo_cleared:
                names = ", ".join(sorted(set(self.slo_fired))) or "-"
                telemetry += (
                    f" SLO burn alerts: {len(self.slo_fired)} fired"
                    f" ({names}), {len(self.slo_cleared)} cleared\n"
                )
        incidents = ""
        if self.incident_ledgers:
            incidents = (
                " + INCIDENTS:\n"
                f" Incidents: {self.incident_count}"
                f" ({self.incident_attributed} alert(s) attributed,"
                f" {self.incident_unattributed} unattributed,"
                f" {self.incident_residual} residual)\n"
                f" Worst MTTR: {self.incident_worst_mttr_ms:,.1f} ms\n"
            )
            if self.burn_verdict is not None:
                incidents += (
                    f" Burn budget: {self.burn_verdict}"
                    f" ({self.burn_over} SLO row(s) over)\n"
                )
        matrix = ""
        if self.matrix_cells:
            greens = sum(1 for _c, v in self.matrix_cells if v == "green")
            reds = len(self.matrix_cells) - greens
            matrix = (
                " + MATRIX:\n"
                f" Cells: {len(self.matrix_cells)} run"
                f" ({greens} green, {reds} red)\n"
            )
            if self.matrix_regressions:
                names = ", ".join(sorted(set(self.matrix_regressions)))
                matrix += (
                    f" REGRESSION: {len(self.matrix_regressions)} previously-"
                    f"green cell(s) went red: {names}\n"
                )
            if self.matrix_worst:
                cell, pct = min(self.matrix_worst, key=lambda cw: cw[1])
                matrix += (
                    f" Worst commit-rate delta vs baseline: {cell}"
                    f" {pct:+.2f} %\n"
                )
        agg = ""
        if self.agg_quorums or self.agg_fallbacks:
            agg = " + AGG:\n"
            if self.agg_quorums:
                qcs = sum(1 for k, _r, _n in self.agg_quorums if k == "QC")
                tcs = len(self.agg_quorums) - qcs
                entries = sum(n for _k, _r, n in self.agg_quorums)
                agg += (
                    f" Bundle quorums: {len(self.agg_quorums)}"
                    f" ({qcs} QC, {tcs} TC) from {entries:,} merged entries\n"
                )
            if self.agg_fallbacks:
                gossiped = sum(e for _r, e, _p in self.agg_fallbacks)
                frames = sum(p for _r, _e, p in self.agg_fallbacks)
                agg += (
                    f" Fallbacks: {len(self.agg_fallbacks)}"
                    f" ({gossiped:,} entries gossiped over {frames:,} frames)\n"
                )
        certs = ""
        if self.cert_nodes:
            total_certs = self.cert_agg + self.cert_legacy
            agg_pct = 100.0 * self.cert_agg / total_certs if total_certs else 0.0
            certs = (
                " + CERTS:\n"
                f" Committed certificates: {total_certs:,}"
                f" ({self.cert_agg:,} aggregate = {agg_pct:.1f} %,"
                f" {self.cert_legacy:,} entry-list)"
                f" across {self.cert_nodes} node(s)\n"
                f" Worst cert: {self.cert_worst_bytes:,} B,"
                f" aggregation depth {self.cert_depth}\n"
            )
        proofs = ""
        if self.proof_nodes:
            shed_pct = (
                100.0 * self.proof_shed / (self.proof_subs + self.proof_shed)
                if (self.proof_subs + self.proof_shed)
                else 0.0
            )
            proofs = (
                " + PROOFS:\n"
                f" Proofs served: {self.proof_served:,}"
                f" across {self.proof_nodes} node(s)"
                f" ({self.proof_subs:,} subscriptions,"
                f" {self.proof_shed:,} shed = {shed_pct:.1f} %)\n"
                f" Worst proof: {self.proof_worst_bytes:,} B\n"
            )
        election = ""
        if self.elect_nodes and self.elect_rounds:
            match_pct = 100.0 * self.elect_matches / self.elect_rounds
            hops_per = self.elect_hops / self.elect_rounds
            blind_per = self.elect_hops_blind / self.elect_rounds
            election = (
                " + ELECTION:\n"
                f" Pivots scored: {self.elect_rounds:,} committed round(s)"
                f" across {self.elect_nodes} node(s)\n"
                f" Co-located: {self.elect_matches:,} ({match_pct:.1f} %);"
                f" cross-region hops: {self.elect_hops:,}"
                f" ({hops_per:.3f}/commit vs {blind_per:.3f} under"
                " round-robin)\n"
            )
        reconfig = ""
        if self.epoch_switches or self.handoffs or self.range_lags:
            reconfig = " + RECONFIG:\n"
            if self.epoch_switches:
                top_epoch, top_round = max(self.epoch_switches)
                reconfig += (
                    f" Epoch switches observed: {len(self.epoch_switches)}"
                    f" (highest epoch {top_epoch} at round {top_round})\n"
                )
            if self.handoffs:
                rotations = len({e for e, _t, _b, _s in self.handoffs})
                # worst = SMALLEST slack: the handoff that came closest
                # to its boundary (the margin-sizing signal).
                worst_slack = min(s for _e, _t, _b, s in self.handoffs)
                reconfig += (
                    f" Handoffs: {len(self.handoffs)} across"
                    f" {rotations} rotation(s), worst slack"
                    f" {worst_slack} round(s) before the boundary\n"
                )
            if self.range_lags:
                reconfig += (
                    f" Catch-up: {len(self.range_lags)} range sync(s),"
                    f" worst start lag {max(self.range_lags)} rounds,"
                    f" {self.range_blocks} blocks fetched\n"
                )
        lint = ""
        if self.graftlint_findings is not None:
            lint = (
                " + LINT:\n"
                f" graftlint: {self.graftlint_findings} findings\n"
            )
        warn = ""
        if self.graftlint_findings:
            warn += (
                f" WARNING: graftlint reported {self.graftlint_findings} "
                "finding(s) — the deployed tree violates committed "
                "contracts\n"
            )
        if self.handoff_violations:
            warn += (
                f" WARNING: {self.handoff_violations} epoch handoff "
                "VIOLATION(s) — a commit landed at/past its declared "
                "activation round (the epoch-final invariant; gap rounds "
                "were certified by the old committee)\n"
            )
        if self.incident_unattributed or self.burn_verdict == "violated":
            warn += (
                f" WARNING: incident ledger left "
                f"{self.incident_unattributed} alert(s) unattributed and "
                f"judged the burn budget {self.burn_verdict or 'unjudged'} "
                f"({self.burn_over} SLO row(s) over) — fault attribution "
                "or the error budget broke down\n"
            )
        if self.misses:
            warn += f" WARNING: {self.misses} rate-too-high warnings\n"
        if self.timeouts > 2:
            warn += f" WARNING: {self.timeouts} timeouts\n"
        if self.watchdog_fired:
            reasons = ", ".join(sorted(set(self.watchdog_fired)))
            warn += (
                f" WARNING: anomaly watchdog fired {len(self.watchdog_fired)}x"
                f" ({reasons}); {len(self.watchdog_dumps)} recorder dump(s)"
                " written — read them before trusting these numbers\n"
            )
        return (
            "\n-----------------------------------------\n"
            " SUMMARY:\n"
            "-----------------------------------------\n"
            " + CONFIG:\n"
            f" Committee size: {self.committee_size} nodes\n"
            f" Faults: {self.faults} nodes\n"
            f" Input rate: {self.rate:,} tx/s\n"
            f" Transaction size: {self.size:,} B\n"
            f" {self.configs}\n"
            f"{warn}"
            " + RESULTS:\n"
            f" Consensus TPS: {round(c_tps):,} tx/s\n"
            f" Consensus BPS: {round(c_bps):,} B/s\n"
            f" Consensus latency: {round(c_lat * 1000):,} ms\n"
            f" End-to-end TPS: {round(e_tps):,} tx/s\n"
            f" End-to-end BPS: {round(e_bps):,} B/s\n"
            f" End-to-end latency: {round(e_lat * 1000):,} ms\n"
            f" Batch verification rate: {round(v_rate):,} sigs/s ({v_total:,} total)\n"
            + self._sidecar_line()
            + (
                f" Workload shed at saturation: >= {self.workload_shed:,} sigs\n"
                if self.workload_shed
                else ""
            )
            + ingress
            + network
            + telemetry
            + incidents
            + lint
            + matrix
            + agg
            + certs
            + proofs
            + election
            + reconfig
            + mtr
            + "-----------------------------------------\n"
        )

    def _sidecar_line(self) -> str:
        rep = ((self.sidecar_metrics or {}).get("info") or {}).get("backend")
        if not isinstance(rep, dict):
            return ""
        cached = (self.sidecar_metrics.get("counters") or {}).get(
            "verifier.dedup_hits", 0
        )
        return (
            f" Sidecar device: {rep.get('platform')} "
            f"({rep.get('device_kind')} x{rep.get('device_count')}), "
            f"{rep.get('tpu_sigs', 0):,} sigs on device, "
            f"{rep.get('cpu_sigs', 0):,} sub-crossover on its host, "
            f"{cached:,} answered from its verified-signature cache, "
            f"programs {rep.get('dispatched')}\n"
        )

    @classmethod
    def process(cls, directory: str, faults: int = 0) -> "LogParser":
        clients = []
        for path in sorted(glob(join(directory, "client-*.log"))):
            with open(path) as f:
                clients.append(f.read())
        nodes = []
        for path in sorted(glob(join(directory, "node-*.log"))):
            with open(path) as f:
                nodes.append(f.read())
        sidecar = None
        try:
            with open(join(directory, "sidecar.log")) as f:
                sidecar = f.read()
        except OSError:
            pass
        return cls(clients, nodes, faults, sidecar=sidecar)
