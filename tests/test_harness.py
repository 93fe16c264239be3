"""Benchmark-harness unit tests: aggregation family files and the three
reference plot families (reference aggregate.py:75-174, plot.py:56-164),
driven from synthetic result files in the reference's result format."""

import os

import pytest

from benchmark.aggregate import aggregate_results, parse_result_file


RESULT_TEMPLATE = """\
-----------------------------------------
 SUMMARY:
-----------------------------------------
 + CONFIG:
 Faults: {faults} nodes
 Committee size: {nodes} nodes
 Input rate: {rate:,} tx/s
 Transaction size: {tx} B
 Execution time: 20 s

 + RESULTS:
 Consensus TPS: {ctps:,} tx/s
 Consensus latency: {clat} ms

 End-to-end TPS: {etps:,} tx/s
 End-to-end latency: {elat} ms
-----------------------------------------
"""


def write_result(directory, nodes, rate, faults, run, etps, elat):
    path = os.path.join(
        directory, f"bench-{nodes}-{rate}-512-{faults}-{run}.txt"
    )
    with open(path, "w") as f:
        f.write(
            RESULT_TEMPLATE.format(
                faults=faults,
                nodes=nodes,
                rate=rate,
                tx=512,
                ctps=etps + 10,
                clat=max(1, elat - 5),
                etps=etps,
                elat=elat,
            )
        )
    return path


@pytest.fixture
def results_dir(tmp_path):
    d = str(tmp_path)
    # 4-node sweep: healthy, then saturated at 20k
    write_result(d, 4, 1_000, 0, 0, 950, 30)
    write_result(d, 4, 1_000, 0, 1, 970, 34)  # repeat run
    write_result(d, 4, 10_000, 0, 0, 9_800, 60)
    write_result(d, 4, 20_000, 0, 0, 12_000, 9_000)  # saturated
    # 10-node point and a faulty run
    write_result(d, 10, 10_000, 0, 0, 9_500, 120)
    write_result(d, 4, 1_000, 1, 0, 700, 800)
    return d


def test_parse_result_file(results_dir):
    r = parse_result_file(
        os.path.join(results_dir, "bench-4-1000-512-0-0.txt")
    )
    assert r["nodes"] == 4 and r["rate"] == 1_000
    assert r["e2e_tps"] == 950 and r["e2e_latency"] == 30


def test_aggregate_means_and_family_files(results_dir):
    agg = aggregate_results(results_dir)
    # repeated runs averaged with stdev
    key = (4.0, 0.0, 512.0, 1_000.0)
    assert agg[key]["e2e_tps"]["runs"] == 2
    assert agg[key]["e2e_tps"]["mean"] == 960
    assert agg[key]["e2e_tps"]["stdev"] > 0
    for name in ("aggregated.txt", "agg-latency.txt", "agg-robustness.txt", "agg-tps.txt"):
        assert os.path.exists(os.path.join(results_dir, name)), name
    with open(os.path.join(results_dir, "agg-tps.txt")) as f:
        tps = f.read()
    # under a 2s SLO the saturated 20k point must NOT win for 4 nodes
    assert "max_latency_ms=2000 nodes=4 best_tps=9800" in tps
    # faulty runs are excluded from the SLO family
    assert "best_tps=700" not in tps


def test_plot_families(results_dir):
    pytest.importorskip("matplotlib")
    from benchmark.plot import plot_results

    outs = plot_results(results_dir)
    assert len(outs) == 3
    for o in outs:
        assert os.path.getsize(o) > 1_000  # a real PDF, not an empty file
    names = {os.path.basename(o) for o in outs}
    assert names == {
        "latency-vs-throughput.pdf",
        "tps-vs-committee.pdf",
        "robustness.pdf",
    }


# ---------------------------------------------------------------------------
# LogParser: synthetic log scraping + crash scan (reference logs.py:27-39,71,88)


CLIENT_LOG = """\
[2026-07-30T10:00:00.000Z INFO hotstuff.client] Transactions size: 512 B
[2026-07-30T10:00:00.001Z INFO hotstuff.client] Transactions rate: 1000 tx/s
[2026-07-30T10:00:00.002Z INFO hotstuff.client] Start sending transactions
[2026-07-30T10:00:00.100Z INFO hotstuff.client] Sending sample transaction 0
[2026-07-30T10:00:01.100Z INFO hotstuff.client] Sending sample transaction 1
"""

NODE_LOG = """\
[2026-07-30T10:00:00.000Z INFO hotstuff.node] Timeout delay set to 5000 ms
[2026-07-30T10:00:00.200Z INFO hotstuff.mempool] Payload abc= contains 1024 B
[2026-07-30T10:00:00.201Z INFO hotstuff.mempool] Payload abc= contains sample tx 0
[2026-07-30T10:00:00.300Z INFO hotstuff.consensus] Created B1(b1=)
[2026-07-30T10:00:00.900Z INFO hotstuff.consensus] Committed B1(b1=)
[2026-07-30T10:00:00.901Z INFO hotstuff.consensus] Committed B1(b1=) -> abc=
[2026-07-30T10:00:01.000Z INFO hotstuff.mempool] Verifying OWN transaction batch. Size: 500
[2026-07-30T10:00:02.000Z INFO hotstuff.mempool] Verifying OTHER transaction batch. Size: 700
"""


def test_log_parser_metrics():
    from benchmark.logs import LogParser

    p = LogParser([CLIENT_LOG], [NODE_LOG])
    assert p.size == 512 and p.rate == 1000
    tps, bps, _ = p.consensus_throughput()
    assert bps > 0 and tps == pytest.approx(bps / 512)
    assert p.consensus_latency() == pytest.approx(0.6)
    # sample 0 sent at t=0.100, payload committed at t=0.901
    assert p.end_to_end_latency() == pytest.approx(0.801)
    rate, total = p.verification_throughput()
    assert total == 1200 and rate == pytest.approx(1200.0)
    assert "Consensus TPS" in p.result()


@pytest.mark.parametrize(
    "bad",
    [
        "[...] Traceback (most recent call last):\n",
        "[2026-07-30T10:00:03.000Z ERROR hotstuff.consensus] consensus core error: boom\n",
        "actor mempool-verify crashed: RuntimeError()\n",
    ],
)
def test_log_parser_raises_on_crash_lines(bad):
    from benchmark.logs import LogParser, ParseError

    with pytest.raises(ParseError):
        LogParser([CLIENT_LOG], [NODE_LOG + bad])
    with pytest.raises(ParseError):
        LogParser([CLIENT_LOG + bad], [NODE_LOG])


def test_log_parser_steady_state_window_excludes_boot_skew():
    """On an oversubscribed host the last client may start minutes after the
    first; throughput must be measured from the LAST client's start, with
    ramp-period commits excluded from the numerator too."""
    from benchmark.logs import LogParser

    early_client = CLIENT_LOG  # starts at 10:00:00.002
    late_client = early_client.replace("10:00:0", "10:01:0")  # starts 60s later
    # One payload commits during the ramp (before the late client starts),
    # one after; only the latter counts, over the post-steady window.
    node = NODE_LOG + (
        "[2026-07-30T10:01:00.300Z INFO hotstuff.consensus] Created B9(b9=)\n"
        "[2026-07-30T10:01:02.000Z INFO hotstuff.mempool] Payload xyz= contains 2048 B\n"
        "[2026-07-30T10:01:02.900Z INFO hotstuff.consensus] Committed B9(b9=)\n"
        "[2026-07-30T10:01:02.901Z INFO hotstuff.consensus] Committed B9(b9=) -> xyz=\n"
    )
    p = LogParser([early_client, late_client], [node])
    assert p.steady_start == pytest.approx(p.start + 60.0)
    tps, bps, duration = p.end_to_end_throughput()
    # window: last client start 10:01:00.002 -> last commit 10:01:02.900
    assert duration == pytest.approx(2.898, abs=0.01)
    assert bps == pytest.approx(2048 / 2.898, rel=0.01)  # abc= excluded
    # consensus window clamps to steady_start as well
    _, c_bps, c_dur = p.consensus_throughput()
    assert c_dur == pytest.approx(2.898, abs=0.01)
    assert c_bps == pytest.approx(2048 / 2.898, rel=0.01)
    # latency is windowed too: only B9 (proposed in-window, 2.6 s) counts,
    # not the uncontended ramp block B1 (0.6 s).
    assert p.consensus_latency() == pytest.approx(2.6)


def test_log_parser_single_client_window_unchanged():
    """With one client (or synchronized starts) steady_start == start and
    the metrics match the reference semantics."""
    from benchmark.logs import LogParser

    p = LogParser([CLIENT_LOG], [NODE_LOG])
    assert p.steady_start == p.start
    tps, bps, _ = p.end_to_end_throughput()
    assert bps > 0


def test_log_parser_reports_workload_shed():
    """The periodic saturation warning's cumulative counter surfaces as a
    'Workload shed' line; absent when never saturated."""
    from benchmark.logs import LogParser

    assert "Workload shed" not in LogParser([CLIENT_LOG], [NODE_LOG]).result()
    node = NODE_LOG + (
        "[2026-07-30T10:00:03.000Z WARNING hotstuff.mempool] verification "
        "pipeline saturated: 100195 synthetic workload signatures skipped "
        "so far (measured rate reflects capacity, not demand)\n"
        "[2026-07-30T10:00:04.000Z WARNING hotstuff.mempool] verification "
        "pipeline saturated: 200390 synthetic workload signatures skipped "
        "so far (measured rate reflects capacity, not demand)\n"
    )
    p = LogParser([CLIENT_LOG], [node])
    assert p.workload_shed == 200390  # LAST cumulative value, not a sum
    assert "Workload shed at saturation: >= 200,390 sigs" in p.result()


def test_log_parser_scrapes_ingress_lines():
    """The ingress load generator's result lines (loadgen.log_summary)
    surface as an INGRESS section: offered/accepted/shed totals summed
    across clients, mean p50, worst p99; absent on Front-only runs."""
    from benchmark.logs import LogParser

    assert "+ INGRESS" not in LogParser([CLIENT_LOG], [NODE_LOG]).result()
    ingress_lines = (
        "[2026-07-30T10:00:20.000Z INFO hotstuff.loadgen] Ingress offered: "
        "840 transactions\n"
        "[2026-07-30T10:00:20.001Z INFO hotstuff.loadgen] Ingress accepted: "
        "510 transactions\n"
        "[2026-07-30T10:00:20.002Z INFO hotstuff.loadgen] Ingress shed: "
        "330 transactions\n"
        "[2026-07-30T10:00:20.003Z INFO hotstuff.loadgen] Ingress client "
        "latency p50: 76.0 ms\n"
        "[2026-07-30T10:00:20.004Z INFO hotstuff.loadgen] Ingress client "
        "latency p99: 7626.0 ms\n"
    )
    quiet_client = CLIENT_LOG  # a client with no ingress traffic
    loud_client = CLIENT_LOG + ingress_lines
    louder = CLIENT_LOG + ingress_lines.replace("76.0", "100.0").replace(
        "7626.0", "9000.0"
    )
    p = LogParser([quiet_client, loud_client, louder], [NODE_LOG])
    assert p.ingress_offered == 1_680
    assert p.ingress_accepted == 1_020
    assert p.ingress_shed == 660
    assert p.ingress_p50s == [76.0, 100.0]
    out = p.result()
    assert "+ INGRESS:" in out
    assert "1,680 tx (1,020 accepted, 660 shed = 39.3 %)" in out
    assert "p50 (mean across clients): 88.0 ms" in out
    assert "p99 (worst client): 9,000.0 ms" in out


def test_log_parser_surfaces_watchdog_firings():
    """Anomaly-watchdog WARNING lines (utils/tracing.py) surface as a
    summary warning with reasons and dump count; absent when quiet."""
    from benchmark.logs import LogParser

    assert "anomaly watchdog" not in LogParser([CLIENT_LOG], [NODE_LOG]).result()
    node = NODE_LOG + (
        "[2026-07-30T10:00:05.000Z WARNING hotstuff.tracing] anomaly "
        "watchdog fired: round_stall {'round': 9, 'consecutive': 3}\n"
        "[2026-07-30T10:00:05.001Z WARNING hotstuff.tracing] watchdog "
        "round_stall: flight recorder dumped to /tmp/n0.trace.json."
        "watchdog-round_stall-1.json\n"
    )
    p = LogParser([CLIENT_LOG], [node])
    assert p.watchdog_fired == ["round_stall"]
    assert len(p.watchdog_dumps) == 1
    out = p.result()
    assert "anomaly watchdog fired 1x (round_stall)" in out
    assert "1 recorder dump(s)" in out


def test_log_parser_scrapes_graftlint_summary():
    """The static-analysis summary line (tools/graftlint) surfaces as a
    LINT section; a nonzero count also warns. The LAST line per node
    wins and the WORST node count is reported; absent on unlinted runs."""
    from benchmark.logs import LogParser

    quiet = LogParser([CLIENT_LOG], [NODE_LOG])
    assert quiet.graftlint_findings is None
    assert "+ LINT" not in quiet.result()

    clean = NODE_LOG + (
        "[2026-07-30T10:00:00.500Z INFO hotstuff.node] graftlint: 0 "
        "findings (6 pragma-allowed, 9 baselined, 10 passes)\n"
    )
    dirty = NODE_LOG + (
        "[2026-07-30T10:00:00.400Z INFO hotstuff.node] graftlint: 7 "
        "findings (0 pragma-allowed, 0 baselined, 10 passes)\n"
        "[2026-07-30T10:00:00.500Z INFO hotstuff.node] graftlint: 3 "
        "findings (0 pragma-allowed, 0 baselined, 10 passes)\n"
    )
    p = LogParser([CLIENT_LOG], [clean])
    assert p.graftlint_findings == 0
    out = p.result()
    assert " + LINT:\n graftlint: 0 findings\n" in out
    assert "WARNING: graftlint" not in out

    p = LogParser([CLIENT_LOG], [clean, dirty])
    assert p.graftlint_findings == 3  # last line per node, worst node
    out = p.result()
    assert "graftlint: 3 findings" in out
    assert "WARNING: graftlint reported 3 finding(s)" in out


# ---------------------------------------------------------------------------
# LogParser: METRICS snapshot scraping (utils/metrics.py periodic emitter)


def _metrics_line(ts: str, counters: dict, histograms: dict | None = None) -> str:
    import json

    snap = {
        "v": 1,
        "counters": counters,
        "gauges": {},
        "histograms": histograms or {},
    }
    return (
        f"[{ts} INFO hotstuff.metrics] METRICS "
        + json.dumps(snap, separators=(",", ":"))
        + "\n"
    )


def test_log_parser_scrapes_metrics_snapshots_interleaved():
    """Cumulative snapshots interleave with Committed/Verifying lines; the
    LAST snapshot per node wins, counters sum across nodes, and the
    existing metrics are unaffected."""
    from benchmark.logs import LogParser

    node1 = (
        NODE_LOG
        + _metrics_line("2026-07-30T10:00:01.500Z", {"consensus.commits": 1})
        + "[2026-07-30T10:00:02.500Z INFO hotstuff.consensus] Committed B2(b2=)\n"
        + _metrics_line(
            "2026-07-30T10:00:03.000Z",
            {"consensus.commits": 2, "net.bytes_sent": 4096},
            {"verifier.e2e_s": {"count": 4, "sum": 0.08, "max": 0.03}},
        )
    )
    node2 = NODE_LOG + _metrics_line(
        "2026-07-30T10:00:03.000Z",
        {"consensus.commits": 2},
        {"verifier.e2e_s": {"count": 1, "sum": 0.02, "max": 0.02}},
    )
    p = LogParser([CLIENT_LOG], [node1, node2])
    assert len(p.node_metrics) == 2
    # last-per-node counters summed: 2 + 2, not 1 + 2 + 2
    assert p.metrics["counters"]["consensus.commits"] == 4
    assert p.metrics["counters"]["net.bytes_sent"] == 4096
    h = p.metrics["histograms"]["verifier.e2e_s"]
    assert h["count"] == 5 and h["sum"] == pytest.approx(0.10)
    assert h["max"] == pytest.approx(0.03)
    # the non-metrics scraping still sees every line
    rate, total = p.verification_throughput()
    assert total == 2400  # two copies of NODE_LOG
    out = p.result()
    assert "+ METRICS (2 node snapshots):" in out
    assert "consensus.commits: 4" in out


def test_log_parser_tolerates_malformed_metrics_snapshot():
    """A snapshot truncated by SIGTERM mid-line (or otherwise malformed)
    must be skipped, never raise ParseError; earlier well-formed snapshots
    still count."""
    from benchmark.logs import LogParser

    node = (
        NODE_LOG
        + _metrics_line("2026-07-30T10:00:01.500Z", {"consensus.commits": 7})
        + "[2026-07-30T10:00:03.000Z INFO hotstuff.metrics] METRICS {\"counters\":{\"consensus.comm\n"
        + "[2026-07-30T10:00:04.000Z INFO hotstuff.metrics] METRICS {not json at all}\n"
    )
    p = LogParser([CLIENT_LOG], [node])
    assert len(p.node_metrics) == 1
    assert p.metrics["counters"]["consensus.commits"] == 7


def test_log_parser_no_metrics_lines_yields_empty_aggregate():
    from benchmark.logs import LogParser

    p = LogParser([CLIENT_LOG], [NODE_LOG])
    assert p.node_metrics == []
    assert p.metrics == {"counters": {}, "histograms": {}}
    assert "+ METRICS" not in p.result()


def test_log_parser_scrapes_cert_plane_lines():
    """The consensus core's cumulative 'Cert plane:' line surfaces as a
    CERTS section: counts summed across nodes (LAST line per node — the
    counter is cumulative), worst cert bytes and aggregation depth maxed;
    absent when no node ever logged it."""
    from benchmark.logs import LogParser

    assert "+ CERTS" not in LogParser([CLIENT_LOG], [NODE_LOG]).result()
    node_a = NODE_LOG + (
        "[2026-07-30T10:00:01.100Z INFO hotstuff.consensus] Cert plane: "
        "3 aggregate / 2 entry-list certs committed, worst cert 428 B, "
        "agg depth 2\n"
        "[2026-07-30T10:00:02.100Z INFO hotstuff.consensus] Cert plane: "
        "9 aggregate / 2 entry-list certs committed, worst cert 428 B, "
        "agg depth 3\n"
    )
    node_b = NODE_LOG + (
        "[2026-07-30T10:00:02.200Z INFO hotstuff.consensus] Cert plane: "
        "7 aggregate / 1 entry-list certs committed, worst cert 204 B, "
        "agg depth 5\n"
    )
    p = LogParser([CLIENT_LOG], [node_a, node_b])
    assert (p.cert_agg, p.cert_legacy) == (16, 3)  # 9+7, 2+1: lasts, not sums
    assert p.cert_worst_bytes == 428 and p.cert_depth == 5
    assert p.cert_nodes == 2
    out = p.result()
    assert "+ CERTS:" in out
    assert "19 (16 aggregate = 84.2 %, 3 entry-list) across 2 node(s)" in out
    assert "Worst cert: 428 B, aggregation depth 5" in out


# ---------------------------------------------------------------------------
# tools/chaos_run.py: the chaos scenario CLI (hotstuff_tpu/chaos)


def test_chaos_run_cli_smoke(tmp_path):
    """rc 0 and a well-formed JSON report from one short seeded scenario
    (subprocess, like the node CLI tests — proves the tool runs standalone
    without jax or the OpenSSL wheel)."""
    import json
    import subprocess
    import sys

    report_path = tmp_path / "chaos.json"
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(os.path.dirname(__file__), "..", "tools", "chaos_run.py"),
            "--scenario",
            "baseline",
            "--seed",
            "1",
            "--report",
            str(report_path),
        ],
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "baseline: OK" in proc.stdout
    report = json.loads(report_path.read_text())
    for key in (
        "scenario",
        "commits",
        "fault_trace",
        "safety_violations",
        "liveness_violations",
        "metrics",
        "flight_recorders",
        "watchdog_dumps",
        "ok",
    ):
        assert key in report, key
    assert report["ok"] is True
    assert report["scenario"] == "baseline"
    assert all(len(c) >= 1 for c in report["commits"].values())
    # per-node flight-recorder dumps are embedded: every node recorded
    # stage events, so a failed scenario is diagnosable from the report
    recorders = report["flight_recorders"]
    assert sorted(recorders) == ["0", "1", "2", "3"]
    assert all(
        any(e["kind"] == "commit" for e in evs) for evs in recorders.values()
    )


def test_chaos_run_cli_rejects_unknown_scenario(tmp_path):
    import subprocess
    import sys

    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(os.path.dirname(__file__), "..", "tools", "chaos_run.py"),
            "--scenario",
            "no-such-scenario",
        ],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 3
    assert "unknown scenario" in proc.stderr


# ---------------------------------------------------------------------------
# tools/loadgen.py: the open-loop ingress load generator CLI


def test_loadgen_cli_selftest_smoke(tmp_path):
    """rc 0 and a well-formed JSON summary from the in-process selftest
    (virtual-time loop, pure-python signatures — no node, no jax, no
    OpenSSL wheel). The flash spike exceeds the paced capacity, so the
    summary must show shedding with retry hints."""
    import json
    import subprocess
    import sys

    out_path = tmp_path / "loadgen.json"
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(os.path.dirname(__file__), "..", "tools", "loadgen.py"),
            "--selftest",
            "--curve", "flash",
            "--rate", "15",
            "--peak", "90",
            "--duration", "6",
            "--capacity", "30",
            "--clients", "4",
            "--seed", "3",
            "--json-out", str(out_path),
        ],
        capture_output=True,
        text=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary == json.loads(out_path.read_text())
    for key in (
        "curve", "offered", "accepted", "shed", "retry_hints",
        "shed_rate", "latency_ms", "mode",
    ):
        assert key in summary, key
    assert summary["mode"] == "selftest"
    assert summary["offered"] > summary["accepted"] > 0
    assert summary["shed"] > 0 and summary["retry_hints"] == summary["shed"]
    assert summary["latency_ms"]["p99"] >= summary["latency_ms"]["p50"]


# ---------------------------------------------------------------------------
# tools/lint_metrics.py: the metric/trace namespace lint


_LINT = os.path.join(os.path.dirname(__file__), "..", "tools", "lint_metrics.py")


def test_lint_metrics_passes_on_repo():
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, _LINT],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr
    assert "clean" in proc.stdout


def test_lint_metrics_flags_unregistered_names(tmp_path):
    import subprocess
    import sys

    bad = tmp_path / "rogue.py"
    bad.write_text(
        "from hotstuff_tpu.utils import metrics, tracing\n"
        'C = metrics.counter("rogue.metric_name")\n'
        'tracing.event("rogue.stage")\n'
    )
    proc = subprocess.run(
        [sys.executable, _LINT, "--root", str(tmp_path)],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 1
    assert "rogue.metric_name" in proc.stderr
    assert "rogue.stage" in proc.stderr


def test_lint_pipeline_flags_unknown_timeline_stage(monkeypatch):
    """lint_pipeline: a DispatchPipeline stage name outside DeviceTimeline's
    PHASES vocabulary must be a violation (it would fall out of the
    occupancy math and the trace_report device rows); the real vocabulary
    is clean."""
    import importlib.util

    spec = importlib.util.spec_from_file_location("lint_metrics", _LINT)
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    assert lint.lint_pipeline() == []
    from hotstuff_tpu.ops import pipeline

    monkeypatch.setattr(pipeline, "TIMELINE_STAGES", ("stage", "warp"))
    problems = lint.lint_pipeline()
    assert len(problems) == 1 and "'warp'" in problems[0]


def test_lint_flags_unregistered_scheduler_source(tmp_path):
    """The starvation lint's call-site half: a verify_group call declaring
    a source class the scheduler never registered would raise at runtime —
    the lint catches it statically (rc 1)."""
    import subprocess
    import sys

    bad = tmp_path / "rogue_source.py"
    bad.write_text(
        "async def f(svc, m, p):\n"
        '    return await svc.verify_group(m, p, source="warpdrive")\n'
    )
    proc = subprocess.run(
        [sys.executable, _LINT, "--root", str(tmp_path)],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 1
    assert "warpdrive" in proc.stderr
    assert "SOURCE_CLASSES" in proc.stderr


def test_lint_scheduler_starvation_check_runs():
    """The drain-simulation half, invoked directly: every registered
    class drains today (empty problem list), and the schema half really
    compares against the canonical namespace (dropping a class's
    histogram row is reported)."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import lint_metrics

    assert lint_metrics.lint_scheduler() == []
    # Simulate a missing per-lane histogram row: the schema half of the
    # starvation lint must name the class and the missing row.
    from hotstuff_tpu.utils import metrics as m

    real = m._DEFAULT_NAMESPACE
    try:
        m._DEFAULT_NAMESPACE = tuple(
            row for row in real if row[0] != "scheduler.queue_ingress_s"
        )
        problems = lint_metrics.lint_scheduler()
    finally:
        m._DEFAULT_NAMESPACE = real
    assert any("scheduler.queue_ingress_s" in p for p in problems)


def test_lint_telemetry_rejects_non_histogram_slo_binding(monkeypatch):
    """An SLOSpec bound to a registered COUNTER row passes the
    name-exists check but the burn evaluator would silently never see an
    event — the lint must name the kind mismatch."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    import lint_metrics

    from hotstuff_tpu.utils import telemetry
    from hotstuff_tpu.utils.telemetry import SLOSpec

    assert lint_metrics.lint_telemetry() == []
    monkeypatch.setattr(
        telemetry,
        "default_slos",
        lambda: (
            SLOSpec("bad", "telemetry.snapshots", threshold_s=1.0),
        ),
    )
    problems = lint_metrics.lint_telemetry()
    assert any(
        "telemetry.snapshots" in p and "counter" in p for p in problems
    )


# ---------------------------------------------------------------------------
# tools/metrics_report.py: chaos reports render flight-recorder sections


def test_metrics_report_renders_chaos_flight_recorders():
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import metrics_report

    chaos_report = {
        "counters": {"chaos.drops": 7},
        "histograms": {},
        "flight_recorders": {
            "0": [
                {"t": 1.0, "kind": "commit", "trace": "r1-aa", "node": 0},
                {"t": 1.5, "kind": "timeout", "node": 0},
            ],
            "1": [{"t": 1.1, "kind": "commit", "trace": "r1-aa", "node": 1}],
        },
        "watchdog_triggers": [
            {"t": 2.0, "reason": "round_stall", "round": 9, "consecutive": 3}
        ],
        "watchdog_dumps": [{"reason": "round_stall", "events": []}],
    }
    out = metrics_report.report(chaos_report)
    assert "Flight recorders" in out
    assert "| 0 | 2 |" in out
    assert "round_stall" in out
    assert "chaos.drops" in out


def test_metrics_report_load_accepts_chaos_report(tmp_path):
    import json
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "tools")
    )
    import metrics_report

    path = tmp_path / "chaos.json"
    path.write_text(json.dumps({
        "metrics": {"chaos.crashes": 1},
        "flight_recorders": {"0": []},
        "ok": True,
    }))
    d = metrics_report._load(str(path))
    assert d["counters"] == {"chaos.crashes": 1}
    assert "flight_recorders" in d


# ---------------------------------------------------------------------------
# tools/telemetry_dash.py: the live/offline telemetry dashboard


_DASH = os.path.join(
    os.path.dirname(__file__), "..", "tools", "telemetry_dash.py"
)


def _run_dash(*argv):
    import subprocess
    import sys

    return subprocess.run(
        [sys.executable, _DASH, *argv],
        capture_output=True,
        text=True,
        timeout=120,
    )


@pytest.mark.chaos
def test_telemetry_dash_live_and_offline_render_identical(tmp_path):
    """The acceptance contract: the dashboard polled over a REAL TCP
    scrape and the same node's section read out of the chaos report
    produce identical normalized records — rc 0 + well-formed JSON in
    both modes. The live side serves the report's telemetry entry
    verbatim (TelemetryServer dict source), so any divergence is the
    dashboard's fault, not the workload's."""
    import json

    from hotstuff_tpu.chaos.scenarios import run_scenario
    from hotstuff_tpu.utils import telemetry

    report = run_scenario("slo_burn_bulk", seed=11)
    assert report["ok"], report.get("expectation_failures") or report
    report_path = tmp_path / "chaos.json"
    report_path.write_text(json.dumps(report, sort_keys=True, default=str))

    # offline: rc 0, one well-formed record per node, alerts visible
    proc = _run_dash("--report", str(report_path), "--json")
    assert proc.returncode == 0, proc.stderr[-2000:]
    offline = json.loads(proc.stdout)
    assert offline["mode"] == "offline"
    assert len(offline["nodes"]) == len(report["telemetry"])
    by_node = {rec["node"]: rec for rec in offline["nodes"]}
    assert all(rec["alerts_fired"] >= 1 for rec in offline["nodes"])
    assert all(rec["snapshots"] >= 2 for rec in offline["nodes"])

    # markdown mode also rc 0 (the human path)
    md = _run_dash("--report", str(report_path))
    assert md.returncode == 0, md.stderr[-2000:]
    assert "Telemetry dashboard (offline" in md.stdout
    assert "SLO burn alerts" in md.stdout

    # live: serve node 0's report entry verbatim and poll it
    port = telemetry.serve_in_thread(report["telemetry"]["0"])
    live_proc = _run_dash("--poll", f"127.0.0.1:{port}", "--json")
    assert live_proc.returncode == 0, live_proc.stderr[-2000:]
    live = json.loads(live_proc.stdout)
    assert live["mode"] == "live" and not live["errors"]
    (live_rec,) = live["nodes"]
    assert live_rec == by_node[live_rec["node"]]


def test_telemetry_dash_rejects_sweep_and_unreachable(tmp_path):
    """rc 3 on a multi-scenario sweep report (per-node telemetry would be
    cross-contaminated), rc 2 when a poll target refuses connections."""
    import json

    sweep = tmp_path / "sweep.json"
    sweep.write_text(json.dumps({"scenarios": {"baseline": {}}}))
    assert _run_dash("--report", str(sweep)).returncode == 3
    proc = _run_dash("--poll", "127.0.0.1:9", "--json", "--timeout", "2")
    assert proc.returncode == 2
    assert json.loads(proc.stdout)["errors"]


def test_log_parser_scrapes_telemetry_lines():
    """SLO-burn fired/cleared lines and the periodic device-occupancy
    line (utils/telemetry.py) fold into the report's `+ TELEMETRY:`
    section: worst-node occupancy + alert counts. Absent when quiet."""
    from benchmark.logs import LogParser

    assert "+ TELEMETRY:" not in LogParser([CLIENT_LOG], [NODE_LOG]).result()
    node_a = NODE_LOG + (
        "[2026-07-30T10:00:05.000Z WARNING hotstuff.telemetry] SLO burn "
        "fired: lane.mempool (burn 4.0x short / 2.5x long, threshold 0.500s)\n"
        "[2026-07-30T10:00:09.000Z WARNING hotstuff.telemetry] SLO burn "
        "cleared: lane.mempool\n"
        "[2026-07-30T10:00:09.500Z INFO hotstuff.telemetry] TELEMETRY "
        "device occupancy 61.3% overlap headroom 82.0%\n"
    )
    node_b = NODE_LOG + (
        "[2026-07-30T10:00:02.000Z INFO hotstuff.telemetry] TELEMETRY "
        "device occupancy 90.0% overlap headroom 10.0%\n"
        "[2026-07-30T10:00:08.000Z INFO hotstuff.telemetry] TELEMETRY "
        "device occupancy 44.8% overlap headroom 71.5%\n"
    )
    p = LogParser([CLIENT_LOG], [node_a, node_b])
    assert p.slo_fired == ["lane.mempool"]
    assert p.slo_cleared == ["lane.mempool"]
    # per node, only the LAST occupancy line counts (cumulative ring)
    assert sorted(p.occupancies) == [(44.8, 71.5), (61.3, 82.0)]
    out = p.result()
    assert "+ TELEMETRY:" in out
    assert "Worst-node device occupancy: 44.8 %" in out
    assert "overlap headroom 71.5 %" in out
    assert "SLO burn alerts: 1 fired (lane.mempool), 1 cleared" in out


def test_log_parser_scrapes_incident_lines():
    """Incident-ledger summary and burn-budget verdict lines
    (utils/incidents.py) fold into the report's `+ INCIDENTS:` section:
    counts summed across logs, worst MTTR maxed, 'violated' sticky over
    'ok'. The LAST summary per log wins (a rerun supersedes), and a
    nonzero unattributed count raises a WARNING. Absent when quiet."""
    from benchmark.logs import LogParser

    assert "+ INCIDENTS:" not in LogParser([CLIENT_LOG], [NODE_LOG]).result()
    node_a = NODE_LOG + (
        "[2026-07-30T10:00:09.000Z INFO hotstuff.incidents] Incident "
        "ledger: 3 incident(s), 8 alert(s) attributed, 0 unattributed, "
        "0 residual, worst MTTR 5500.0 ms\n"
        "[2026-07-30T10:00:09.100Z INFO hotstuff.incidents] Burn budget "
        "verdict: ok (0 SLO row(s) over budget)\n"
    )
    node_b = NODE_LOG + (
        # superseded by the later rerun line below (LAST wins)
        "[2026-07-30T10:00:05.000Z INFO hotstuff.incidents] Incident "
        "ledger: 9 incident(s), 9 alert(s) attributed, 9 unattributed, "
        "9 residual, worst MTTR 9.0 ms\n"
        "[2026-07-30T10:00:09.000Z INFO hotstuff.incidents] Incident "
        "ledger: 2 incident(s), 1 alert(s) attributed, 1 unattributed, "
        "1 residual, worst MTTR 250.5 ms\n"
        "[2026-07-30T10:00:09.100Z INFO hotstuff.incidents] Burn budget "
        "verdict: violated (2 SLO row(s) over budget)\n"
    )
    p = LogParser([CLIENT_LOG], [node_a, node_b])
    assert p.incident_ledgers == 2
    assert p.incident_count == 5
    assert p.incident_attributed == 9
    assert p.incident_unattributed == 1
    assert p.incident_residual == 1
    assert p.incident_worst_mttr_ms == 5500.0
    assert p.burn_verdict == "violated" and p.burn_over == 2
    out = p.result()
    assert "+ INCIDENTS:" in out
    assert (
        "Incidents: 5 (9 alert(s) attributed, 1 unattributed, 1 residual)"
        in out
    )
    assert "Worst MTTR: 5,500.0 ms" in out
    assert "Burn budget: violated (2 SLO row(s) over)" in out
    assert "WARNING: incident ledger left 1 alert(s) unattributed" in out
    # clean ledger: section renders, no warning
    clean = LogParser([CLIENT_LOG], [node_a]).result()
    assert "Burn budget: ok (0 SLO row(s) over)" in clean
    assert "WARNING: incident ledger" not in clean


# ---------------------------------------------------------------------------
# Scenario-registry lint (tools/lint_metrics.py lint_scenarios) + the
# LogParser RECONFIG section (benchmark/logs.py)


def _load_lint():
    import importlib.util

    spec = importlib.util.spec_from_file_location("lint_metrics", _LINT)
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    return lint


def test_lint_scenarios_clean_on_repo():
    assert _load_lint().lint_scenarios() == []


def test_lint_scenarios_flags_expectationless_and_unrun(monkeypatch, tmp_path):
    """An expectation-less scenario and a slow scenario named in no test
    module are both rc-1 violations (an 'unregistered' scenario silently
    never runs; an expect-less one passes while its fault stops firing)."""
    from hotstuff_tpu.chaos import scenarios as sc

    lint = _load_lint()
    rogue = sc.Scenario(
        name="ghost_soak",
        description="registered but never run",
        slow=True,
        expect=None,
    )
    monkeypatch.setitem(sc.SCENARIOS, "ghost_soak", rogue)
    # lint_scenarios imports hotstuff_tpu.chaos.scenarios in-process, so
    # the monkeypatched registry is visible; scan an EMPTY tests dir so
    # this very file's string literals don't count as coverage.
    problems = lint.lint_scenarios(tests_dir=str(tmp_path))
    mine = [p for p in problems if "ghost_soak" in p]
    assert len(mine) == 2
    assert any("expectation" in p for p in mine)
    assert any("nothing ever runs it" in p for p in mine)


def test_log_parser_reconfig_section():
    """Epoch-switch and range-sync log lines fold into a '+ RECONFIG:'
    section: switch count with the highest epoch/activation round, and
    catch-up range syncs with the worst start lag + blocks fetched."""
    from benchmark.logs import LogParser

    assert "+ RECONFIG" not in LogParser([CLIENT_LOG], [NODE_LOG]).result()
    node = NODE_LOG + (
        "[2026-07-30T10:00:03.000Z INFO hotstuff.consensus] Epoch switch "
        "to 2 at activation round 15 (4 validators, quorum 3)\n"
        "[2026-07-30T10:00:05.000Z INFO hotstuff.consensus] Range sync "
        "started for KLeV1S+p: 9 rounds behind\n"
        "[2026-07-30T10:00:05.400Z INFO hotstuff.consensus] Range sync "
        "fetched 4 blocks\n"
        "[2026-07-30T10:00:05.800Z INFO hotstuff.consensus] Range sync "
        "fetched 3 blocks\n"
    )
    other = NODE_LOG + (
        "[2026-07-30T10:00:03.100Z INFO hotstuff.consensus] Epoch switch "
        "to 2 at activation round 15 (4 validators, quorum 3)\n"
        "[2026-07-30T10:00:06.000Z INFO hotstuff.consensus] Range sync "
        "started for sIm244D/: 21 rounds behind\n"
        "[2026-07-30T10:00:06.500Z INFO hotstuff.consensus] Range sync "
        "fetched 12 blocks\n"
    )
    p = LogParser([CLIENT_LOG], [node, other])
    assert p.epoch_switches == [(2, 15), (2, 15)]
    assert sorted(p.range_lags) == [9, 21]
    assert p.range_blocks == 19
    out = p.result()
    assert "+ RECONFIG:" in out
    assert "Epoch switches observed: 2 (highest epoch 2 at round 15)" in out
    assert "2 range sync(s), worst start lag 21 rounds, 19 blocks fetched" in out


def test_log_parser_handoff_lines_and_violation_warning():
    """Epoch-final handoff lines (consensus/reconfig.py §5.5j) fold into
    the '+ RECONFIG:' section — rotation count + the WORST slack (the
    handoff that came closest to its boundary, the margin-sizing signal)
    — and a handoff VIOLATION line raises a WARNING (the hard
    invariant: it must normally never appear)."""
    from benchmark.logs import LogParser

    node = NODE_LOG + (
        "[2026-07-30T10:00:03.000Z INFO hotstuff.consensus] Epoch handoff "
        "to 2 committed at round 11 (boundary 14, slack 3 rounds)\n"
        "[2026-07-30T10:00:07.000Z INFO hotstuff.consensus] Epoch handoff "
        "to 3 committed at round 22 (boundary 23, slack 1 rounds)\n"
    )
    other = NODE_LOG + (
        "[2026-07-30T10:00:03.100Z INFO hotstuff.consensus] Epoch handoff "
        "to 2 committed at round 11 (boundary 14, slack 3 rounds)\n"
    )
    p = LogParser([CLIENT_LOG], [node, other])
    assert sorted(p.handoffs) == [(2, 11, 14, 3), (2, 11, 14, 3), (3, 22, 23, 1)]
    assert p.handoff_violations == 0
    out = p.result()
    assert "Handoffs: 3 across 2 rotation(s), worst slack 1 round(s)" in out
    assert "handoff VIOLATION" not in out

    bad = NODE_LOG + (
        "[2026-07-30T10:00:09.000Z WARN hotstuff.consensus] Epoch handoff "
        "VIOLATION: epoch 2 commit landed at round 16, at/past the "
        "declared activation round 15 — gap rounds were certified by the "
        "old committee (the epoch-final wall should have made this "
        "impossible)\n"
    )
    p2 = LogParser([CLIENT_LOG], [bad])
    assert p2.handoff_violations == 1
    assert "WARNING: 1 epoch handoff VIOLATION(s)" in p2.result()


# ---------------------------------------------------------------------------
# Scenario-matrix runner (tools/chaos_run.py --matrix) + the LogParser
# MATRIX section (benchmark/logs.py) + the matrix-grid lint


def _load_chaos_run():
    import importlib.util

    path = os.path.join(
        os.path.dirname(__file__), "..", "tools", "chaos_run.py"
    )
    spec = importlib.util.spec_from_file_location("chaos_run", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.chaos
def test_chaos_matrix_cli_smoke_and_auto_numbering(tmp_path):
    """Subprocess acceptance: --matrix sweeps the given grid, prints the
    scrapeable MATRIX lines, auto-numbers CHAOS_MATRIX_rNN.json in the
    working directory, and a second run diffs against the first (all
    deltas zero — cells are deterministic per config)."""
    import json
    import subprocess
    import sys

    tool = os.path.join(
        os.path.dirname(__file__), "..", "tools", "chaos_run.py"
    )
    argv = [
        sys.executable, tool, "--matrix",
        "--matrix-scenarios", "baseline",
        "--matrix-seeds", "1",
        "--matrix-sizes", "4",
        "--trusted", "on",  # stub even at n=4: the cheap smoke shape
    ]
    proc = subprocess.run(
        argv, capture_output=True, text=True, timeout=300, cwd=tmp_path
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "MATRIX cell baseline@s1/n4 green crypto=trusted-stub" in proc.stdout
    assert "MATRIX result: 1 green / 0 red of 1 cells" in proc.stdout
    artifact = json.loads((tmp_path / "CHAOS_MATRIX_r01.json").read_text())
    assert artifact["kind"] == "chaos_matrix"
    assert artifact["summary"] == {
        "cells": 1, "green": 1, "red": 0,
        "wall_seconds": artifact["summary"]["wall_seconds"],
    }
    (cell,) = artifact["cells"]
    assert cell["cell"] == "baseline@s1/n4"
    assert cell["rollup"]["verdict"]["ok"] is True
    assert cell["rollup"]["commits"]["total"] >= 16
    assert artifact["regression"] == {"baseline": None}

    proc2 = subprocess.run(
        argv, capture_output=True, text=True, timeout=300, cwd=tmp_path
    )
    assert proc2.returncode == 0, proc2.stderr[-2000:]
    assert "MATRIX worst regression: baseline@s1/n4 commit rate +0.00%" in (
        proc2.stdout
    )
    artifact2 = json.loads((tmp_path / "CHAOS_MATRIX_r02.json").read_text())
    reg = artifact2["regression"]
    assert reg["baseline"].endswith("CHAOS_MATRIX_r01.json")
    assert reg["newly_red"] == [] and reg["newly_green"] == []
    assert reg["commit_rate_deltas"] == {"baseline@s1/n4": 0.0}


@pytest.mark.chaos
def test_chaos_matrix_regression_rc1_when_green_cell_goes_red(
    monkeypatch, tmp_path, capsys
):
    """The regression contract: a cell the baseline artifact recorded
    GREEN that comes back RED exits rc 1 (ranked above plain red cells,
    which are rc 2 without a baseline flip)."""
    import json

    from hotstuff_tpu.chaos import scenarios as sc
    from hotstuff_tpu.chaos.plan import FaultPlan, LinkFaults

    chaos_run = _load_chaos_run()
    rigged = sc.Scenario(
        name="rigged_red",
        description="always fails its expectation (test fixture)",
        plan=lambda: FaultPlan(default_link=LinkFaults(delay=0.01)),
        duration=3.0,
        min_commits=1,
        expect=lambda report, deltas: ["forced red (fixture)"],
    )
    monkeypatch.setitem(sc.SCENARIOS, "rigged_red", rigged)
    monkeypatch.chdir(tmp_path)

    # no baseline: red cells are rc 2
    out1 = tmp_path / "m1.json"
    rc = chaos_run.main(
        [
            "--matrix", "--matrix-scenarios", "rigged_red",
            "--matrix-seeds", "1", "--matrix-sizes", "4",
            "--trusted", "on", "--report", str(out1),
        ]
    )
    assert rc == 2
    assert "rigged_red@s1/n4 red" in capsys.readouterr().out

    # baseline claims the cell was green: the flip is rc 1 + the
    # regression line the LogParser scrapes
    doctored = json.loads(out1.read_text())
    doctored["cells"][0]["green"] = True
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps(doctored))
    rc = chaos_run.main(
        [
            "--matrix", "--matrix-scenarios", "rigged_red",
            "--matrix-seeds", "1", "--matrix-sizes", "4",
            "--trusted", "on", "--report", str(tmp_path / "m2.json"),
            "--baseline", str(baseline),
        ]
    )
    assert rc == 1
    out = capsys.readouterr().out
    assert "MATRIX regression: rigged_red@s1/n4 went red (was green)" in out
    report2 = json.loads((tmp_path / "m2.json").read_text())
    assert report2["regression"]["newly_red"] == ["rigged_red@s1/n4"]

    # unknown grid scenario names are a usage error, not a silent skip
    assert chaos_run.main(
        ["--matrix", "--matrix-scenarios", "no_such_cell"]
    ) == 3


def test_chaos_matrix_regression_deltas_unit():
    """_regression_deltas joins on the stable cell key: verdict flips in
    both directions, per-cell commit-rate deltas, worst pick."""
    chaos_run = _load_chaos_run()

    def cell(name, green, rate):
        return {
            "cell": name,
            "green": green,
            "rollup": {"commits": {"rate_per_s": rate}},
        }

    baseline = {
        "cells": [
            cell("a@s1/n4", True, 10.0),
            cell("b@s1/n4", False, 5.0),
            cell("gone@s1/n4", True, 1.0),
        ]
    }
    now = [
        cell("a@s1/n4", False, 8.0),
        cell("b@s1/n4", True, 6.0),
        cell("new@s1/n4", True, 2.0),
    ]
    deltas = chaos_run._regression_deltas(now, baseline)
    assert deltas["newly_red"] == ["a@s1/n4"]
    assert deltas["newly_green"] == ["b@s1/n4"]
    assert deltas["commit_rate_deltas"] == {
        "a@s1/n4": -20.0, "b@s1/n4": 20.0,
    }
    assert deltas["worst_commit_rate_delta"] == {
        "cell": "a@s1/n4", "pct": -20.0,
    }
    # baseline cells absent from this run's grid are surfaced, never
    # silently dropped from the regression chain
    assert deltas["missing_from_run"] == ["gone@s1/n4"]


def test_lint_matrix_flags_unknown_and_committee_pinned_grid(monkeypatch):
    """The matrix-grid lint: every grid name must resolve in the registry
    and no grid scenario may pin a committee subset (the size override
    cannot survive one); today's grid is clean."""
    from hotstuff_tpu.chaos import scenarios as sc

    lint = _load_lint()
    assert lint.lint_matrix() == []
    monkeypatch.setattr(
        sc, "MATRIX_SCENARIOS", ("baseline", "ghost_cell", "epoch_reconfig")
    )
    problems = lint.lint_matrix()
    assert len(problems) == 2
    assert any("ghost_cell" in p and "does not resolve" in p for p in problems)
    assert any(
        "epoch_reconfig" in p and "committee" in p for p in problems
    )


def test_lint_incidents_clean_on_repo():
    """Every AnomalyWatchdog trigger reason classifies into a ledger
    alert class and every incident.* metric row is registered — today's
    tree is clean."""
    assert _load_lint().lint_incidents() == []


def test_lint_incidents_flags_unmapped_and_stale_reasons(monkeypatch):
    """An unmapped watchdog reason (its triggers would all land in
    `unattributed`) and a stale classification (maps a reason nothing
    emits) are both violations."""
    from hotstuff_tpu.utils import incidents

    lint = _load_lint()
    mutated = dict(incidents.WATCHDOG_ALERT_CLASSES)
    mutated.pop("round_stall")
    mutated["ghost_reason"] = "ghost"
    monkeypatch.setattr(incidents, "WATCHDOG_ALERT_CLASSES", mutated)
    problems = lint.lint_incidents()
    assert any(
        "'round_stall'" in p and "unattributed" in p for p in problems
    )
    assert any("'ghost_reason'" in p and "stale" in p for p in problems)


def test_log_parser_matrix_section():
    """MATRIX result lines (chaos_run.py --matrix) fold into a
    '+ MATRIX:' section: cells run/green/red, newly-red regressions, and
    the worst commit-rate delta. Absent when no matrix ran."""
    from benchmark.logs import LogParser

    assert "+ MATRIX" not in LogParser([CLIENT_LOG], [NODE_LOG]).result()
    node = NODE_LOG + (
        "MATRIX cell baseline@s1/n4 green crypto=exact commits=18 "
        "rate=24.0/s wall=0.5s\n"
        "MATRIX cell baseline@s1/n64 green crypto=trusted-stub commits=288 "
        "rate=384.0/s wall=0.6s\n"
        "MATRIX cell lossy_links@s2/n64 red crypto=trusted-stub commits=100 "
        "rate=50.0/s wall=3.0s\n"
        "MATRIX result: 2 green / 1 red of 3 cells\n"
        "MATRIX regression: lossy_links@s2/n64 went red (was green)\n"
        "MATRIX worst regression: lossy_links@s2/n64 commit rate -41.18%\n"
    )
    p = LogParser([CLIENT_LOG], [node])
    assert p.matrix_cells == [
        ("baseline@s1/n4", "green"),
        ("baseline@s1/n64", "green"),
        ("lossy_links@s2/n64", "red"),
    ]
    assert p.matrix_regressions == ["lossy_links@s2/n64"]
    assert p.matrix_worst == [("lossy_links@s2/n64", -41.18)]
    out = p.result()
    assert "+ MATRIX:" in out
    assert "Cells: 3 run (2 green, 1 red)" in out
    assert (
        "REGRESSION: 1 previously-green cell(s) went red: "
        "lossy_links@s2/n64" in out
    )
    assert (
        "Worst commit-rate delta vs baseline: lossy_links@s2/n64 -41.18 %"
        in out
    )


@pytest.mark.chaos
def test_telemetry_dash_matrix_view(tmp_path, monkeypatch):
    """The dashboard renders a matrix artifact: one row per cell with
    verdict/commit-rate/regression markers, --json emits the normalized
    cells, and a non-matrix JSON is rc 3."""
    import json

    # isolate baseline auto-discovery from whatever CHAOS_MATRIX_r*.json
    # the pytest invocation directory happens to hold
    monkeypatch.chdir(tmp_path)
    chaos_run = _load_chaos_run()
    out = tmp_path / "matrix.json"
    rc = chaos_run.main(
        [
            "--matrix", "--matrix-scenarios", "baseline",
            "--matrix-seeds", "1", "--matrix-sizes", "4",
            "--trusted", "on", "--report", str(out),
        ]
    )
    assert rc == 0
    md = _run_dash("--matrix", str(out))
    assert md.returncode == 0, md.stderr[-2000:]
    assert "Scenario matrix (1 green / 0 red of 1 cells" in md.stdout
    assert "| baseline@s1/n4 | trusted-stub | GREEN |" in md.stdout
    js = _run_dash("--matrix", str(out), "--json")
    assert js.returncode == 0, js.stderr[-2000:]
    data = json.loads(js.stdout)
    assert data["mode"] == "matrix"
    (rec,) = data["cells"]
    assert rec["cell"] == "baseline@s1/n4" and rec["green"] is True
    assert rec["commits"] >= 16 and rec["truncated"] is False

    not_matrix = tmp_path / "plain.json"
    not_matrix.write_text(json.dumps({"ok": True}))
    bad = _run_dash("--matrix", str(not_matrix))
    assert bad.returncode == 3
    assert "chaos_matrix" in bad.stderr
