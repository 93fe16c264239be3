"""The yardstick checked against hand-made cases. Run by hand and in the CPU
rehearsal (`python3 -m chipbench.selfcheck`); exits non-zero on a mismatch.

  1. window, latency and percentile arithmetic on a hand-made record set
     with a stall (late sends, late commits) and a lost sample
  2. the schedule: bursts add up to the rate, ticks map back from sequence
  3. `trace_reduce` on the small recorded trace beside this file
     (`selfcheck_trace.xplane.pb`, where present) and on hand-made intervals
  4. the roofline constant against a count by hand
  5. the device's idle share and the roofline share counted over the window
     (chunks and signatures between two snapshots, one program's time)
  6. the wait after the window on hand-made logs and a clock of its own: a
     backlog that commits late is waited for, a shed transaction is given up
  7. `attempted` and `failed` where a front port shed and a node lost
  8. the all-nodes verified share beside the worst node's skipped share, and
     a span's window mean where the histogram holds a warm-up from before it
  9. a crash fault: the outages of the live nodes' logs and the timeouts in
     them; a per-layer metric with no reading is left out of a line that is
     still printed, an end-to-end one prints no line
"""

from __future__ import annotations

import os
import sys

from . import arith, drain, roofline, trace_reduce, traffic
from . import reference as ref

HERE = os.path.dirname(os.path.abspath(__file__))


def check(name: str, got, want, tol: float = 0.0) -> int:
    ok = abs(got - want) <= tol if isinstance(want, float) else got == want
    print(f"{'ok  ' if ok else 'FAIL'} {name}: got {got!r}, want {want!r}")
    return 0 if ok else 1


def latency_case() -> int:
    """One client, tick 1 s, window [100, 110): ten samples due 100..109.
    Samples 0-3 commit 0.2 s after due. The node stalls from 104 to 107:
    samples 4, 5, 6 all commit at 107.5 (3.5, 2.5, 1.5 s). Samples 7, 8
    commit 0.2 s after due. Sample 9 is lost; the run ends at 115, so it has
    waited 6 s. The generator was late by 0.8 s on sample 5 only."""
    node = {"payload_commits": [], "samples": {}, "own_payloads": {}, "blocks": [],
            "snapshots": []}
    records = []
    commit_at = {0: 100.2, 1: 101.2, 2: 102.2, 3: 103.2, 4: 107.5, 5: 107.5, 6: 107.5,
                 7: 107.2, 8: 108.2}
    for k in range(10):
        due = 100.0 + k
        records.append([0, k, due, due + (0.8 if k == 5 else 0.001), 20, 20 * k])
        if k in commit_at:
            node["samples"][ref.sample_id(0, k)] = f"P{k}"
            node["own_payloads"][f"P{k}"] = 20 * 512
            node["payload_commits"].append((commit_at[k], k, f"P{k}"))
    # a sample due before the window and one due after it must not count
    records.append([0, 10, 110.0, 110.001, 20, 200])
    src = {
        "window": {"start": 100.0, "t0": 100.0, "t1": 110.0, "seconds": 10.0, "end": 115.0},
        "records": records, "nodes": [node], "config": {"tx_size": 512},
        "traffic": {"rate": 20.0, "tick_s": 1.0},
    }
    lat, failed = arith.sample_latencies(src)
    bad = 0
    bad += check("samples in the window", len(lat), 10)
    bad += check("failed samples", failed, 1)
    bad += check("attempted tx", arith.attempted(src), 200)
    # sorted: .2 x6 (0,1,2,3,7,8), 1.5, 2.5, 3.5, 6.0 -> p50 = 5th = 0.2; p95 = 10th = 6.0
    bad += check("p50 s", arith.percentile(lat, 0.5), 0.2, 1e-6)
    bad += check("p95 s (the lost sample is the tail)", arith.percentile(lat, 0.95), 6.0, 1e-6)
    bad += check("p80 s", arith.percentile(lat, 0.8), 2.5, 1e-6)
    bad += check("committed-only p95 s", arith.percentile(arith.committed_latencies(src), 0.95), 3.5, 1e-6)
    # commits in the window: 9 payloads of 20 tx
    bad += check("committed tx in window", arith.committed_tx_in_window(src), 180)
    late = [r[3] - r[2] for r in arith.window_records(src)]
    bad += check("late p95 s", arith.percentile(late, 0.95), 0.8, 1e-6)
    return bad


def schedule_case() -> int:
    bad = 0
    rate_c, tick = 1125.0, 0.05  # 56.25 a tick
    total = sum(traffic.burst(rate_c, tick, k) for k in range(400))
    bad += check("bursts over 20 s add up", total, 22500)
    bad += check("burst sizes", sorted({traffic.burst(rate_c, tick, k) for k in range(400)}), [56, 57])
    bad += check("ticks in [t0, t1)", list(traffic.ticks_between(10.0, 0.05, 10.1, 10.25)), [2, 3, 4])
    return bad


def trace_case() -> int:
    bad = 0
    s, merged = trace_reduce.union_seconds([(0, 10), (5, 20), (30, 40), (40, 45), (100, 101)])
    bad += check("union of overlapping intervals (ns -> s)", s, 36e-9, 1e-15)
    bad += check("merged intervals", merged, [(0, 20), (30, 45), (100, 101)])
    # a stretch clocked at 0.4001 s whose operations span 0.4048 s is 0.4048 s
    # long; one whose operations span less is as long as it was clocked
    bad += check("stretch, operations past its clocked ends",
                 trace_reduce.stretch_seconds(0.4001, {"span_s": 0.4048}), 0.4048, 1e-12)
    bad += check("stretch, device idle at its ends",
                 trace_reduce.stretch_seconds(0.4001, {"span_s": 0.3726}), 0.4001, 1e-12)
    bad += check("stretch, no operation", trace_reduce.stretch_seconds(0.4001, {}), 0.4001, 1e-12)
    path = os.path.join(HERE, "selfcheck_trace.xplane.pb")
    if not os.path.exists(path):
        print("skip trace file: none recorded beside selfcheck.py")
        return bad
    out = trace_reduce.reduce(trace_reduce.load(path))
    want = os.path.join(HERE, "selfcheck_trace.expect.json")
    import json

    with open(want) as f:
        expect = json.load(f)
    if "program_ms" in out:
        out["program_runs"] = out["program_ms"]["count"]
    bad += check("recorded trace busy_s within its span", out["busy_s"] <= out["span_s"], True)
    for key, value in expect.items():
        got = out.get(key)
        if isinstance(value, float):
            bad += check(f"recorded trace {key}", got, value, 1e-9 + 1e-6 * abs(value))
        else:
            bad += check(f"recorded trace {key}", got, value)
    return bad


def roofline_case() -> int:
    bad = 0
    # by hand: 2 x (262 + 9) + 9 + 256 x 17 + 267 = 542 + 9 + 4352 + 267
    bad += check("field multiplications per signature", roofline.FIELD_MULS_PER_SIG, 5170)
    bad += check("operations per signature", roofline.OPS_PER_SIG, 5170 * 2048)
    t = roofline.least_seconds(4096, "TPU v5 lite")
    bad += check("least s for 4096 signatures on a v5e", t, 4096 * 5170 * 2048 / 197e12, 1e-12)
    bytes_t = 4096 * 129 / 819e9
    bad += check("compute bound, not bytes", t > bytes_t, True)
    try:
        roofline.least_seconds(1, "cpu")
        bad += check("unknown device is an error", "no error", "KeyError")
    except KeyError:
        bad += check("unknown device is an error", "KeyError", "KeyError")
    return bad


def device_count_case() -> int:
    """The window [100, 140) against sidecar snapshots at 99.7 and 139.6
    (39.9 s apart): 600 chunks of 40 ms are 24 s of programs, so 39.85 % of
    those seconds are idle; 1,474,560 real signatures are 60 % of the lanes,
    and the roofline is 0.6 x (least time of a full chunk) over 40 ms."""
    from .run import load_reader

    snap = lambda t, chunks, sigs: (  # noqa: E731
        t, {"counters": {}, "histograms": {},
            "info": {"backend": {"dispatched": {"generic": chunks}, "tpu_sigs": sigs}}})
    src = {
        "window": {"t0": 100.0, "t1": 140.0, "seconds": 40.0},
        "sidecar": {"snapshots": [snap(98.7, 90, 10), snap(99.7, 100, 1000),
                                  snap(139.6, 700, 1000 + 1474560), snap(140.6, 720, 9 ** 9)]},
        "trace": {"program_ms": {"median": 40.0}},
        "device": {"kind": "TPU v5 lite"},
        "config": {"sidecar": {"chunk": 4096}},
    }
    bad = check("device idle share over the window, %",
                load_reader("per_layer", "device.idle_share")(src), 100 * (1 - 24 / 39.9), 1e-9)
    bad += check("lane fill, %", load_reader("per_layer", "sidecar.lane_fill")(src), 60.0, 1e-9)
    full = 100 * roofline.least_seconds(4096, "TPU v5 lite") / 0.040
    bad += check("verify_roofline, %", load_reader("per_layer", "verify_roofline")(src),
                 0.6 * full, 1e-9)
    del src["trace"]
    bad += check("no trace, no device idle share",
                 load_reader("per_layer", "device.idle_share")(src), None)
    return bad


def drain_case() -> int:
    """One node, 512 B transactions, its client sent 30; the window closed
    at 100. Payload A (10 tx) is committed before the close, B (10) at 103,
    C (10) at 109: a backlog, and the wait ends at 109, all committed, not
    at its least of 4 s. In a second log C is sealed and never committed:
    the wait gives up 10 s after B, at 113, with 10 transactions out. A
    payload committed twice counts once."""
    import tempfile

    line = "[2026-01-01T00:00:00.000Z INFO hotstuff.x] "
    sealed = "".join(f"{line}Payload {d}= contains 5120 B\n" for d in "ABC")
    commit = lambda d: f"{line}Committed B7(xyz=) -> {d}=\n"  # noqa: E731
    bad = 0
    with tempfile.TemporaryDirectory() as tmp:
        for name, script, want in (
            ("late", {103: commit("B"), 109: commit("C") + commit("C")}, (109.0, "all_committed", 0)),
            ("shed", {103: commit("B")}, (113.0, "quiet", 10)),
        ):
            path = os.path.join(tmp, name + ".log")
            with open(path, "w") as f:
                f.write(sealed + commit("A") + f"{line}Committed B8(q=)\n{line}Payload Z= conta")
            now = [100.0]

            def sleep(_s, path=path, script=script):
                now[0] += 0.25
                if now[0] in script:
                    with open(path, "a") as f:
                        f.write(script.pop(now[0]))

            got = drain.wait_committed([path], 512, [30], 100.0, 4.0, 10.0, 60.0,
                                       sleep=sleep, clock=lambda: now[0])
            bad += check(f"drain, {name}: ends, why, still out",
                         (got["past_close_s"] + 100.0, got["why"], got["still_out"][0]), want)
    return bad


def shed_case() -> int:
    """Two clients, 100 transactions each due in the window, 20 more each
    outside it. Node 0 committed 90 of the window's and all 20 others, and
    its front port counts 10 evictions: 10 shed, none lost. Node 1 committed
    85 and 15, so 20 are out, and its counter explains 12: 8 lost, charged
    to the window, 7 shed there. A flood (`admitted`) attempts 200 - 17 and
    fails 8; any other mix attempts 200 and fails 25."""
    from . import judge

    node = lambda dropped: {"snapshots": [  # noqa: E731
        (1.0, {"counters": {"mempool.front_dropped": dropped}})]}
    bad = 0
    for mode, want in (("admitted", (183, 8, 17)), ("offered", (200, 25, 17))):
        src = {
            "window": {"t0": 10.0, "t1": 20.0},
            "records": [(c, k, 10.0 + k, 10.0 + k, 10, 0) for c in (0, 1) for k in range(10)],
            "nodes": [node(10), node(12)],
            "traffic": {"attempted": mode},
        }
        judge._attempted_failed(src, [90, 85], [110, 100], [120, 120])
        bad += check(f"attempted, failed, shed ({mode})",
                     (src["attempted"], src["failed"], src["shed_at_front"]), want)
    return bad


def share_case() -> int:
    """The window [100, 140), snapshots at 99.5 and 139.5. Node 0 verified
    6,000 workload signatures in it and skipped 4,000, node 1 verified 9,000
    and skipped 1,000: 15,000 of 20,000 due, 75 % over all nodes, and the
    worst node skipped 40 %. The sidecar's `verifier.e2e_s` held ten warm-up
    calls of 5 s before the window and takes 200 calls of 0.1 s in it: the
    window mean is 100 ms (since boot it would be 333). Its snapshots hold no
    `service.scatter_s` (a program older than the span): 0, not None."""
    from .run import load_reader

    hist = lambda total, count: {"sum": total, "count": count}  # noqa: E731
    node = lambda t, done, skipped: (  # noqa: E731
        t, {"counters": {"mempool.synthetic_skipped": skipped},
            "histograms": {"mempool.verify_batch_size": hist(done, 1)}})
    side = lambda t, total, count: (  # noqa: E731
        t, {"counters": {}, "histograms": {"verifier.e2e_s": hist(total, count)}})
    src = {
        "window": {"t0": 100.0, "t1": 140.0, "seconds": 40.0},
        "nodes": [{"snapshots": [node(99.5, 500, 70), node(139.5, 6500, 4070)]},
                  {"snapshots": [node(99.5, 800, 0), node(139.5, 9800, 1000)]}],
        "sidecar": {"snapshots": [side(99.5, 50.0, 10), side(139.5, 70.0, 210)]},
    }
    bad = check("verified share over all nodes, %",
                load_reader("per_layer", "flood.verified_share")(src), 75.0, 1e-9)
    bad += check("skipped share of the worst node, %",
                 load_reader("per_layer", "mempool.skipped_share")(src), 40.0, 1e-9)
    bad += check("verifier.e2e_ms, window mean",
                 load_reader("per_layer", "verifier.e2e_ms")(src), 100.0, 1e-9)
    bad += check("service.scatter_ms of a program without the span",
                 load_reader("per_layer", "service.scatter_ms")(src), 0.0, 1e-9)
    src["nodes"][1]["snapshots"].pop(0)
    bad += check("a node whose snapshots do not bracket the window: no share",
                 load_reader("per_layer", "flood.verified_share")(src), None)
    return bad


def fault_case() -> int:
    """The window [100, 140), `timeout_delay` 5 s. Node 0 commits at 95, 99,
    110.4, 111, 121.8, 122 and 145; node 1 at 99, 110.5, 122.1 and 150. Its
    timeouts fire at 104.9 and 109.9 and at 116.5 and 121.5. Outages of node
    0: 99-110.4 (timeouts 104.9, 109.9), 111-121.8 (116.5, 121.5), 122-145
    (none: a stall the logs do not explain, counted all the same); node 1:
    99-110.5, 110.5-122.1 and 122.1-150, with the same timeouts. 95-99 is
    under 5 s. The six outages sorted: 10.8, 11.4, 11.5, 11.6, 23.0, 27.9 s,
    and the nearest-rank median is the third, 11.5; recovery, over the four
    that hold a timeout: 0.5, 0.3, 0.6, 0.6 s -> the second sorted, 0.5."""
    from .run import load_reader, printable, read_metrics

    def node(commits, timeouts):
        return {"blocks": [(t, 0, "B") for t in commits],
                "timeouts": [(t, 0) for t in timeouts], "snapshots": []}

    timeouts = [104.9, 109.9, 116.5, 121.5]
    src = {
        "window": {"t0": 100.0, "t1": 140.0, "seconds": 40.0},
        "config": {"parameters": {"consensus": {"timeout_delay": 5000}}},
        "nodes": [node([95.0, 99.0, 110.4, 111.0, 121.8, 122.0, 145.0], timeouts),
                  node([99.0, 110.5, 122.1, 150.0], timeouts)],
    }
    bad = check("outages of the live nodes", len(arith.outages(src)), 6)
    bad += check("service.outage_ms", load_reader("per_layer", "service.outage_ms")(src),
                 11500.0, 1e-6)
    bad += check("consensus.recovery_ms",
                 load_reader("per_layer", "consensus.recovery_ms")(src), 500.0, 1e-6)
    calm = dict(src, nodes=[node([99.0 + k for k in range(45)], [])])
    bad += check("no outage: no reading", load_reader("per_layer", "service.outage_ms")(calm),
                 None)
    bench = {
        "end_to_end": [{"name": "commit_p95_ms", "workloads": ["c"]}],
        "per_layer": [
            {"name": name, "unit": "ms", "moves": "commit_p95_ms", "workloads": ["c"]}
            for name in ("service.outage_ms", "consensus.recovery_ms")
        ],
    }
    got = read_metrics(bench, "c", "per_layer", calm)
    missing = [m["name"] for m in bench["per_layer"] if m["name"] not in got]
    half = read_metrics(bench, "c", "per_layer", dict(
        src, nodes=[node([99.0, 110.4, 150.0], [])]))
    bad += check("a traced line without the metric that reads nothing is printed",
                 (sorted(half), printable(half, ["consensus.recovery_ms"], True)),
                 (["service.outage_ms"], True))
    bad += check("no per-layer reading at all: no line", printable(got, missing, True), False)
    bad += check("an end-to-end metric without a reading: no line",
                 printable({"setup_s": {}}, ["commit_p95_ms"], False), False)
    try:
        arith.live_nodes({"nodes": 10, "faults": 4})
        bad += check("faults over (n - 1) // 3 refused", False, True)
    except ValueError:
        bad += check("faults over (n - 1) // 3 refused", True, True)
    bad += check("live nodes of ten, one dead", arith.live_nodes({"nodes": 10, "faults": 1}), 9)
    return bad


def record(out_dir: str) -> int:
    """Record the small trace kept beside this file: five runs of one small
    jitted program on whatever device JAX has (meant for the chip), traced,
    with what `trace_reduce.reduce` reads from it as the expectation."""
    import glob
    import json
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    @jax.jit
    def small_program(x):
        return jnp.tanh(x @ x).sum()

    x = jnp.ones((256, 256), jnp.float32)
    small_program(x).block_until_ready()
    tmp = tempfile.mkdtemp(dir=out_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    for _ in range(5):
        small_program(x).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
    dst = os.path.join(out_dir, "selfcheck_trace.xplane.pb")
    shutil.copy(src, dst)
    shutil.rmtree(tmp)
    out = trace_reduce.reduce(trace_reduce.load(dst))
    keep = {k: out[k] for k in ("device_plane", "on_tpu", "busy_s", "program_s") if k in out}
    if "program_ms" in out:
        keep["program_runs"] = out["program_ms"]["count"]
    with open(os.path.join(out_dir, "selfcheck_trace.expect.json"), "w") as f:
        json.dump(keep, f, indent=1)
    print(json.dumps({"recorded": dst, "bytes": os.path.getsize(dst), "reads": keep,
                      "device": str(jax.devices()[0])}))
    return 0


def main() -> int:
    if len(sys.argv) > 2 and sys.argv[1] == "--record":
        return record(sys.argv[2])
    bad = (latency_case() + schedule_case() + trace_case() + roofline_case()
           + device_count_case() + drain_case() + shed_case() + share_case() + fault_case())
    print("selfcheck:", "all ok" if not bad else f"{bad} FAILED")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
