"""The dead-member cell `fork-n10-f1.steady` in BENCHMARK.json: what it
reports, that the six cells before it report what they reported, and the
three readers of what the cell added to the program (`chipbench/
layer_metrics/`), on sources made by hand."""

import copy

import pytest

CELL = "fork-n10-f1.steady"
FAULT_METRICS = (
    "consensus.proposals_per_round", "consensus.view_change_ms",
    "mempool.requeued_per_s", "service.outage_ms", "consensus.recovery_ms",
)

# What each cell of the benchmark before the fault cell reports, pinned.
_BASE = {
    "loadgen.late_p95_ms", "consensus.blocks_per_s", "mempool.skipped_share",
    "sidecar.device_share", "sidecar.lane_fill", "verifier.e2e_ms", "kernel.chunk_ms",
    "verify_roofline", "device.idle_share", "service.scatter_ms", "node.verify_rtt_ms",
    "remote.rtt_ms", "sidecar.request_ms", "sidecar.queue_ms", "sidecar.loop_us_per_sig",
    "sidecar.loop_cpu_share", "node.loop_cpu_share", "verifier.stage_ms",
    "verifier.readback_ms", "sidecar.columnar_share", "sidecar.cache_hit_share",
    "node.pool_build_s", "node.batch_sigs", "node.request_sigs", "node.cpu_verified_share",
    "sidecar.sigs_per_request",
    # appended later with no `workloads` list: due wherever
    # `verified_tx_per_s` is, so in every cell
    "mempool.duplicate_share",
    "device.idle_no_request_share", "device.idle_held_share", "device.idle_host_share",
}
_FLOOD = {"loadgen.failed_share", "flood.committed_p95_ms", "front.shed_share",
          "flood.verified_share"}
_CRITICAL = {"sidecar.critical_groups_per_dispatch"}
_FLOOD_E2E = {"verified_tx_per_s", "setup_s"}
PINNED = {
    "fork-n4.flood": (_FLOOD_E2E, _BASE | _FLOOD),
    "fork-n10.flood": (_FLOOD_E2E, _BASE | _FLOOD | _CRITICAL),
    "fork-n4.steady": (
        _FLOOD_E2E | {"commit_p95_ms"},
        _BASE | _CRITICAL | {"steady.commit_p50_ms", "consensus.commit_ms",
                             "consensus.timeouts_per_s", "consensus.tcs_per_s"},
    ),
    "fork-n10-ownpool.flood": (_FLOOD_E2E, _BASE | _CRITICAL),
    "fork-n10-ownpool.flood-16500": (_FLOOD_E2E, _BASE),
    "fork-n4-fablocal.flood": (_FLOOD_E2E, _BASE | _CRITICAL),
}


def _names(bench, cell, kind):
    from chipbench import run

    return {m["name"] for m in run.metrics_for(bench, cell, kind)}


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_an_accepted_cell_reports_what_it_reported(cell):
    from chipbench import run

    bench = run.load_benchmark()
    e2e, per_layer = PINNED[cell]
    assert _names(bench, cell, "end_to_end") == e2e
    assert _names(bench, cell, "per_layer") == per_layer


def test_the_fault_cell_reports_its_tail_beside_what_every_cell_reports():
    from chipbench import run

    bench = run.load_benchmark()
    (cell,) = (w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("fork-n10-f1", "steady-n10", 1)
    assert len(cell["why"]) <= 200
    (cfg,) = (c for c in bench["configs"] if c["name"] == "fork-n10-f1")
    assert cfg["file"] == "chipbench/configs/fork-n10-f1.json"
    assert cfg["reduced"] == run.load_config("fork-n10-f1")["reduced"]
    assert _names(bench, CELL, "end_to_end") == _FLOOD_E2E | {"commit_p95_ms"}
    layer = _names(bench, CELL, "per_layer")
    assert set(FAULT_METRICS) <= layer
    # `verified_tx_per_s` lists no cells, so the metrics that move it are due
    # here as in every cell, and so are the steady cell's tail metrics
    assert _BASE | {"steady.commit_p50_ms", "consensus.commit_ms"} <= layer
    # the pacemaker's two counters, whose lists name the fault cell beside
    # the steady cell they were written for
    assert {"consensus.timeouts_per_s", "consensus.tcs_per_s"} <= layer
    # a metric of these moves the tail and is due in the fault cell alone
    for m in bench["per_layer"]:
        if m["name"] in FAULT_METRICS:
            assert (m["moves"], m["workloads"]) == ("commit_p95_ms", [CELL]), m["name"]
            run.load_reader("per_layer", m["name"])  # every one has a reader


# -- the readers ------------------------------------------------------------------

# A committee of four with node 3 dead, on the host's clock: a rotation of
# the leader is four rounds and three proposals. Each burst (at 91 + 10.5 k)
# the three live leaders propose 0.1 s apart and every node commits twice;
# then two timeouts: the round advances by TC at +5.2 and +10.2 s.
ROTATION_S = 10.5


def _fault_src(extra_per_burst: int = 0):
    t0, t1 = 100.0, 140.0
    nodes = []
    bursts = [91.0 + ROTATION_S * k for k in range(6)]
    for i in range(3):
        proposals = []  # instants node i proposed
        rounds = []  # (instant, round node i entered)
        blocks = []
        r = 1
        for b in bursts:
            for j in range(3):
                rounds.append((b + 0.1 * j, r + j))
                if j == i:
                    # the leader after the stall may propose again (the parent)
                    copies = 1 + (extra_per_burst if j == 0 else 0)
                    proposals += [b + 0.1 * j] * copies
            blocks += [(b + 0.05, r, "d"), (b + 0.15, r + 1, "d")]
            rounds += [(b + 5.2, r + 3), (b + 10.2, r + 4)]
            r += 4
        snaps = []
        for k in range(70):
            t = 85.0 + 0.3 * i + k
            snaps.append((t, {
                "counters": {
                    "consensus.proposals": sum(1 for p in proposals if p <= t),
                    "mempool.orphans_requeued": 31 * sum(1 for b in bursts if b <= t),
                },
                "gauges": {"consensus.round": max([n for s, n in rounds if s <= t], default=1)},
                "histograms": {"consensus.view_change_s": {
                    "sum": 5.1 * sum(1 for b in bursts if b + 10.3 <= t),
                    "count": sum(1 for b in bursts if b + 10.3 <= t),
                }},
            }))
        nodes.append({"blocks": blocks, "timeouts": [], "snapshots": snaps})
    return {
        "window": {"t0": t0, "t1": t1, "seconds": t1 - t0},
        "config": {"parameters": {"consensus": {"timeout_delay": 5000}}},
        "nodes": nodes,
    }


def _read(name, src):
    from chipbench import run

    return run.load_reader("per_layer", name)(src)


def test_proposals_per_round_counts_whole_rotations():
    # three proposals in four rounds, whatever phase each node's snapshots have
    assert _read("consensus.proposals_per_round", _fault_src()) == pytest.approx(0.75)
    # a leader that proposes three times after every stall: five in four
    assert _read("consensus.proposals_per_round", _fault_src(2)) == pytest.approx(1.25)


def test_proposals_per_round_needs_two_stalls_and_its_counter():
    src = _fault_src()
    for node in src["nodes"]:  # a commit every half second: no stall
        node["blocks"] = [(90.0 + 0.5 * k, k, "d") for k in range(120)]
    assert _read("consensus.proposals_per_round", src) is None
    src = _fault_src()
    for node in src["nodes"]:
        for _t, snap in node["snapshots"]:
            snap["counters"].pop("consensus.proposals")
    assert _read("consensus.proposals_per_round", src) is None


def test_view_change_ms_is_the_windows_mean():
    src = _fault_src()
    assert _read("consensus.view_change_ms", src) == pytest.approx(5_100.0)
    older = copy.deepcopy(src)  # a program older than the histogram
    for node in older["nodes"]:
        for _t, snap in node["snapshots"]:
            snap["histograms"].pop("consensus.view_change_s")
    assert _read("consensus.view_change_ms", older) is None
    quiet = copy.deepcopy(src)  # no view change ended in the window
    for node in quiet["nodes"]:
        for _t, snap in node["snapshots"]:
            snap["histograms"]["consensus.view_change_s"] = {"sum": 0.0, "count": 0}
    assert _read("consensus.view_change_ms", quiet) is None


def test_requeued_per_s_is_a_pooled_rate():
    src = _fault_src()
    # 31 digests a rotation of 10.5 s, on every node
    assert _read("mempool.requeued_per_s", src) == pytest.approx(31 * 4 / 40.0, rel=0.3)
    for node in src["nodes"]:
        for _t, snap in node["snapshots"]:
            snap["counters"].pop("mempool.orphans_requeued")
    assert _read("mempool.requeued_per_s", src) is None
    src["nodes"][0]["snapshots"] = []
    assert _read("mempool.requeued_per_s", src) is None
