"""Device-occupancy timeline (ops/timeline.py): summary math, ring
bounds, the idle account, jax-free importability, and the wired verifier
chunk loop.

The summary-math tests drive a private DeviceTimeline with hand-placed
intervals so occupancy / idle gaps / overlap headroom are checked against
numbers computed by hand, not against the implementation; the idle
account's tests drive it through scripted edges on a scripted clock.
"""

import json
import os
import subprocess
import sys

import pytest

from hotstuff_tpu.ops import timeline
from hotstuff_tpu.ops.timeline import DeviceTimeline, IdleAccount
from hotstuff_tpu.utils import metrics


def _fill(tl: DeviceTimeline, intervals):
    for batch, chunk, phase, t0, t1, n in intervals:
        tl.note(batch, chunk, phase, t0, t1, n)


class Clock:
    """A scripted `time.monotonic`."""

    def __init__(self, t: float = 100.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t


ZERO_ACCOUNT = {"busy_s": 0.0, "idle_s": {"host": 0.0, "held": 0.0, "no_request": 0.0}}


def test_summary_empty_ring_is_stable_shape():
    s = DeviceTimeline(capacity=64).summary()
    assert s["account"] == ZERO_ACCOUNT
    assert s["chunks"] == 0
    assert s["occupancy"] == 0.0
    assert s["overlap_headroom"] == 0.0
    assert set(s["phase_s"]) == {"stage", "upload", "dispatch", "readback"}
    assert s["idle"] == {"count": 0, "total_s": 0.0, "p50_s": 0.0, "max_s": 0.0}


def test_summary_occupancy_and_idle_gaps_hand_computed():
    clock = Clock(0.0)
    tl = DeviceTimeline(capacity=64, account=IdleAccount(clock=clock))
    # span [0, 10]; device busy [0,2] and [5,6] -> occupancy 0.3; one
    # idle gap of 3 between them ([6,10] is trailing span from the host
    # stage below, not an inter-busy gap).
    _fill(
        tl,
        [
            (1, 0, "upload", 0.0, 1.0, 64),
            (1, 0, "dispatch", 1.0, 2.0, 64),
            (1, 0, "readback", 5.0, 6.0, 64),
            (1, 0, "stage", 9.0, 10.0, 64),  # host phase: not device-busy
        ],
    )
    s = tl.summary()
    assert s["chunks"] == 1 and s["batches"] == 1
    assert s["span_s"] == pytest.approx(10.0)
    assert s["occupancy"] == pytest.approx(0.3)
    assert s["idle"]["count"] == 1
    assert s["idle"]["total_s"] == pytest.approx(3.0)
    assert s["idle"]["max_s"] == pytest.approx(3.0)
    assert s["phase_s"]["stage"] == pytest.approx(1.0)
    # the account over the same edges: nothing until the dispatch returns
    # at 2, busy to the mask at 6, no request to 9, then a closed bucket
    # whose stage runs to 10. The ring's gap [2, 5] is the account's busy:
    # the program ran while the readback worker had not yet taken it.
    account = tl.account
    clock.t = 2.0
    account.dispatched()
    clock.t = 6.0
    account.read()
    clock.t = 9.0
    account.submitted()
    account.closed(1)
    clock.t = 10.0
    assert tl.summary()["account"] == {
        "busy_s": 4.0, "idle_s": {"host": 1.0, "held": 0.0, "no_request": 3.0},
    }


def test_summary_overlap_headroom_pairs_consecutive_chunks():
    tl = DeviceTimeline(capacity=64)
    # chunk 0: dispatch 2s; chunk 1: upload 1s (fully hideable under
    # chunk 0's dispatch); chunk 2: upload 3s vs chunk 1's 0.5s dispatch
    # (only 0.5s hideable). chunk 0's own upload (1s) has no predecessor.
    _fill(
        tl,
        [
            (1, 0, "upload", 0.0, 1.0, 64),
            (1, 0, "dispatch", 1.0, 3.0, 64),
            (1, 1, "upload", 3.0, 4.0, 64),
            (1, 1, "dispatch", 4.0, 4.5, 64),
            (1, 2, "upload", 4.5, 7.5, 64),
        ],
    )
    s = tl.summary()
    # hideable = min(1, 2) + min(3, 0.5) = 1.5; total upload = 5
    assert s["overlap_headroom"] == pytest.approx(1.5 / 5.0)
    # pairing is per batch: a new batch's chunk 0 pairs with nothing
    tl.note(2, 0, "upload", 8.0, 9.0, 64)
    assert tl.summary()["overlap_headroom"] == pytest.approx(1.5 / 6.0)


def test_ring_bound_evicts_oldest_and_counts_drops():
    tl = DeviceTimeline(capacity=16)
    for i in range(20):
        tl.note(1, i, "upload", float(i), float(i) + 0.5, 8)
    assert len(tl) == 16
    assert tl.dropped == 4
    assert tl.intervals()[0]["chunk"] == 4  # oldest evicted


# -- the idle account -----------------------------------------------------------

# A script of edges and clock steps: "d" a program dispatched outside any
# bucket, "D" one that serves the last bucket closed, "r" a mask on the
# host, "s" a group submitted, ("c", k) a bucket of k groups closed, "e"
# the last bucket's dispatch ended ("e0" the first's), "x" a reset, a number
# the seconds that pass. Then (busy, host, held, no_request) seconds.
ACCOUNT_SCRIPTS = {
    "busy": (["d", 2, "r"], (2, 0, 0, 0)),
    "no_request": (["d", "r", 3], (0, 0, 0, 3)),
    "held": (["d", "r", "s", 5], (0, 0, 5, 0)),
    "host": (["d", "r", "s", ("c", 1), 7], (0, 7, 0, 0)),
    # a closed bucket wins over groups still queued beside it
    "host_over_held": (["d", "r", "s", "s", ("c", 1), 4, "e", 1], (0, 4, 1, 0)),
    # a program on the device wins over everything the host holds
    "busy_over_host": (["d", "s", "s", ("c", 1), 6], (6, 0, 0, 0)),
    # a bucket the cache answers whole leaves with its task, programless
    "cache_hit_only": (["d", "r", "s", ("c", 1), 2, "e", 3], (0, 2, 0, 3)),
    # busy until the last of two programs in flight is back
    "two_programs": (["d", 1, "d", 2, "r", 3, "r", 4], (6, 0, 0, 4)),
    # a bucket's first program takes it out; its end changes nothing then
    "first_program": (["d", "r", "s", ("c", 1), 2, "D", 3, "r", 4, "e", 5],
                      (3, 2, 0, 9)),
    # a node: edges, never a program, so nothing is charged
    "never_dispatches": (["s", 5, ("c", 1), 5, "e", 5], (0, 0, 0, 0)),
    # a bucket closed before a reset ends after it: the new one stays closed
    "reset": (["d", "r", "s", ("c", 1), "x", "d", "r", "s", ("c", 1), 1, "e0", 2],
              (0, 3, 0, 0)),
}


def _play(account: IdleAccount, clock: Clock, script) -> None:
    buckets = []
    for step in script:
        if isinstance(step, (int, float)):
            clock.t += step
        elif isinstance(step, tuple):
            buckets.append(account.closed(step[1]))
        elif step in ("e", "e0"):
            buckets[-1 if step == "e" else 0].end()
        elif step == "x":
            account.reset()
        else:
            bucket = buckets[-1] if buckets else None
            {"d": account.dispatched, "D": lambda: account.dispatched(bucket),
             "r": account.read, "s": account.submitted}[step]()


@pytest.mark.parametrize("case", sorted(ACCOUNT_SCRIPTS))
def test_idle_account_charges_every_instant_once(case):
    script, (busy, host, held, no_request) = ACCOUNT_SCRIPTS[case]
    clock = Clock()
    account = IdleAccount(clock=clock)
    _play(account, clock, script)
    got = account.totals()
    assert got == {
        "busy_s": busy, "idle_s": {"host": host, "held": held, "no_request": no_request},
    }
    # the four add up to the time since the first program
    started = next((i for i, s in enumerate(script) if s in ("d", "D")), None)
    elapsed = 0 if started is None else sum(
        s for s in script[started:] if isinstance(s, (int, float)))
    assert got["busy_s"] + sum(got["idle_s"].values()) == elapsed


def test_idle_account_keeps_to_the_metrics_gate():
    clock = Clock()
    account = IdleAccount(clock=clock)
    metrics.enable(False)
    try:
        _play(account, clock, ["d", 2, "r", "s", ("c", 1), 3])
    finally:
        metrics.enable(True)
    assert account.totals() == ZERO_ACCOUNT


def test_idle_account_loses_no_edge_across_threads():
    """More threads than cores run whole bucket lives at once, with the
    interpreter switching threads as often as it can: every count comes back
    to 0 and the totals add up to the clock's whole advance. (A lost update
    leaves a count off 0; a charge made twice or lost breaks the sum.)"""
    import itertools
    import sys
    import threading

    ticks = itertools.count()
    account = IdleAccount(clock=lambda: float(next(ticks)))
    account.dispatched()  # reads 0.0: the account starts there
    account.read()

    def life():
        for _ in range(500):
            account.submitted()
            bucket = account.closed(1)
            account.dispatched(bucket)
            account.read()
            bucket.end()

    threads = [threading.Thread(target=life) for _ in range(2 * (os.cpu_count() or 4))]
    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
    finally:
        sys.setswitchinterval(prev)
    assert not any(t.is_alive() for t in threads)
    assert (account._programs, account._closed, account._queued) == (0, 0, 0)
    got = account.totals()  # one more clock read: the charge up to it
    assert got["busy_s"] + sum(got["idle_s"].values()) == account._t


def test_a_metrics_dump_charges_the_open_interval(monkeypatch):
    """The counters reach a snapshot with the interval still open charged
    up to it: ten seconds with nothing sent land in the snapshot that
    follows them, not in a later one."""
    clock = Clock()
    monkeypatch.setattr(timeline.ACCOUNT, "_clock", clock)
    timeline.ACCOUNT.reset()
    names = ("timeline.device_busy_s", "timeline.idle_host_s",
             "timeline.idle_held_s", "timeline.idle_no_request_s")

    def counters():
        return json.loads(metrics.snapshot_json())["counters"]

    try:
        c0 = counters()
        _play(timeline.ACCOUNT, clock, ["d", 2, "r", 3, "s", ("c", 1), 4, "e"])
        c1 = counters()
        clock.t += 10.0
        c2 = counters()
    finally:
        timeline.ACCOUNT.reset()
    assert [c1[n] - c0[n] for n in names] == pytest.approx([2.0, 4.0, 0.0, 3.0])
    assert [c2[n] - c1[n] for n in names] == pytest.approx([0.0, 0.0, 0.0, 10.0])


def test_span_context_manager_records_monotonic_interval():
    tl = DeviceTimeline(capacity=16)
    from hotstuff_tpu.ops import timeline as mod

    with mod.span("upload", 3, 1, 42, timeline=tl):
        pass
    (iv,) = tl.intervals()
    assert iv["phase"] == "upload" and iv["batch"] == 3 and iv["chunk"] == 1
    assert iv["n"] == 42
    assert iv["t1"] >= iv["t0"]


def test_dump_carries_anchor_and_summary(tmp_path):
    tl = DeviceTimeline(capacity=16)
    tl.note(1, 0, "upload", 0.0, 1.0, 8)
    d = tl.dump()
    assert d["kind"] == "device_timeline"
    assert {"mono", "wall"} <= set(d["anchor"])
    assert d["summary"]["chunks"] == 1
    path = tmp_path / "tl.json"
    tl.write_json(str(path))
    assert json.loads(path.read_text())["intervals"][0]["phase"] == "upload"


def test_disabled_mode_records_nothing():
    from hotstuff_tpu.ops import timeline as mod

    tl = DeviceTimeline(capacity=16)
    mod.enable(False)
    try:
        mod.span("upload", 1, 0, 8, timeline=tl).__enter__()
        tl.note(1, 0, "upload", 0.0, 1.0, 8)
        assert len(tl) == 0
    finally:
        mod.enable(True)


def test_verifier_chunk_loop_records_intervals():
    """The wiring test: a 2-chunk junk batch through the packed pipeline
    leaves stage/upload/dispatch intervals per chunk plus one readback,
    and a summary with occupancy in (0, 1]. Junk data on purpose — masks
    are discarded, the timeline is the subject. Shapes match the width-128
    w4 family the rest of tier-1 compiles (persistent-cache-shared)."""
    pytest.importorskip("jax")
    from hotstuff_tpu.ops import timeline
    from hotstuff_tpu.ops.ed25519 import Ed25519TpuVerifier

    timeline.TIMELINE.reset()
    v = Ed25519TpuVerifier(
        min_bucket=128, max_bucket=128, kernel="w4", chunk=64
    )
    v.verify_batch_mask(
        [os.urandom(32)] * 128, [os.urandom(32)] * 128, [os.urandom(64)] * 128
    )
    ivs = timeline.TIMELINE.intervals()
    assert ivs, "chunk loop recorded nothing"
    batch = ivs[0]["batch"]
    seen = {(i["chunk"], i["phase"]) for i in ivs if i["batch"] == batch}
    for chunk in (0, 1):
        for phase in ("stage", "upload", "dispatch"):
            assert (chunk, phase) in seen, (chunk, phase)
    assert any(i["phase"] == "readback" for i in ivs)
    s = timeline.TIMELINE.summary()
    assert s["chunks"] == 2
    assert 0.0 < s["occupancy"] <= 1.0
    assert 0.0 <= s["overlap_headroom"] <= 1.0


@pytest.mark.parametrize("family", ["generic", "committee"])
def test_deferred_readback_masks_bit_identical(family):
    """`_defer_readback` (the multi-process mesh mode, parallel/mesh.py):
    per-chunk readbacks return raw device handles and ONE end-of-batch
    `_materialize` call splits the concatenated mask back on bucket
    widths. Masks must match the streamed per-chunk path bit-for-bit —
    valid AND forged lanes — in BOTH families: they share the one chunk
    loop, so the committee family's two staged arrays (index vector and
    wire rows) take the fresh-buffer path too, and its mask equals the
    generic family's. Single-chip here (multihost needs the
    `cryptography` wheel this box lacks); the defer/concat/split
    machinery is what's under test, at the same cache-shared w4/128
    2-chunk shapes as the wiring test above, the second chunk ragged."""
    pytest.importorskip("jax")
    from hotstuff_tpu.crypto import pysigner
    from hotstuff_tpu.ops.ed25519 import Ed25519TpuVerifier

    n = 101  # chunks of 64 and 37 lanes
    pool = []
    for i in range(8):
        pk, seed = pysigner.keypair_from_seed(bytes([i + 1]) * 32)
        m = (b"defer-%d" % i).ljust(32, b"\0")
        pool.append((m, pk, pysigner.sign(seed, m)))
    msgs = [pool[i % 8][0] for i in range(n)]
    pks = [pool[i % 8][1] for i in range(n)]
    sigs = [pool[i % 8][2] for i in range(n)]
    sigs[5] = os.urandom(64)  # forged lane in chunk 0
    sigs[100] = os.urandom(64)  # forged lane in chunk 1

    kw = dict(min_bucket=128, max_bucket=128, kernel="w4", chunk=64)
    vn = Ed25519TpuVerifier(**kw)
    vd = Ed25519TpuVerifier(**kw)
    vd._defer_readback = True
    try:
        generic = vn.verify_batch_mask(msgs, pks, sigs)
        if family == "generic":
            want = generic
            got = vd.verify_batch_mask(msgs, pks, sigs)
        else:
            for v in (vn, vd):
                v.set_committee([pk for _, pk, _ in pool])
            idx = [i % 8 for i in range(n)]
            want = vn.verify_batch_mask_committee(msgs, idx, sigs)
            got = vd.verify_batch_mask_committee(msgs, idx, sigs)
            assert want.tolist() == generic.tolist()
            assert dict(vd.dispatched) == {"w4c96dh": 2}
    finally:
        vn.close()
        vd.close()
    assert got.tolist() == want.tolist()
    assert bool(want[0]) and not bool(want[5]) and not bool(want[100])
    assert want.sum() == n - 2


@pytest.mark.slow
def test_timeline_importable_without_jax():
    """The lint contract: ops.timeline (and the lazified ops package, and
    telemetry + the scheduler behind default_slos) must import on a host
    with no jax at all — DeviceScheduler's rule.

    Slow tier: graftlint's import-boundary pass pins the same contract
    statically in tier-1 (tests/test_graftlint.py), so this subprocess
    smoke is the belt-and-braces runtime proof, not the gate."""
    code = (
        "import sys; sys.modules['jax'] = None; sys.modules['jaxlib'] = None\n"
        "from hotstuff_tpu.ops import timeline\n"
        "from hotstuff_tpu.utils import telemetry\n"
        "assert timeline.summary()['chunks'] == 0\n"
        "assert len(telemetry.default_slos()) >= 5\n"
        "print('ok')\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True,
        text=True,
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        timeout=120,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ok" in proc.stdout
