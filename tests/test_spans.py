"""The life of a verify request as spans (ops/timeline.py `span`: ring,
histogram, profiler annotation), the counters beside them, and the
benchmark's readers of both (chipbench/spans.py, layer_metrics/*).

CPU only: counts, orders and names. No time here is a device's.
"""

import asyncio
import glob
import json
import os
import threading
import time

import pytest

from hotstuff_tpu.ops import timeline
from hotstuff_tpu.ops.pipeline import ChunkTask, DispatchPipeline
from hotstuff_tpu.utils import metrics


class FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs what it is told."""

    log: list = []

    def __init__(self, name, **stats):
        self.name, self.stats = name, stats
        self.log.append(("new", name, dict(stats)))

    def set_metadata(self, **stats):
        self.log.append(("set", self.name, stats))

    def __enter__(self):
        self.log.append(("enter", self.name, time.monotonic()))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, time.monotonic()))


@pytest.fixture
def fake_annotator():
    FakeAnnotation.log = []
    prev = timeline.set_annotator(FakeAnnotation)
    yield FakeAnnotation.log
    timeline.set_annotator(prev)


@pytest.fixture
def no_annotator():
    prev = timeline.set_annotator(None)
    yield
    timeline.set_annotator(prev)


def _hist(name):
    return metrics.histogram(name)


# -- (a) one helper, three sinks ---------------------------------------------


def test_one_with_feeds_ring_histogram_and_annotation_nested(fake_annotator):
    tl = timeline.DeviceTimeline(capacity=16)
    h = metrics.Histogram("test.span_s")
    with timeline.span("stage", 7, 2, 99, timeline=tl, hist=h, rid=5) as sp:
        time.sleep(0.002)
        sp.set(miss=3)
    (iv,) = tl.intervals()
    assert (iv["phase"], iv["batch"], iv["chunk"], iv["n"]) == ("stage", 7, 2, 99)
    assert h.count == 1
    # one clock read per edge: ring interval and histogram sample are one
    assert h.sum == pytest.approx(iv["t1"] - iv["t0"], abs=2e-6)
    kinds = [e[0] for e in fake_annotator]
    assert kinds == ["new", "enter", "set", "exit"]
    new, enter, meta, exit_ = fake_annotator
    # the annotation carries the profiler's name of the phase and the stats
    assert new[1] == "verifier.stage" == timeline.PHASES["stage"]
    assert new[2] == {"batch": 7, "chunk": 2, "n": 99, "rid": 5}
    assert meta[2] == {"miss": 3}
    # nesting: the annotation is outermost, the timed interval inside it
    assert enter[2] <= iv["t0"] + 1e-6 and iv["t1"] <= exit_[2] + 1e-6


def test_no_annotator_costs_no_call(fake_annotator):
    assert timeline.set_annotator(None) is FakeAnnotation  # taken out again
    tl = timeline.DeviceTimeline(capacity=16)
    h = metrics.Histogram("test.span_s")
    with timeline.span("upload", 1, 0, 8, timeline=tl, hist=h) as sp:
        sp.set(miss=1)  # nowhere to go, and no error
    assert len(tl) == 1 and h.count == 1 and fake_annotator == []


def test_ring_off_still_feeds_histogram_and_annotation(fake_annotator):
    tl = timeline.DeviceTimeline(capacity=16)
    h = metrics.Histogram("test.span_s")
    timeline.enable(False)
    try:
        with timeline.span("dispatch", 1, 0, 8, timeline=tl, hist=h):
            pass
    finally:
        timeline.enable(True)
    assert len(tl) == 0 and h.count == 1
    assert [e[0] for e in fake_annotator] == ["new", "enter", "exit"]


def test_every_sink_off_is_the_null_span(no_annotator):
    timeline.enable(False)
    try:
        assert timeline.span("upload", 1, 0, 8) is timeline.NULL
    finally:
        timeline.enable(True)


def test_backdate_moves_the_ring_edge_alone(fake_annotator):
    """`start=` (the pipeline's readback) opens the RING interval earlier;
    the histogram and the annotation run from the real enter."""
    tl = timeline.DeviceTimeline(capacity=16)
    h = metrics.Histogram("test.span_s")
    began = time.monotonic() - 1.0
    with timeline.span("readback", 1, 0, 8, timeline=tl, hist=h, start=began):
        pass
    (iv,) = tl.intervals()
    assert iv["t1"] - iv["t0"] >= 1.0
    assert h.sum < 0.5
    enter = [e for e in fake_annotator if e[0] == "enter"][0]
    assert enter[2] > began + 0.9


def test_request_phases_share_the_ring_not_the_summary():
    tl = timeline.DeviceTimeline(capacity=16)
    tl.note(1, 0, "upload", 0.0, 1.0, 64)
    tl.note(41, 0, "parse", 5.0, 6.0, 64)
    tl.note(9, 0, "collect", 6.0, 7.0, 64)
    tl.note(41, 0, "reply", 8.0, 9.0, 64)
    assert len(tl.intervals()) == 4
    s = tl.summary()
    assert s["chunks"] == 1 and s["batches"] == 1
    assert s["span_s"] == pytest.approx(1.0)
    assert set(s["phase_s"]) == set(timeline.CHUNK_PHASES)
    assert set(timeline.CHUNK_PHASES) < set(timeline.PHASES)
    assert not timeline.DEVICE_PHASES & {"parse", "collect", "reply"}


def test_pipeline_spans_feed_the_tasks_histograms(no_annotator):
    tl = timeline.DeviceTimeline(capacity=64)
    stage_h = metrics.Histogram("test.stage_s")
    read_h = metrics.Histogram("test.readback_s")
    pipe = DispatchPipeline(depth=2, name="spans-test", tl=tl)
    tasks = [
        ChunkTask(
            stage=lambda: 1, submit=lambda p: p, readback=lambda h: h,
            tlkey=(3, ci, 10),
            hists={"stage": stage_h, "readback": read_h} if ci else {"stage": stage_h},
        )
        for ci in range(3)
    ]
    try:
        assert pipe.run(tasks) == [1, 1, 1]
    finally:
        pipe.close()
    phases = sorted((i["chunk"], i["phase"]) for i in tl.intervals())
    assert phases == [(c, p) for c in range(3) for p in ("readback", "stage")]
    # a phase a task names no histogram for feeds none (deferred readback)
    assert stage_h.count == 3 and read_h.count == 2


def test_batch_number_crosses_the_thread_hop():
    async def body():
        opened = timeline.open_batch()
        assert timeline.batch_id() == opened
        assert await asyncio.to_thread(timeline.batch_id) == opened

    asyncio.run(body())
    # outside any opened batch every caller gets a fresh number
    a, b = timeline.batch_id(), timeline.batch_id()
    assert a != b


def test_collect_and_verifier_spans_share_one_batch(run_async, no_annotator):
    """`service.collect` opens the batch; a backend called under it (here a
    fake that asks, as Ed25519TpuVerifier does) sees the same number."""
    from hotstuff_tpu.crypto.batch_service import BatchVerificationService
    from hotstuff_tpu.crypto.primitives import PublicKey, Signature

    seen = []

    class Backend:
        name = "fake"

        def verify_batch_mask(self, m, k, s):
            seen.append(timeline.batch_id())
            return [True] * len(m)

    async def body():
        timeline.TIMELINE.reset()
        hists = ("service.collect_s", "service.backend_s", "service.scatter_s")
        before = [_hist(h).count for h in hists]
        svc = BatchVerificationService(Backend())
        pairs = [(PublicKey(bytes(32)), Signature(bytes(64)))] * 5
        assert await svc.verify_group([b"m"] * 5, pairs, dedup=False, rid=17) == [True] * 5
        ring = timeline.TIMELINE.intervals()
        collects = [i for i in ring if i["phase"] == "collect"]
        assert len(collects) == 1 and collects[0]["n"] == 5
        assert seen == [collects[0]["batch"]]
        # the section after the backend call: same batch, after the collect
        (scatter,) = [i for i in ring if i["phase"] == "scatter"]
        assert (scatter["batch"], scatter["n"]) == (collects[0]["batch"], 5)
        assert scatter["t0"] >= collects[0]["t1"]
        assert [_hist(h).count for h in hists] == [c + 1 for c in before]

    run_async(body())


# -- (b) a span on the profiler's clock --------------------------------------


def test_span_lands_in_the_profilers_host_plane(tmp_path):
    """Under a real jax.profiler session, with the profiler options the
    benchmark's shim uses, a `timeline.span` is an event of the `/host:CPU`
    plane under its profiler name, with its stats. No kernel is compiled."""
    jax = pytest.importorskip("jax")
    from jax.profiler import ProfileData

    prev = timeline.set_annotator(jax.profiler.TraceAnnotation)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    tl = timeline.DeviceTimeline(capacity=16)
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with timeline.span("stage", 12, 3, 4001, timeline=tl):
                time.sleep(0.003)
            with timeline.span("collect", 13, 0, 77, timeline=tl, groups=2) as sp:
                sp.set(miss=70)
        finally:
            jax.profiler.stop_trace()
    finally:
        timeline.set_annotator(prev)
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    data = ProfileData.from_file(path)
    (host,) = [p for p in data.planes if p.name == "/host:CPU"]
    found = {}
    for line in host.lines:
        for ev in line.events:
            if ev.name in timeline.PHASES.values():
                found[ev.name] = (ev.duration_ns, dict(ev.stats))
    assert set(found) == {"verifier.stage", "service.collect"}
    dur, stats = found["verifier.stage"]
    assert dur >= 3e6
    assert (stats["batch"], stats["chunk"], stats["n"]) == (12, 3, 4001)
    assert found["service.collect"][1]["groups"] == 2
    assert found["service.collect"][1]["miss"] == 70


# -- (c) a request's counters, node to sidecar and back ----------------------


def test_loopback_sidecar_counts_requests_and_slot_holds(run_async, base_port):
    pytest.importorskip("cryptography")
    from hotstuff_tpu.crypto.backend import CpuBackend
    from hotstuff_tpu.crypto.batch_service import BatchVerificationService
    from hotstuff_tpu.crypto.remote import RemoteBackend, serve
    from hotstuff_tpu.mempool import MempoolParameters
    from hotstuff_tpu.mempool.core import Core
    from hotstuff_tpu.store import Store
    from hotstuff_tpu.utils.actors import channel
    from tests.common import keys
    from tests.common_mempool import mempool_committee

    names = ("sidecar.requests", "sidecar.request_sigs", "sidecar.columnar_sigs",
             "mempool.synthetic_skipped", "runtime.loop_cpu_s")
    hists = ("sidecar.request_s", "sidecar.parse_s", "sidecar.reply_s",
             "crypto.remote_rtt_s", "mempool.verify_rtt_s")

    def read():
        return ({n: metrics.counter(n).value for n in names},
                {n: _hist(n).count for n in hists})

    async def body():
        server = asyncio.create_task(
            serve(("127.0.0.1", base_port), CpuBackend())
        )
        await asyncio.sleep(0.2)
        c0, h0 = read()
        timeline.TIMELINE.reset()
        try:
            service = BatchVerificationService(
                RemoteBackend(("127.0.0.1", base_port), crossover=1)
            )
            core = Core(
                keys(4)[0][0], mempool_committee(base_port + 1, 4),
                MempoolParameters(benchmark_mode=True, synthetic_pool_size=32),
                Store(), None, None, channel(), channel(), channel(),
                verification_service=service, max_inflight_verifications=1,
            )
            for _ in range(2):
                await core._submit_synthetic_batch("OWN", 16)  # takes the one slot
                await core._submit_synthetic_batch("OWN", 16)  # finds it taken: skipped
                await asyncio.wait_for(asyncio.gather(*core._inflight), 20)
            await asyncio.sleep(0.1)  # the sidecar's drain, the meter's tick
        finally:
            server.cancel()
        c1, h1 = read()
        d = {n: c1[n] - c0[n] for n in names}
        dh = {n: h1[n] - h0[n] for n in hists}
        # two admitted workload batches made two requests of 16 signatures
        assert d["sidecar.requests"] == 2 and d["sidecar.request_sigs"] == 32
        # the pool's messages are 32-byte digests: both stayed columnar
        assert d["sidecar.columnar_sigs"] == 32
        assert dh == {n: 2 for n in hists}
        # one slot-hold per admitted batch, none per skipped one
        assert d["mempool.synthetic_skipped"] == 32
        # the sidecar's main started the loop's CPU meter
        assert d["runtime.loop_cpu_s"] > 0
        # a request's parse and reply share its rid; collect names it
        ring = timeline.TIMELINE.intervals()
        parses = [i["batch"] for i in ring if i["phase"] == "parse"]
        replies = [i["batch"] for i in ring if i["phase"] == "reply"]
        assert len(parses) == 2 and sorted(parses) == sorted(replies)

    run_async(body())


class _PacedBackend:
    """Stands in for the sidecar's TpuBackend: a call stages for `HOST_S`,
    runs two programs through a real DispatchPipeline (depth 2) whose masks
    take `DEVICE_S` each to come back, and spends `AFTER_S` on the host
    after them. `after` is set once a call is past its programs."""

    HOST_S, DEVICE_S, AFTER_S = 0.02, 0.01, 0.03
    name = "paced"
    bucket_alignment = 0

    def __init__(self):
        self.pipeline = DispatchPipeline(depth=2, name="paced")
        self.first_dispatch = None
        self.after = threading.Event()

    def _submit(self, _payload):
        if self.first_dispatch is None:
            self.first_dispatch = time.monotonic()
        return "handle"

    def verify_batch_mask(self, msgs, _pks, _sigs):
        time.sleep(self.HOST_S)
        self.pipeline.run(
            ChunkTask(stage=lambda: None, submit=self._submit,
                      readback=lambda _h: time.sleep(self.DEVICE_S))
            for _ in range(2)
        )
        self.after.set()
        time.sleep(self.AFTER_S)
        return [True] * len(msgs)


def test_loopback_idle_account_adds_up_to_the_elapsed_time(run_async):
    """The real scheduler, service and pipeline through a script in which
    each cause holds for a while: the account's four counters add up to the
    time since the first program, and none of them is 0."""
    from hotstuff_tpu.crypto.batch_service import BatchVerificationService
    from hotstuff_tpu.crypto.scheduler import SchedulerConfig

    names = ("timeline.device_busy_s", "timeline.idle_host_s",
             "timeline.idle_held_s", "timeline.idle_no_request_s")
    backend = _PacedBackend()

    def counters():
        c = json.loads(metrics.snapshot_json())["counters"]
        return [c[n] for n in names]

    async def body():
        service = BatchVerificationService(
            backend, dedup_cache_size=0,
            scheduler_config=SchedulerConfig(bulk_concurrency=1),
        )

        def group():
            return service.verify_group([b"m"] * 4, [(b"k", b"s")] * 4)

        timeline.ACCOUNT.reset()
        c0 = counters()
        await group()  # its first program starts the account
        await asyncio.sleep(0.05)  # nothing sent: no_request
        backend.after.clear()
        # held for the mempool lane's deadline, then closed: host, busy
        second = asyncio.ensure_future(group())
        await asyncio.to_thread(backend.after.wait, 5)
        # the one bulk slot is the second's, past its programs: held
        await group()
        await second
        t_end = time.monotonic()
        c1 = counters()
        t_after = time.monotonic()
        return [b - a for a, b in zip(c0, c1)], t_end, t_after

    try:
        deltas, t_end, t_after = run_async(body())
    finally:
        backend.pipeline.close()
        timeline.ACCOUNT.reset()
    busy, host, held, no_request = deltas
    total = sum(deltas)
    # the dump charges up to its own clock read, taken between the two
    # stamps; the account's start follows the first submit by one edge
    assert t_end - backend.first_dispatch - 2e-3 <= total
    assert total <= t_after - backend.first_dispatch
    assert busy >= 3 * 2 * _PacedBackend.DEVICE_S * 0.9
    assert host >= 2 * _PacedBackend.HOST_S * 0.9
    assert no_request >= 0.05 * 0.9
    assert held > 0


# -- (d) the benchmark's readers ----------------------------------------------

NEW_METRICS = (
    "node.verify_rtt_ms", "remote.rtt_ms", "sidecar.request_ms", "sidecar.queue_ms",
    "sidecar.loop_us_per_sig", "sidecar.loop_cpu_share", "node.loop_cpu_share",
    "verifier.stage_ms", "verifier.readback_ms",
)
# what a program older than these spans still has (the parent commit)
OLD_NAMES = {"scheduler.queue_mempool_s", "verifier.stage_s", "verifier.readback_s"}


def _snap(counters=None, hists=None):
    return {
        "counters": dict(counters or {}),
        "histograms": {k: {"sum": s, "count": c} for k, (s, c) in (hists or {}).items()},
    }


def _src(keep=None):
    """Window [100, 140); snapshots 0.5 s before each edge and one after
    the close, 40 s apart. Since-boot sums are large (an 83.8 s warm-up
    sample): only the difference may show. `keep`: the names a snapshot
    holds (None: all)."""

    def pick(d):
        return d if keep is None else {k: v for k, v in d.items() if k in keep}

    def log(first, last):
        snaps = [(99.5, first), (139.5, last), (141.0, last)]
        return {"snapshots": [
            (t, _snap(pick(s["counters"]), pick(s["hists"]))) for t, s in snaps
        ]}

    sidecar = log(
        {"counters": {"sidecar.request_sigs": 500_000, "runtime.loop_cpu_s": 31.0},
         "hists": {"sidecar.request_s": (90.0, 300), "scheduler.queue_mempool_s": (2.0, 100),
                   "sidecar.parse_s": (3.0, 300), "service.collect_s": (4.0, 120),
                   "sidecar.reply_s": (0.5, 300), "verifier.stage_s": (83.8, 10),
                   "verifier.readback_s": (1.0, 10)}},
        {"counters": {"sidecar.request_sigs": 600_000, "runtime.loop_cpu_s": 51.0},
         "hists": {"sidecar.request_s": (99.0, 360), "scheduler.queue_mempool_s": (3.2, 140),
                   "sidecar.parse_s": (3.5, 360), "service.collect_s": (5.0, 160),
                   "sidecar.reply_s": (0.6, 360), "verifier.stage_s": (87.2, 210),
                   "verifier.readback_s": (11.4, 210)}},
    )
    node0 = log(
        {"counters": {"runtime.loop_cpu_s": 10.0},
         "hists": {"mempool.verify_rtt_s": (10.0, 100), "crypto.remote_rtt_s": (1.0, 10)}},
        {"counters": {"runtime.loop_cpu_s": 18.0},
         "hists": {"mempool.verify_rtt_s": (30.0, 140), "crypto.remote_rtt_s": (5.0, 30)}},
    )
    node1 = log(
        {"counters": {"runtime.loop_cpu_s": 2.0},
         "hists": {"mempool.verify_rtt_s": (5.0, 50), "crypto.remote_rtt_s": (0.0, 0)}},
        {"counters": {"runtime.loop_cpu_s": 32.0},
         "hists": {"mempool.verify_rtt_s": (25.0, 60), "crypto.remote_rtt_s": (2.0, 10)}},
    )
    return {"window": {"t0": 100.0, "t1": 140.0, "seconds": 40.0},
            "sidecar": sidecar, "nodes": [node0, node1]}


# by hand: pooled over nodes (20 + 20) s / (40 + 10); (4 + 2) / (20 + 10);
# 9 s / 60; 1.2 s / 40; (0.5 + 1.0 + 0.1) s / 100,000; 20 s / 40 s;
# max(8, 30) s / 40 s; 3.4 s / 200; 10.4 s / 200
EXPECTED = dict(zip(NEW_METRICS, (800.0, 200.0, 150.0, 30.0, 16.0, 50.0, 75.0, 17.0, 52.0)))


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_is_a_window_mean(name):
    from chipbench import run

    read = run.load_reader("per_layer", name)
    assert read(_src()) == pytest.approx(EXPECTED[name])
    # no snapshots around the window: nothing to read
    empty = _src()
    for log in [empty["sidecar"], *empty["nodes"]]:
        log["snapshots"] = []
    assert read(empty) is None
    # a program older than the span: what it has reads as before, the rest 0
    old = read(_src(keep=OLD_NAMES))
    still = {"sidecar.queue_ms", "verifier.stage_ms", "verifier.readback_ms"}
    assert old == (pytest.approx(EXPECTED[name]) if name in still else 0.0)


def test_every_cell_reports_the_new_metrics():
    from chipbench import run

    bench = run.load_benchmark()
    for cell in bench["workloads"]:
        names = [m["name"] for m in run.metrics_for(bench, cell["name"], "per_layer")]
        assert set(NEW_METRICS) <= set(names), cell["name"]
    # they stand together, in the table's order; what later PRs appended
    # after them (PR 27's `sidecar.columnar_share` first) is theirs to pin
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(NEW_METRICS[0])
    assert tuple(names[first:first + len(NEW_METRICS)]) == NEW_METRICS
    assert len(set(names)) == len(names)


def test_columnar_share_reads_the_windows_two_counters():
    from chipbench import run

    read = run.load_reader("per_layer", "sidecar.columnar_share")
    src = _src()
    first, last = (snap for _t, snap in src["sidecar"]["snapshots"][:2])
    # a program older than the counter: 0 of the 100,000 that arrived
    assert read(src) == 0.0
    first["counters"]["sidecar.columnar_sigs"] = 400_000
    last["counters"]["sidecar.columnar_sigs"] = 475_000
    assert read(src) == pytest.approx(75.0)
    # no signature arrived, or no snapshots around the window: nothing to read
    last["counters"]["sidecar.request_sigs"] = first["counters"]["sidecar.request_sigs"]
    assert read(src) is None
    src["sidecar"]["snapshots"] = []
    assert read(src) is None


def test_cache_hit_share_reads_the_windows_two_counters():
    from chipbench import run

    read = run.load_reader("per_layer", "sidecar.cache_hit_share")
    src = _src()
    first, last = (snap for _t, snap in src["sidecar"]["snapshots"][:2])
    # nothing was looked up in the window: nothing to read
    assert read(src) is None
    # since boot 300,000 hits in 400,000 lookups; in the window 15,000 in 60,000
    first["counters"].update({"verifier.dedup_hits": 300_000, "verifier.dedup_misses": 100_000})
    last["counters"].update({"verifier.dedup_hits": 315_000, "verifier.dedup_misses": 145_000})
    assert read(src) == pytest.approx(25.0)
    # per-node pools: every lookup a miss
    last["counters"]["verifier.dedup_hits"] = 300_000
    assert read(src) == 0.0
    src["sidecar"]["snapshots"] = []
    assert read(src) is None


def test_pool_build_s_is_the_slowest_nodes_one_sample():
    from chipbench import run

    read = run.load_reader("per_layer", "node.pool_build_s")
    src = _src()
    # a program older than the histogram: 0, as spans.py reads every absent name
    assert read(src) == 0.0
    # one sample a node, taken at boot: the snapshot before the close holds it
    for node, took in zip(src["nodes"], (31.5, 33.25)):
        for _t, snap in node["snapshots"]:
            snap["histograms"]["mempool.pool_build_s"] = {"sum": took, "count": 1}
    assert read(src) == pytest.approx(33.25)
    # a node with no snapshot by the close: nothing to read
    src["nodes"][0]["snapshots"] = [(141.0, src["nodes"][0]["snapshots"][-1][1])]
    assert read(src) is None


# -- PR 33: how large the verify plane's batches and requests were ------------

SMALL_BATCH_METRICS = (
    "node.batch_sigs", "node.request_sigs", "node.cpu_verified_share",
    "sidecar.sigs_per_request",
)


def _small_batch_src():
    """`_src()` with two nodes' and the sidecar's counts as `fab local`
    payloads make them: in the window node 0 admitted 1,000 batches of 29
    and node 1 500 of 20; they sent 100 + 60 requests of 15,000 + 5,000
    signatures and kept 14,000 + 6,000 on their CPUs; the sidecar parsed
    180 requests (the probe's 20 among them) of 26,400."""
    src = _src()
    adds = [
        ({"crypto.remote_sigs": 50_000, "crypto.remote_batches": 900,
          "crypto.remote_cpu_sigs": 7_000},
         {"crypto.remote_sigs": 65_000, "crypto.remote_batches": 1_000,
          "crypto.remote_cpu_sigs": 21_000},
         ((976_000.0, 1_000), (1_005_000.0, 2_000))),
        ({"crypto.remote_sigs": 1_000, "crypto.remote_batches": 10,
          "crypto.remote_cpu_sigs": 0},
         {"crypto.remote_sigs": 6_000, "crypto.remote_batches": 70,
          "crypto.remote_cpu_sigs": 6_000},
         ((0.0, 0), (10_000.0, 500))),
    ]
    for node, (c_first, c_last, (h_first, h_last)) in zip(src["nodes"], adds):
        (_t, first), (_t, last) = node["snapshots"][:2]
        first["counters"].update(c_first)
        last["counters"].update(c_last)
        first["histograms"]["mempool.verify_batch_size"] = dict(zip(("sum", "count"), h_first))
        last["histograms"]["mempool.verify_batch_size"] = dict(zip(("sum", "count"), h_last))
    (_t, first), (_t, last) = src["sidecar"]["snapshots"][:2]
    first["counters"]["sidecar.requests"] = 300
    last["counters"]["sidecar.requests"] = 480
    last["counters"]["sidecar.request_sigs"] = first["counters"]["sidecar.request_sigs"] + 26_400
    return src


# by hand: (29,000 + 10,000) / 1,500; (15,000 + 5,000) / 160;
# 20,000 / (20,000 + 20,000); 26,400 / 180
SMALL_BATCH_EXPECTED = dict(zip(SMALL_BATCH_METRICS, (26.0, 125.0, 50.0, 146.0 + 2.0 / 3.0)))
# the names each reader divides by: absent (a program older than them, or a
# window in which they stood still), there is nothing to read
DIVIDES_BY = {
    "node.batch_sigs": ("histograms", "mempool.verify_batch_size"),
    "node.request_sigs": ("counters", "crypto.remote_batches"),
    "node.cpu_verified_share": ("counters", "crypto.remote_sigs", "crypto.remote_cpu_sigs"),
    "sidecar.sigs_per_request": ("counters", "sidecar.requests"),
}


@pytest.mark.parametrize("name", SMALL_BATCH_METRICS)
def test_small_batch_reader_divides_the_windows_counts(name):
    from chipbench import run

    read = run.load_reader("per_layer", name)
    assert read(_small_batch_src()) == pytest.approx(SMALL_BATCH_EXPECTED[name])
    # the counter absent from every snapshot: None, never a made-up number
    bare = _small_batch_src()
    kind, *names = DIVIDES_BY[name]
    for log in [bare["sidecar"], *bare["nodes"]]:
        for _t, snap in log["snapshots"]:
            for n in names:
                snap[kind].pop(n, None)
    assert read(bare) is None
    # no snapshots around the window: nothing to read
    empty = _small_batch_src()
    for log in [empty["sidecar"], *empty["nodes"]]:
        log["snapshots"] = []
    assert read(empty) is None


def test_cpu_share_of_a_program_older_than_its_counter_is_zero():
    """The parent commit counts what went over the wire and not what stayed:
    its share reads 0 (as `sidecar.columnar_share` reads such a program), so
    that its traced run still prints a line (`run.py` prints none where a
    metric the cell is due has no reading)."""
    from chipbench import run

    read = run.load_reader("per_layer", "node.cpu_verified_share")
    src = _small_batch_src()
    for node in src["nodes"]:
        for _t, snap in node["snapshots"]:
            snap["counters"].pop("crypto.remote_cpu_sigs", None)
    assert read(src) == 0.0


def test_every_cell_reports_the_small_batch_metrics():
    from chipbench import run

    bench = run.load_benchmark()
    for cell in bench["workloads"]:
        names = [m["name"] for m in run.metrics_for(bench, cell["name"], "per_layer")]
        assert set(SMALL_BATCH_METRICS) <= set(names), cell["name"]
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(SMALL_BATCH_METRICS[0])
    assert tuple(names[first:first + 4]) == SMALL_BATCH_METRICS
    assert all("workloads" not in m for m in bench["per_layer"][first:first + 4])


# -- copies of a payload not checked again -------------------------------------

# (node 0's duplicates and accepted at the window's first snapshot, the same at
# its last; node 1's; the reading)
DUPLICATE_CASES = {
    # in the window 30 + 10 duplicates of 80 + 80 copies
    "pooled": ((100, 500), (130, 550), (0, 20), (10, 90), 25.0),
    "none_again": ((5, 10), (5, 60), (0, 0), (0, 40), 0.0),
    "no_copy_in_the_window": ((5, 10), (5, 10), (0, 0), (0, 0), None),
}


def _duplicate_src(node0, node1):
    src = _src()
    for node, (at_first, at_last) in zip(src["nodes"], (node0, node1)):
        (_t, first), (_t, last), (_t, after) = node["snapshots"]
        for snap, (dup, other) in ((first, at_first), (last, at_last), (after, at_last)):
            snap["counters"].update({"mempool.payloads_duplicate": dup,
                                     "mempool.payloads_other": other})
    return src


@pytest.mark.parametrize("case", sorted(DUPLICATE_CASES))
def test_duplicate_share_is_pooled_over_the_nodes(case):
    from chipbench import run

    read = run.load_reader("per_layer", "mempool.duplicate_share")
    n0_first, n0_last, n1_first, n1_last, expected = DUPLICATE_CASES[case]
    src = _duplicate_src((n0_first, n0_last), (n1_first, n1_last))
    assert read(src) == (expected if expected is None else pytest.approx(expected))


def test_duplicate_share_needs_the_counter_and_a_bracketing_snapshot():
    from chipbench import run

    read = run.load_reader("per_layer", "mempool.duplicate_share")
    # a program that accepts every copy: the counter is on no snapshot
    older = _duplicate_src(((100, 500), (130, 550)), ((0, 20), (10, 100)))
    for _t, snap in older["nodes"][1]["snapshots"]:
        snap["counters"].pop("mempool.payloads_duplicate")
    assert read(older) is None
    # one node with no snapshot at or before the window's opening
    late = _duplicate_src(((100, 500), (130, 550)), ((0, 20), (10, 100)))
    late["nodes"][0]["snapshots"] = late["nodes"][0]["snapshots"][1:]
    assert read(late) is None


def test_every_cell_reports_the_duplicate_share():
    from chipbench import run

    bench = run.load_benchmark()
    (entry,) = (m for m in bench["per_layer"] if m["name"] == "mempool.duplicate_share")
    assert entry == {
        "name": "mempool.duplicate_share", "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "mempool", "moves": "verified_tx_per_s",
    }
    # what later PRs appended after it is theirs to pin
    assert bench["per_layer"][-1 - len(IDLE_METRICS)] == entry
    for cell in bench["workloads"]:
        names = [m["name"] for m in run.metrics_for(bench, cell["name"], "per_layer")]
        assert "mempool.duplicate_share" in names, cell["name"]


# -- the device's idle by cause (the idle account) ------------------------------

IDLE_METRICS = (
    "device.idle_no_request_share", "device.idle_held_share", "device.idle_host_share",
)
# since boot at the window's first snapshot, at its last; 40 s apart
IDLE_COUNTS = {
    "device.idle_no_request_share": ("timeline.idle_no_request_s", 50.0, 62.0, 30.0),
    "device.idle_held_share": ("timeline.idle_held_s", 1.0, 3.0, 5.0),
    "device.idle_host_share": ("timeline.idle_host_s", 2.0, 2.8, 2.0),
}


def _idle_src(names=None):
    src = _src()
    (_t, first), (_t, last), (_t, after) = src["sidecar"]["snapshots"]
    for name, at_first, at_last, _share in IDLE_COUNTS.values():
        if names is None or name in names:
            first["counters"][name] = at_first
            last["counters"][name] = after["counters"][name] = at_last
    return src


@pytest.mark.parametrize("name", IDLE_METRICS)
def test_idle_share_is_the_windows_advance_over_its_seconds(name):
    from chipbench import run

    read = run.load_reader("per_layer", name)
    counter, _a, _b, share = IDLE_COUNTS[name]
    assert read(_idle_src()) == pytest.approx(share)
    # a program without the account (the parent): nothing to read, and not 0
    assert read(_src()) is None
    assert read(_idle_src(names={c for c, *_ in IDLE_COUNTS.values()} - {counter})) is None
    # no snapshot at or before the window's opening: nothing to read
    late = _idle_src()
    late["sidecar"]["snapshots"] = late["sidecar"]["snapshots"][1:]
    assert read(late) is None
    late["sidecar"]["snapshots"] = []
    assert read(late) is None


def test_every_cell_reports_the_idle_shares():
    from chipbench import run

    bench = run.load_benchmark()
    entries = [m for m in bench["per_layer"] if m["name"] in IDLE_METRICS]
    assert entries == [
        {"name": name, "unit": "%", "better": "lower", "source": "program_counter",
         "layer": "device", "moves": "verified_tx_per_s"}
        for name in IDLE_METRICS
    ]
    assert bench["per_layer"][-len(IDLE_METRICS):] == entries
    for cell in bench["workloads"]:
        names = [m["name"] for m in run.metrics_for(bench, cell["name"], "per_layer")]
        assert set(IDLE_METRICS) <= set(names), cell["name"]


def test_every_new_name_is_in_the_namespace():
    declared = {name for name, _kind, _b in metrics._DEFAULT_NAMESPACE}
    assert {
        "sidecar.requests", "sidecar.request_sigs", "sidecar.parse_s", "sidecar.reply_s",
        "sidecar.request_s", "service.collect_s", "service.backend_s",
        "crypto.remote_rtt_s", "mempool.verify_rtt_s", "runtime.loop_cpu_s",
        "sidecar.columnar_sigs", "service.scatter_s",
        "mempool.pool_build_s", "mempool.pool_triples",
        "crypto.remote_cpu_sigs", "crypto.remote_cpu_batches",
        "mempool.synthetic_skipped_batches",
        "scheduler.critical_groups", "scheduler.critical_held",
        "mempool.payloads_duplicate",
        "timeline.device_busy_s", "timeline.idle_host_s", "timeline.idle_held_s",
        "timeline.idle_no_request_s",
    } <= declared
    # a phase's histogram is its profiler name plus `_s`
    for name in timeline.PHASES.values():
        assert name + "_s" in declared, name


# -- the critical lane's dispatch window (ISSUE 35) ---------------------------

# (counters and the consensus lane's histogram count at the window's first
# snapshot, the same at its last, the reading)
CRITICAL_WINDOW_CASES = {
    # 900 groups on 400 dispatches in the window, since boot 1,000 on 1,000
    "shared": ({"scheduler.critical_groups": 1_000, "scheduler.critical_dispatches": 1_000},
               {"scheduler.critical_groups": 1_900, "scheduler.critical_dispatches": 1_400},
               2.25),
    # the parent's one program a request
    "alone": ({"scheduler.critical_groups": 50, "scheduler.critical_dispatches": 50},
              {"scheduler.critical_groups": 2_050, "scheduler.critical_dispatches": 2_050},
              1.0),
    # a program older than the counter: the lane's histogram counted the groups
    "older_program": ({"scheduler.critical_dispatches": 10, "queue_count": 12},
                      {"scheduler.critical_dispatches": 30, "queue_count": 72},
                      3.0),
    # the counter wins where a program has both
    "both": ({"scheduler.critical_groups": 0, "scheduler.critical_dispatches": 0, "queue_count": 5},
             {"scheduler.critical_groups": 30, "scheduler.critical_dispatches": 20, "queue_count": 999},
             1.5),
    "no_critical_dispatch_in_the_window": (
        {"scheduler.critical_groups": 7, "scheduler.critical_dispatches": 7},
        {"scheduler.critical_groups": 7, "scheduler.critical_dispatches": 7}, None),
    "dispatches_uncounted": ({"scheduler.critical_groups": 1}, {"scheduler.critical_groups": 9}, None),
    "groups_uncounted": ({"scheduler.critical_dispatches": 1}, {"scheduler.critical_dispatches": 9}, None),
    "neither": ({}, {}, None),
}


@pytest.mark.parametrize("case", sorted(CRITICAL_WINDOW_CASES))
def test_critical_groups_per_dispatch(case):
    from chipbench import run

    read = run.load_reader("per_layer", "sidecar.critical_groups_per_dispatch")
    at_first, at_last, expected = CRITICAL_WINDOW_CASES[case]
    src = _src()
    (_t, first), (_t, last), (_t, after) = src["sidecar"]["snapshots"]
    for snap, add in ((first, at_first), (last, at_last), (after, at_last)):
        add = dict(add)
        if "queue_count" in add:
            snap["histograms"]["scheduler.queue_consensus_s"] = {
                "sum": 0.1, "count": add.pop("queue_count")}
        snap["counters"].update(add)
    assert read(src) == (expected if expected is None else pytest.approx(expected))
    # no snapshots around the window: nothing to read
    src["sidecar"]["snapshots"] = []
    assert read(src) is None


def test_the_critical_windows_metric_is_due_where_critical_dispatches_are():
    """No critical dispatch in the window means no reading, and `run.py`
    prints no line where a due metric has none: the entry lists the cells
    whose windows hold tens to thousands of critical dispatches, and leaves
    out the two whose requests are nearly all over 256 signatures (0 to 13
    a window on the chip, PERF.md section 6, PR 35)."""
    from chipbench import run

    bench = run.load_benchmark()
    name = "sidecar.critical_groups_per_dispatch"
    (entry,) = (m for m in bench["per_layer"] if m["name"] == name)
    listed = ["fork-n4-fablocal.flood", "fork-n4.steady", "fork-n10.flood",
              "fork-n10-ownpool.flood"]
    assert entry == {
        "name": name, "unit": "groups",
        "better": "higher", "source": "program_counter", "layer": "sidecar",
        "moves": "verified_tx_per_s", "workloads": listed,
    }
    for cell in bench["workloads"]:
        names = [m["name"] for m in run.metrics_for(bench, cell["name"], "per_layer")]
        assert (name in names) == (cell["name"] in listed), cell["name"]
