"""`fork-n4-fablocal` (PR 33): the fork's workload under upstream's own `fab
local` node parameters, where a payload is 29 transactions and every workload
batch 29 signatures or fewer.

What is pinned here: the configuration is `benchmark/fabfile.py`'s
`LOCAL_NODE_PARAMS` value for value; a payload maker at those parameters seals
29 transactions of 512 B; and small groups through a node's real service over
a `RemoteBackend` and an in-process sidecar (the jnp verify program, on the
CPU) get, group by group, the verdict of `chipbench/reference.py` (OpenSSL,
which imports nothing of the program), whether their coalesced request went
over the wire or stayed, under the crossover, on the node's own CPU.
"""

import asyncio
import json
import random
from dataclasses import replace

import pytest

pytest.importorskip("cryptography")

from benchmark.fabfile import LOCAL_BENCH_PARAMS, LOCAL_NODE_PARAMS
from chipbench import reference, run
from chipbench.traffic import Traffic
from hotstuff_tpu.crypto import PublicKey, Signature, SignatureService
from hotstuff_tpu.crypto.backend import make_backend
from hotstuff_tpu.crypto.batch_service import BatchVerificationService
from hotstuff_tpu.crypto.remote import RemoteBackend, serve
from hotstuff_tpu.mempool import MempoolParameters
from hotstuff_tpu.mempool.core import Core
from hotstuff_tpu.mempool.payload_maker import PayloadMaker
from hotstuff_tpu.node.config import NodeParameters
from hotstuff_tpu.store import Store
from hotstuff_tpu.utils import metrics
from hotstuff_tpu.utils.actors import channel
from tests.common import keys
from tests.common_mempool import mempool_committee

WORKLOAD_KEYS = {"benchmark_mode", "synthetic_pool_size"}
_config = run.load_config


# -- the configuration ----------------------------------------------------------


@pytest.mark.parametrize("name", ["fork-n4-fablocal", "tiny-n4-fablocal"])
def test_parameters_are_upstreams_local_node_parameters(name, tmp_path):
    cfg = _config(name)
    params = cfg["parameters"]
    assert params["consensus"] == LOCAL_NODE_PARAMS["consensus"]
    mempool = {k: v for k, v in params["mempool"].items() if k not in WORKLOAD_KEYS}
    assert mempool == LOCAL_NODE_PARAMS["mempool"]
    assert set(params["mempool"]) - set(mempool) == WORKLOAD_KEYS
    assert params["mempool"]["benchmark_mode"] is True
    assert (cfg["nodes"], cfg["tx_size"]) == (
        LOCAL_BENCH_PARAMS["nodes"], LOCAL_BENCH_PARAMS["tx_size"])
    # as a node reads them (`launch.py` writes this object as .parameters.json)
    path = tmp_path / "parameters.json"
    path.write_text(json.dumps(params))
    read = NodeParameters.read(str(path))
    assert (read.mempool.max_payload_size, read.mempool.min_block_delay) == (15_000, 0)
    assert (read.consensus.timeout_delay, read.consensus.min_block_delay) == (1_000, 0)
    assert read.mempool.synthetic_pool_size == params["mempool"]["synthetic_pool_size"]


def test_the_benchmarks_configuration_states_what_a_configuration_must():
    cfg = _config("fork-n4-fablocal")
    assert cfg["parameters"]["mempool"]["synthetic_pool_size"] == {"per_node": 200_000}
    assert cfg["sidecar"] == _config("fork-n10-ownpool")["sidecar"]
    assert cfg["reduced"] == ["run_length", "hosts"] == list(cfg["reduced_note"])
    assert {"pool_seed", "node_parameters"} <= set(cfg["assumed"])
    # fork-n10-ownpool's guarantees word for word where they apply, and one more
    own = _config("fork-n10-ownpool")["guarantees"]
    assert cfg["guarantees"][:5] == own[:5]
    assert "own CPU" in cfg["guarantees"][-1] and "counts as verified" in cfg["guarantees"][-1]
    bench = run.load_benchmark()
    (entry,) = (c for c in bench["configs"] if c["name"] == "fork-n4-fablocal")
    assert (entry["name"], entry["source"], entry["reduced"]) == (
        cfg["name"], cfg["source"], cfg["reduced"])
    assert len(entry["source"]) <= 200
    (cell,) = (w for w in bench["workloads"] if w["name"] == "fork-n4-fablocal.flood")
    assert (cell["name"], cell["config"], cell["chips"]) == (
        "fork-n4-fablocal.flood", "fork-n4-fablocal", 1)
    assert len(cell["why"]) <= 200
    # the other flood files' shape; only the rate is this cell's own
    traffic, other = Traffic.load(cell["traffic"]), Traffic.load("flood-n10-ownpool")
    assert replace(traffic, name=other.name, rate=other.rate) == other and traffic.rate > 0


# -- the payload maker ----------------------------------------------------------------


def test_a_payload_at_fifteen_thousand_bytes_is_29_transactions_of_512(run_async):
    mempool = MempoolParameters.from_json(_config("fork-n4-fablocal")["parameters"]["mempool"])

    async def body():
        pk, sk = keys()[0]
        tx_in, core_ch = channel(), channel()
        PayloadMaker(pk, SignatureService(sk), mempool.max_payload_size,
                     mempool.min_block_delay, tx_in, core_ch)
        for i in range(100):
            await tx_in.put(i.to_bytes(4, "little") + bytes(508))
        sealed = []
        for _ in range(3):
            sealed.append((await asyncio.wait_for(core_ch.get(), 2.0)).payload)
        # no block delay: three payloads are out before any sleep could end
        return sealed

    sealed = run_async(body())
    assert [len(p.transactions) for p in sealed] == [29, 29, 29]
    assert all(p.size() == 29 * 512 <= 15_000 < 30 * 512 for p in sealed)
    # in order, none lost between payloads
    firsts = [int.from_bytes(p.transactions[0][:4], "little") for p in sealed]
    assert firsts == [0, 29, 58]


# -- the cap counts batches ---------------------------------------------------------


def test_a_skipped_batch_counts_once_whatever_its_size(run_async):
    skipped = metrics.counter("mempool.synthetic_skipped")
    batches = metrics.counter("mempool.synthetic_skipped_batches")
    before = skipped.value, batches.value

    async def body():
        hold = asyncio.Event()

        class Held:
            async def verify_group(self, msgs, pairs, **_kw):
                await hold.wait()
                return [True] * len(msgs)

        core = Core(
            keys(4)[0][0], mempool_committee(19_000, 4),
            MempoolParameters(benchmark_mode=True, synthetic_pool_size=64),
            Store(), None, None, channel(), channel(), channel(),
            verification_service=Held(), max_inflight_verifications=2,
        )
        for n in (29, 29, 29, 7, 1):  # two take the slots, three find them taken
            await core._submit_synthetic_batch("OTHER", n)
        hold.set()
        await core.drain_verifications()
        await core._submit_synthetic_batch("OWN", 29)  # a slot is free again
        await core.drain_verifications()

    run_async(body())
    assert skipped.value - before[0] == 29 + 7 + 1
    assert batches.value - before[1] == 3


# -- the acceptance bound ------------------------------------------------------------


def test_a_full_acceptance_bound_drops_a_payload_and_a_free_one_releases_the_block(
    run_async, base_port
):
    """The bound as it stands (64 slots; `PERF.md` section 7 holds the open
    question it leaves under 100 small payloads a second): while it is full
    every arriving payload is dropped and counted, one that a suspended block
    waits for among them; once a slot is free the same payload is verified,
    stored, and the block goes back to consensus."""
    from hotstuff_tpu.consensus.mempool_driver import MempoolVerify, PayloadStatus
    from hotstuff_tpu.mempool import Mempool
    from hotstuff_tpu.mempool.messages import Payload
    from tests.common import chain, committee

    dropped = metrics.counter("mempool.gossip_dropped")

    async def body():
        n = 4
        pk, sk = keys()[0]
        store, cm, consensus_channel = Store(), channel(), channel()
        core = Mempool.run(
            pk, mempool_committee(base_port, n), MempoolParameters(), store,
            SignatureService(sk), cm, consensus_channel,
        )
        await asyncio.sleep(0.05)
        author_pk, author_sk = keys()[1]
        asked = Payload.new_from_key([b"\x01" + bytes(40)], author_pk, author_sk)
        gossip = Payload.new_from_key([b"\x01" + bytes(41)], author_pk, author_sk)
        block = chain(1, committee(base_port + 2 * n))[0]
        object.__setattr__(block, "payload", (asked.digest(),))
        fut = asyncio.get_running_loop().create_future()
        await cm.put(MempoolVerify(block, fut))
        assert await asyncio.wait_for(fut, 5) == PayloadStatus.WAIT

        core._accept_sem = asyncio.Semaphore(1)
        await core._accept_sem.acquire()  # the bound is full
        before = dropped.value
        await core._handle_others_payload(gossip)
        await core._handle_others_payload(asked)
        await core.drain_verifications()
        assert dropped.value - before == 2
        assert await store.read(b"payload:" + asked.digest().data) is None
        assert await store.read(b"payload:" + gossip.digest().data) is None
        assert consensus_channel.empty()

        core._accept_sem.release()
        await core._handle_others_payload(asked)
        lb = await asyncio.wait_for(consensus_channel.get(), 5)
        assert lb.block == block
        assert dropped.value - before == 2
        assert await store.read(b"payload:" + asked.digest().data) is not None
        await core.drain_verifications()
        assert not core._accept_sem.locked()

    run_async(body())


# -- small groups through the node's service, the wire and the sidecar --------------

GROUPS = 40
# the seven ways of `chip_smoke.py`'s corpus, which are `reference._corrupt`'s
KINDS = 7


def _corpus(seed):
    """Forty groups of 1 to 29 triples over distinct 32-byte messages from 16
    seeded keys; a fifth of the lanes corrupted, kinds cycling, the first and
    the last lane of some groups among them (kinds 1 and 2 borrow from the
    lane before: across the group's boundary)."""
    rng = random.Random(seed)
    sizes = [rng.randint(1, 29) for _ in range(GROUPS)]
    sizes[3], sizes[17] = 1, 29
    n = sum(sizes)
    signers = [reference.keypair(rng.randbytes(32)) for _ in range(16)]
    msgs = [rng.randbytes(28) + i.to_bytes(4, "little") for i in range(n)]
    pks = [signers[i % 16][0] for i in range(n)]
    sigs = [signers[i % 16][1].sign(msgs[i]) for i in range(n)]
    starts = [sum(sizes[:g]) for g in range(GROUPS)]
    edges = [starts[g] for g in range(2, GROUPS, 3)] + [
        starts[g] + sizes[g] - 1 for g in range(1, GROUPS, 5)]
    bad = sorted(set(edges) | set(rng.sample(range(2, n), n // 5)))[: n // 5]
    for j, i in enumerate(bad):
        reference._corrupt(i, j % KINDS, msgs, pks, sigs, rng)
    groups = [
        (msgs[a:a + k], pks[a:a + k], sigs[a:a + k]) for a, k in zip(starts, sizes)
    ]
    return groups, len(bad)


def test_small_groups_get_the_references_verdict_on_the_wire_and_under_the_crossover(
    run_async, base_port
):
    groups, n_bad = _corpus(33)
    want = [
        [reference.verify_strict(m, k, s) for m, k, s in zip(*g)] for g in groups
    ]
    total = sum(len(w) for w in want)
    assert sum(not ok for w in want for ok in w) == n_bad == total // 5
    # corrupted lanes at both edges of groups
    assert sum(not w[0] for w in want) >= 5 and sum(not w[-1] for w in want) >= 5

    names = ("crypto.remote_sigs", "crypto.remote_batches", "crypto.remote_cpu_sigs",
             "crypto.remote_cpu_batches", "crypto.remote_fallback_batches",
             "sidecar.requests", "sidecar.request_sigs", "sidecar.columnar_sigs")

    def read():
        return {n: metrics.counter(n).value for n in names}

    async def body():
        device = make_backend("tpu", crossover=1, min_bucket=128, max_bucket=128)
        # as the sidecar's `main` does before it serves: a first request that
        # met the program's compile would outwait the node's 30 s and be sent twice
        await asyncio.to_thread(device.warmup)
        server = asyncio.create_task(serve(("127.0.0.1", base_port), device))
        await asyncio.sleep(0.2)
        c0 = read()
        remote = RemoteBackend(("127.0.0.1", base_port))  # crossover 64, a node's
        service = BatchVerificationService(remote)
        got = [None] * GROUPS

        async def one(g):
            msgs, pks, sigs = groups[g]
            pairs = [(PublicKey(k), Signature(s)) for k, s in zip(pks, sigs)]
            # as `mempool/core.py` `_run_synthetic` submits a workload batch
            got[g] = await service.verify_group(
                msgs, pairs, urgent=False, dedup=False, source="mempool")

        # waves as a node's eight slots make them: a few alone or in pairs
        # (coalesced under 64: the node's CPU), the rest eight at a time
        waves = [[0], [1, 2], [3], [4, 5]] + [
            list(range(a, min(a + 8, GROUPS))) for a in range(6, GROUPS, 8)]
        try:
            for wave in waves:
                await asyncio.wait_for(asyncio.gather(*(one(g) for g in wave)), 120)
            await asyncio.sleep(0.1)
        finally:
            server.cancel()
        c1 = read()
        return got, {n: c1[n] - c0[n] for n in names}, remote.stats, device.stats

    got, d, stats, device_stats = run_async(body(), timeout=600.0)
    assert got == want
    # both paths were taken, and between them every signature sent is counted once
    assert d["crypto.remote_sigs"] >= 64 and d["crypto.remote_cpu_sigs"] >= 1
    assert d["crypto.remote_sigs"] + d["crypto.remote_cpu_sigs"] == total
    assert d["crypto.remote_cpu_sigs"] == stats["cpu_sigs"]
    assert d["crypto.remote_cpu_batches"] == stats["cpu_batches"] >= 1
    assert d["crypto.remote_batches"] == stats["remote_batches"] >= 1
    assert d["crypto.remote_fallback_batches"] == stats["fallback_batches"] == 0
    # what went over the wire is what the sidecar parsed, columnar, and what
    # its device path checked (no lane repeats: its cache answers nothing)
    assert d["sidecar.request_sigs"] == d["crypto.remote_sigs"] == d["sidecar.columnar_sigs"]
    assert d["sidecar.requests"] == d["crypto.remote_batches"]
    assert device_stats["tpu_sigs"] == d["crypto.remote_sigs"]
    # a request is a coalesced bucket: never under the crossover, and larger
    # than any one group
    assert d["crypto.remote_sigs"] / d["crypto.remote_batches"] > 29
