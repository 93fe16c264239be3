"""The benchmark workload's signature pool in its two forms
(`MempoolParameters.synthetic_pool_size`): an integer is one pool that every
node holds alike, `{"per_node": k}` is the fork's own deployment, a pool a
node seeded from its public key (mempool/core.py `SyntheticPool`).

The plain reference is `chipbench/reference.py`'s strict RFC 8032 verdict by
OpenSSL, which imports nothing of the program; the exact-integer `pysigner`
is asked too where it is cheap enough.
"""

import hashlib
import logging
import random

import numpy as np
import pytest

pytest.importorskip("cryptography")

from chipbench import reference
from hotstuff_tpu.crypto import generate_keypair, pysigner
from hotstuff_tpu.crypto.backend import CpuBackend, make_backend, row_columns
from hotstuff_tpu.crypto.batch_service import BatchVerificationService
from hotstuff_tpu.crypto.remote import _encode_request, _parse
from hotstuff_tpu.mempool.config import MempoolParameters
from hotstuff_tpu.mempool.core import Core, SyntheticPool
from hotstuff_tpu.store import Store
from hotstuff_tpu.utils import metrics
from hotstuff_tpu.utils.actors import channel
from tests.common_mempool import mempool_committee

NODES = 4
SIZE = 96
# SHA-256 over the 16 triples of `SyntheticPool(16)` at the parent commit
# (0127ea1): the integer form's pool may not move by a byte
SHARED_16 = "bd3c379d2883e1f57b1401ddf8dcecab22368c07bdd458f3489ff41bdb42af91"


def _names(n=NODES):
    rng = random.Random(29)
    return [generate_keypair(rng)[0] for _ in range(n)]


def _triples(pool):
    return [(m, pk.data, sig.data) for m, (pk, sig) in zip(pool.messages, pool.pairs)]


@pytest.fixture(scope="module")
def pools():
    return [SyntheticPool(SIZE, SyntheticPool.node_seed(name)) for name in _names()]


# -- the parameter -------------------------------------------------------------


@pytest.mark.parametrize("value", [10_000, 1, {"per_node": 200_000}, {"per_node": 1}])
def test_both_forms_parse_and_round_trip(value):
    p = MempoolParameters.from_json({"benchmark_mode": True, "synthetic_pool_size": value})
    assert p.synthetic_pool_size == value
    assert p.to_json()["synthetic_pool_size"] == value
    assert MempoolParameters.from_json(p.to_json()) == p
    assert MempoolParameters(synthetic_pool_size=value).synthetic_pool_size == value


@pytest.mark.parametrize(
    "value",
    ["200000", -1, 0, True, 2.5, None, [200_000], {}, {"per_node": -1}, {"per_node": 0},
     {"per_node": "200000"}, {"per_node": True}, {"per_node": 64, "seed": 7}, {"shared": 64}],
    ids=repr,
)
def test_any_other_value_is_refused_when_the_parameters_are_read(value):
    with pytest.raises(ValueError, match="synthetic_pool_size"):
        MempoolParameters.from_json({"synthetic_pool_size": value})
    with pytest.raises(ValueError, match="synthetic_pool_size"):
        MempoolParameters(synthetic_pool_size=value)


def test_the_default_is_one_shared_pool_of_ten_thousand():
    assert MempoolParameters().synthetic_pool_size == 10_000
    assert MempoolParameters.from_json({}).synthetic_pool_size == 10_000


# -- the pools -------------------------------------------------------------------


def test_the_integer_form_is_the_parents_pool_byte_for_byte():
    h = hashlib.sha256()
    for triple in _triples(SyntheticPool(16)):
        h.update(b"".join(triple))
    assert h.hexdigest() == SHARED_16
    assert SyntheticPool(16).fingerprint() == SHARED_16[:8]
    # and every node makes the same one
    assert _triples(SyntheticPool(16)) == _triples(SyntheticPool(16, seed=7))


def test_the_same_name_gives_the_same_pool_twice(pools):
    name = _names()[0]
    again = SyntheticPool(SIZE, SyntheticPool.node_seed(name))
    assert _triples(again) == _triples(pools[0])
    assert again.fingerprint() == pools[0].fingerprint()


def test_no_triple_is_in_two_nodes_pools(pools):
    seeds = {SyntheticPool.node_seed(name) for name in _names()}
    assert len(seeds) == NODES and 7 not in seeds
    everything = [t for pool in pools for t in _triples(pool)]
    assert len(set(everything)) == NODES * SIZE
    # not a message, a key or a signature either, and none of the shared pool's
    everything += _triples(SyntheticPool(SIZE))
    for part in range(3):
        assert len({t[part] for t in everything}) == (NODES + 1) * SIZE
    assert len({pool.fingerprint() for pool in pools}) == NODES


@pytest.mark.parametrize("node", range(NODES))
def test_every_triple_verifies_under_the_reference(pools, node):
    triples = _triples(pools[node])
    assert all(reference.verify_strict(*t) for t in triples)
    # the exact-integer verifier on a few (20 ms a signature)
    for m, pk, sig in triples[:3]:
        assert pysigner.verify_exact(pk, m, sig)


def test_a_corrupted_triple_does_not_verify(pools):
    m, pk, sig = _triples(pools[1])[5]
    other = _triples(pools[2])[5]
    for bad in (
        (m, pk, sig[:40] + bytes([sig[40] ^ 4]) + sig[41:]),
        (bytes([m[0] ^ 1]) + m[1:], pk, sig),
        (m, other[1], sig),  # another node's key
        (m, pk, other[2]),  # another node's signature
    ):
        assert not reference.verify_strict(*bad)
        assert not pysigner.verify_exact(bad[1], bad[0], bad[2])


@pytest.mark.parametrize("node", range(3))
def test_the_programs_masks_equal_the_references_lane_for_lane(node):
    """Whole batches as `take` draws them (the second wraps round the pool),
    a few lanes corrupted, through the verify program itself: the jnp kernel
    that stands in for the chip's here, every batch on the device path."""
    pool = SyntheticPool(SIZE, SyntheticPool.node_seed(_names()[node]))
    backend = make_backend("tpu", crossover=1)
    rng = random.Random(node)
    for _ in range(2):
        msgs, pairs = pool.take(64)
        pks = [pk for pk, _sig in pairs]
        sigs = [sig.data for _pk, sig in pairs]
        for lane in rng.sample(range(64), 5):
            sigs[lane] = sigs[lane][:9] + bytes([sigs[lane][9] ^ 1]) + sigs[lane][10:]
        msgs[rng.randrange(64)] = rng.randbytes(32)
        want = [reference.verify_strict(m, pk.data, s) for m, pk, s in zip(msgs, pks, sigs)]
        assert 50 <= sum(want) < 64
        rows = np.frombuffer(
            b"".join(m + pk.data + s for m, pk, s in zip(msgs, pks, sigs)), np.uint8
        ).reshape(64, 128)
        assert list(backend.verify_batch_mask(*row_columns(rows))) == want
    assert backend.stats["tpu_sigs"] == 128 and backend.stats["cpu_sigs"] == 0
    # the cursor went once round the pool and a third of the way again
    assert pool.take(1)[0][0] == pool.messages[128 % SIZE]


# -- what the deployment is about: the sidecar's cache finds nothing ------------------


class _CountingCpu(CpuBackend):
    def __init__(self):
        self.lanes = 0

    def verify_batch_mask(self, messages, keys, signatures):
        self.lanes += len(messages)
        return super().verify_batch_mask(messages, keys, signatures)


@pytest.mark.parametrize("kind", ["per_node", "shared"])
def test_the_cache_answers_a_shared_pool_and_nothing_of_per_node_pools(run_async, kind):
    """Three nodes verify the same payloads, one after another, through one
    service with its cache on, fed as the sidecar feeds it: the request's
    bytes parsed into rows, `dedup` left on as the wire leaves it. Batches of
    64 and more: `RemoteBackend` keeps smaller ones on the node's own CPU."""
    pools = [
        SyntheticPool(256, SyntheticPool.node_seed(name)) if kind == "per_node"
        else SyntheticPool(256)
        for name in _names(3)
    ]
    payloads = (64, 96, 80)  # no node comes round its pool of 256
    hits = metrics.counter("verifier.dedup_hits")
    misses = metrics.counter("verifier.dedup_misses")
    hits0, misses0 = hits.value, misses.value

    async def body():
        backend = _CountingCpu()
        service = BatchVerificationService(backend)
        assert service.dedup is not None
        for k in payloads:
            for pool in pools:
                msgs, pairs = pool.take(k)
                rows = _parse(_encode_request(msgs, *zip(*pairs))[4:])
                assert isinstance(rows, np.ndarray) and rows.shape == (k, 128)
                assert (await service.verify_rows(rows)).all()
        return backend.lanes

    lanes = run_async(body())
    sent = 3 * sum(payloads)
    assert (hits.value - hits0) + (misses.value - misses0) == sent
    if kind == "per_node":
        # no node's check spares another's: the backend sees every lane
        assert (hits.value - hits0, lanes) == (0, sent)
    else:
        # the first node's check answers the two that follow it
        assert (hits.value - hits0, lanes) == (2 * sent // 3, sent // 3)


# -- the node's boot ---------------------------------------------------------------


@pytest.mark.parametrize("value, kind", [(48, "shared"), ({"per_node": 48}, "per_node")])
def test_core_builds_the_pool_its_parameters_name_and_says_so(
    run_async, base_port, caplog, value, kind
):
    name = _names()[2]
    built = metrics.histogram("mempool.pool_build_s")
    triples = metrics.counter("mempool.pool_triples")
    count0, triples0 = built.count, triples.value

    async def core(parameters):
        return Core(
            name, mempool_committee(base_port, 4), parameters,
            Store(), None, None, channel(), channel(), channel(),
        )

    with caplog.at_level(logging.INFO, logger="hotstuff.mempool"):
        pool = run_async(
            core(MempoolParameters(benchmark_mode=True, synthetic_pool_size=value))
        ).pool
    seed = SyntheticPool.node_seed(name) if kind == "per_node" else 7
    want = SyntheticPool(48, seed)
    assert _triples(pool) == _triples(want)
    assert (built.count, triples.value) == (count0 + 1, triples0 + 48)
    line = f"Synthetic pool: 48 triples, {kind}, fingerprint {want.fingerprint()}"
    assert line in [r.getMessage() for r in caplog.records]
    # without benchmark_mode no pool is made, whatever the size says
    assert run_async(core(MempoolParameters(synthetic_pool_size=value))).pool is None
    assert built.count == count0 + 1
