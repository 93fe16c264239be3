"""Crash faults in the harness: a configuration's `faults` f are the last f
members of its committee, which never boot; the run is judged on the n - f
live nodes. On hand-made logs, stores and records, and on a deployment that
spawns nothing. Run by hand (`JAX_PLATFORMS=cpu python3 -m pytest
chipbench/tests -q`); seconds.
"""

from __future__ import annotations

import base64
import json
import os
import struct

import pytest

from chipbench import arith, collect, judge, launch
from chipbench import reference as ref

SEED = 4_000_000_011
SIZE = 512
PER_TICK = 10  # 30 tx/s over three live clients, ticks of 1 s


def _stamp(t: float) -> str:
    """A log time stamp for `t` seconds after 2026-10-15T03:00:00Z."""
    ms = round(t * 1000)
    return f"2026-10-15T03:{ms // 60000:02d}:{ms // 1000 % 60:02d}.{ms % 1000:03d}Z"


T0 = 1_792_033_200.0  # 2026-10-15T03:00:00Z


def _write_log(work, i, commits, timeouts=()):
    os.makedirs(os.path.join(work, "logs"), exist_ok=True)
    lines = []
    for t, r, digest in commits:
        lines.append(f"[{_stamp(t)} INFO hotstuff.consensus] Committed B{r}({digest})")
    for t, r in timeouts:
        lines.append(f"[{_stamp(t)} WARNING hotstuff.consensus] Timeout reached for round {r}")
    lines.sort()
    with open(os.path.join(work, "logs", f"node-{i}.log"), "w") as f:
        f.write("\n".join(lines) + "\n")


def _cfg(n, f):
    return {"nodes": n, "faults": f, "tx_size": SIZE,
            "parameters": {"consensus": {"timeout_delay": 5000}}}


def _window():
    return {"start": T0, "t0": T0 + 2.0, "t1": T0 + 4.0, "seconds": 2.0, "end": T0 + 6.0}


def _judged(work, cfg, n_logs=None):
    """Sources gathered as `run.py` gathers them, the consensus part judged."""
    src = collect.gather(work, arith.live_nodes(cfg) if n_logs is None else n_logs)
    src.update({"window": _window(), "config": cfg})
    out: dict = {}
    judge._consensus(src, out)
    return out


def test_a_member_dead_by_design_is_not_silent(tmp_path):
    work = str(tmp_path)
    for i in range(3):  # node 3 never booted: no log
        _write_log(work, i, [(2.5, 1, "A"), (3.5, 2, "B")])
    out = _judged(work, _cfg(4, 1))
    assert out["nodes_silent"][0] == 0 and out["no_common_round"][0] == 0
    # the harness of before read all n logs and failed the run on the dead one
    assert _judged(work, _cfg(4, 1), n_logs=4)["nodes_silent"][0] == 1


def test_a_live_node_that_falls_silent_still_fails(tmp_path):
    work = str(tmp_path)
    _write_log(work, 0, [(2.5, 1, "A"), (3.5, 2, "B")])
    _write_log(work, 1, [(2.5, 1, "A"), (3.5, 2, "B")])
    _write_log(work, 2, [(1.0, 1, "A")])  # commits before the window only
    assert _judged(work, _cfg(4, 1))["nodes_silent"][0] == 1


def test_chain_agreement_is_over_the_live_nodes(tmp_path):
    work = str(tmp_path)
    _write_log(work, 0, [(2.5, 1, "A"), (3.5, 2, "B")])
    _write_log(work, 1, [(2.5, 1, "A"), (3.5, 2, "B")])
    _write_log(work, 2, [(2.5, 1, "A"), (3.5, 2, "C")])
    out = _judged(work, _cfg(4, 1))
    assert out["chain_forks"][0] == 1 and out["nodes_silent"][0] == 0
    _write_log(work, 2, [(3.6, 3, "D")])  # in the window, no round the others have
    out = _judged(work, _cfg(4, 1))
    assert out["chain_forks"][0] == 0 and out["no_common_round"][0] == 1


def _payload_record(digest: bytes, txs, author: bytes) -> bytes:
    body = struct.pack("<I", len(txs))
    for tx in txs:
        body += struct.pack("<I", len(tx)) + tx
    body += author + bytes(64)
    key = b"payload:" + digest
    return struct.pack("<II", len(key), len(body)) + key + body


def _run_sources(work, cfg):
    """Three live clients of a committee of four, 10 tx a tick each at ticks
    0..4 (the window holds ticks 2 and 3); every live node commits its own
    client's 50 in one payload inside the window."""
    seed8 = ref.seed_bytes(SEED)
    live = arith.live_nodes(cfg)
    pubs = [bytes([i + 1]) * 32 for i in range(cfg["nodes"])]
    records, nodes = [], []
    for c in range(live):
        txs = []
        for k in range(5):
            records.append([c, k, T0 + k, T0 + k, PER_TICK, PER_TICK * k])
            txs.append(ref.make_tx(seed8, ref.SAMPLE, ref.sample_id(c, k), SIZE))
            txs += [ref.make_tx(seed8, 1, ref.tx_tag(c, PER_TICK * k + x), SIZE)
                    for x in range(1, PER_TICK)]
        digest = ref.payload_digest(pubs[c], txs)
        d64 = base64.standard_b64encode(digest).decode()
        os.makedirs(os.path.join(work, f".db-{c}"))
        with open(os.path.join(work, f".db-{c}", "log"), "wb") as f:
            f.write(_payload_record(digest, txs, pubs[c]))
        snap = lambda t, sigs: (t, {"counters": {}, "histograms": {},  # noqa: E731
                                    "info": {"backend": {"tpu_sigs": sigs}}})
        nodes.append({
            "blocks": [(T0 + 2.5, 1, "A"), (T0 + 3.5, 2, "B")],
            "payload_commits": [(T0 + 3.5, 2, d64)], "created": [],
            "own_payloads": {d64: len(txs) * SIZE}, "samples": {}, "timeouts": [],
            "verify": [], "verify_failed": 0, "warnings": 0, "errors": [],
            "snapshots": [snap(T0 + 1.0, 0), snap(T0 + 4.5, 0)],
        })
    return {
        "seed": SEED, "config": cfg,
        "traffic": {"rate": PER_TICK * live, "tick_s": 1.0, "drain_s": 1.0},
        "window": _window(), "records": records, "committee_pubs": pubs, "nodes": nodes,
        "sidecar": {"snapshots": [snap(T0 + 1.0, 0), snap(T0 + 3.9, 9)]},
        "probe": {"corpus": [], "answers": []},
    }


def test_offer_and_accounting_are_over_the_live_clients(tmp_path):
    cfg = _cfg(4, 1)
    src = _run_sources(str(tmp_path), cfg)
    compared = judge.judge(src, str(tmp_path))
    assert src["attempted"] == 3 * 2 * PER_TICK and src["failed"] == 0, compared
    assert src["tx_checked"] == 3 * 5 * PER_TICK
    over = {k: v for k, (v, limit) in compared.items() if v > limit}
    # (an offer split over all four members maps a client's sequence numbers
    # to ticks at 7.5 a tick: 4 of each client's 20 would read as out, 12
    # failed)
    assert not over, over
    from chipbench import drain

    assert drain.sent_by_client(src["records"], arith.live_nodes(cfg)) == [50, 50, 50]


def test_nodes_logs_and_config_must_agree(tmp_path):
    src = _run_sources(str(tmp_path), _cfg(4, 1))
    src["nodes"].append(dict(src["nodes"][0]))
    with pytest.raises(ValueError):
        judge.judge(src, str(tmp_path))


class _Spawned(launch.Deployment):
    def _spawn(self, name, cmd):
        self.procs[name] = cmd
        return cmd


def test_launch_boots_the_live_nodes_of_the_whole_committee(tmp_path):
    cfg = {"nodes": 4, "faults": 1, "tx_size": SIZE, "parameters": {},
           "sidecar": {"min_bucket": 256, "chunk": 256}}
    dep = _Spawned(str(tmp_path), cfg, SEED)
    dep.start()
    assert sorted(dep.procs) == ["node-0", "node-1", "node-2", "sidecar"]
    with open(tmp_path / ".committee.json") as f:
        committee = json.load(f)
    assert len(committee["consensus"]["authorities"]) == 4  # the quorum is 3 of 4
    client = dep.start_client(30.0, 50.0, SEED, 0.0, 1.0, "r.jsonl")
    targets = client[client.index("--targets") + 1].split(",")
    assert targets == [dep.committee.front(i) for i in range(3)]
    side = dep.procs["sidecar"]
    assert side[side.index("--committee") + 1] == ".committee.json"


@pytest.mark.parametrize("n,f,ok", [(4, 1, True), (4, 2, False), (10, 3, True), (10, 4, False),
                                    (10, -1, False), (4, 0, True)])
def test_launch_refuses_more_faults_than_the_committee_survives(tmp_path, n, f, ok):
    cfg = {"nodes": n, "faults": f, "tx_size": SIZE, "parameters": {}, "sidecar": {}}
    if ok:
        assert _Spawned(str(tmp_path), cfg, SEED).live == n - f
    else:
        with pytest.raises(launch.LaunchError):
            _Spawned(str(tmp_path), cfg, SEED)
