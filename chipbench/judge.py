"""Decides `correct`: what the timed path produced in the window, against
the plain reference (`reference.py`), number by number, each with its limit.
Every comparison here is exact, so every limit is 0.

Every node here is a LIVE node: `src["nodes"]` holds the n - f that booted
(`arith.live_nodes`, `collect.gather`), one client each. A member that is
dead by design has no log and is judged on nothing; a live one that falls
silent fails the run.

  consensus   every live node commits in the window; no round that two nodes
              both committed carries two digests
  mempool     every payload a node committed for its own client is in that
              node's store, hashes to its digest by the reference's own
              hashing, and holds only transactions that client sent, byte
              for byte as the reference makes them again from the seed, none
              of them twice
  verify      every probe request was answered, lane for lane as OpenSSL
              answers; no workload batch (valid signatures only) was
              rejected; no node fell back to its own CPU; the device checked
              signatures
"""

from __future__ import annotations

import base64
import bisect
import os

from . import arith
from . import reference as ref
from . import traffic


def _consensus(src, out) -> None:
    w = src["window"]
    silent = sum(
        1 for n in src["nodes"] if not any(w["t0"] <= t < w["t1"] for t, _r, _d in n["blocks"])
    )
    by_round: dict = {}
    for n in src["nodes"]:
        for _t, r, d in n["blocks"]:
            by_round.setdefault(r, set()).add(d)
    shared = [set(r for _t, r, _d in n["blocks"]) for n in src["nodes"]]
    common = set.intersection(*shared) if shared else set()
    out["nodes_silent"] = [silent, 0]
    out["chain_forks"] = [sum(1 for ds in by_round.values() if len(ds) > 1), 0]
    out["no_common_round"] = [0 if common else 1, 0]


def _mempool(src, work: str, out) -> tuple[list, list, list, int]:
    """Returns, per node, the transactions of its client committed by the end
    of the run (those due in the window; all), the transactions its client
    sent, and the number of transactions checked."""
    cfg, tr = src["config"], src["traffic"]
    size, n = cfg["tx_size"], len(src["nodes"])
    seed8 = ref.seed_bytes(src["seed"])
    rate_c = tr["rate"] / n  # the offer is split over the live nodes' clients
    ticks = arith.window_ticks(src)
    sent_ticks = [0] * n  # ticks each client got through
    sent_txs = [0] * n
    for c, k, _due, _sent, cnt, seq in src["records"]:
        sent_ticks[c] = max(sent_ticks[c], k + 1)
        sent_txs[c] = max(sent_txs[c], seq + cnt)
    # cumulative schedule of one client: tick -> transactions due before it
    horizon = max(sent_ticks) if sent_ticks else 0
    before = [0] * (horizon + 1)
    for k in range(horizon):
        before[k + 1] = traffic.due_count(rate_c, tr["tick_s"], k)
    missing = digest_bad = tx_bad = dup = checked = late = 0
    from_window, from_all = [0] * n, [0] * n
    # what the fixed wait of before PR 25 s3 would have called failed
    late_after = src["window"]["t1"] + tr.get("drain_s", 0.0)
    seen: set = set()
    mask = (1 << ref.CLIENT_SHIFT) - 1
    for i, node in enumerate(src["nodes"]):
        times = arith.commit_times(node)
        own = [d for d in times if d in node["own_payloads"]]
        if not own:
            continue
        store = ref.read_store(os.path.join(work, f".db-{i}", "log"))
        author = src["committee_pubs"][i]
        for d64 in own:
            digest = base64.standard_b64decode(d64)
            value = store.get(digest)
            if value is None:
                missing += 1
                continue
            txs, who, _sig = ref.decode_payload(value)
            if who != author or ref.payload_digest(who, txs) != digest:
                digest_bad += 1
                continue
            for tx in txs:
                checked += 1
                kind, ident = tx[0], int.from_bytes(tx[1:9], "big")
                c, low = ident >> ref.CLIENT_SHIFT, ident & mask
                ok = c == i and len(tx) == size and kind in (0, 1)
                if ok and kind == ref.SAMPLE:
                    tick = low
                    ok = tick < sent_ticks[c]
                elif ok:
                    ok = low < sent_txs[c]
                    tick = bisect.bisect_right(before, low) - 1
                if not ok or tx != ref.make_tx(seed8, kind, ident, size):
                    tx_bad += 1
                    continue
                key = (kind, ident)
                if key in seen:
                    dup += 1
                    continue
                seen.add(key)
                from_all[i] += 1
                if ticks.start <= tick < ticks.stop:
                    from_window[i] += 1
                    late += times[d64] > late_after
    out["payloads_missing"] = [missing, 0]
    out["payload_digest_wrong"] = [digest_bad, 0]
    out["tx_not_as_sent"] = [tx_bad, 0]
    out["tx_committed_twice"] = [dup, 0]
    src["late_past_drain_s"] = late
    return from_window, from_all, sent_txs, checked


def _attempted_failed(src, from_window, from_all, sent_txs) -> None:
    """`attempted` and `failed`, and what the front ports shed.

    A transaction of the window that its node had not committed when the run
    ended is out. The front port of a node evicts the oldest queued
    transaction when its queue is full (admission control, drop-oldest) and
    counts each in `mempool.front_dropped`. Per node, as many of the
    transactions that are out as that counter explains were shed at the door
    and never admitted; any beyond it were admitted and lost, and those are
    charged to the window first. Where the traffic file says `"attempted":
    "admitted"` (a flood, which sheds by design), what was shed is not
    attempted; else it is attempted and failed. What was admitted and lost
    has failed in every cell."""
    n = len(from_window)
    due = [0] * n
    for r in arith.window_records(src):
        due[r[0]] += r[4]
    shed = lost = 0
    for i, dropped in enumerate(arith.front_dropped(src)):
        out_window = max(0, due[i] - from_window[i])
        out_all = max(0, sent_txs[i] - from_all[i])
        lost_i = min(out_window, max(0, out_all - dropped))
        lost += lost_i
        shed += out_window - lost_i
    src["shed_at_front"] = shed
    if src["traffic"].get("attempted") == "admitted":
        src["attempted"], src["failed"] = sum(due) - shed, lost
    else:
        src["attempted"], src["failed"] = sum(due), shed + lost


def _verify_plane(src, out) -> None:
    probe = src["probe"]
    unanswered = sum(1 for a in probe["answers"] if a is None)
    wrong = bad = lanes = 0
    for (msgs, pks, sigs), got in zip(probe["corpus"], probe["answers"]):
        if got is None:
            continue
        want = [ref.verify_strict(m, k, s) for m, k, s in zip(msgs, pks, sigs)]
        lanes += len(want)
        bad += want.count(False)
        wrong += sum(1 for a, b in zip(got, want) if a != b) + abs(len(got) - len(want))
    out["probe_unanswered"] = [unanswered, 0]
    out["probe_lanes_wrong"] = [wrong, 0]
    out["probe_has_no_bad_lane"] = [0 if bad or not lanes else 1, 0]
    src["probe_lanes"] = {"checked": lanes, "reference_rejects": bad}
    out["workload_batches_rejected"] = [sum(n["verify_failed"] for n in src["nodes"]), 0]
    fallbacks = 0
    for node in src["nodes"]:
        if node["snapshots"]:
            fallbacks += node["snapshots"][-1][1]["counters"].get(
                "crypto.remote_fallback_batches", 0
            )
    out["node_cpu_fallbacks"] = [fallbacks, 0]
    dev = arith.backend_delta(src, "tpu_sigs")
    out["device_checked_nothing"] = [0 if dev else 1, 0]


def judge(src, work: str) -> dict:
    """name -> [number, limit]; adds `attempted`, `failed` to src."""
    live = arith.live_nodes(src["config"])
    if len(src["nodes"]) != live:
        raise ValueError(f"{len(src['nodes'])} node logs for {live} live nodes")
    out: dict = {}
    _consensus(src, out)
    from_window, from_all, sent_txs, checked = _mempool(src, work, out)
    _verify_plane(src, out)
    _attempted_failed(src, from_window, from_all, sent_txs)
    src["tx_checked"] = checked
    return out


def correct(compared: dict) -> bool:
    return all(v <= limit for v, limit in compared.values())
