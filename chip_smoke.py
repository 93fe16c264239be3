#!/usr/bin/env python3
"""Quickest proof that tpu-hotstuff still starts on the chip.

    python chip_smoke.py              # one chip: verify plane, then served path
    python chip_smoke.py --chips 4    # only the sharded verifier on four chips
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny   # rehearsal; always FAILS

The parent process never imports JAX: a chip belongs to one process at a
time, so every phase that touches the device runs as a child, one after the
other, each the only holder of the chip while it lives. All of them share
the compile cache (`JAX_COMPILATION_CACHE_DIR` if set, else `.jax_cache/`
in the checkout), so the served phase's sidecar warms up from what the
verify phase compiled.

Phases (one chip):
  verify  `make_backend("tpu")` at the sidecar's own bucket/chunk, a 64-key
          committee registered on the device; 16,384 seeded signatures (a
          few hundred corrupted) through the generic program and 256
          quorum-43 QC batches through the committee program. Masks must
          equal CpuBackend's exactly, every signature must be accounted to
          the device, and the programs dispatched are named.
  served  `benchmark.local.LocalBench`: one sidecar on the chip, four
          nodes, four clients, 20 s. Every node commits, the nodes agree on
          their common prefix, end-to-end TPS > 0, and the sidecar's exit
          report says the DEVICE checked the signatures while no node fell
          back to its own CPU.
With --chips 4 (never given by the driver) one child shards the same seeded
batches over four devices and compares with one chip and with CpuBackend.

The last stdout line is one JSON object: {"ok": ..., "device": {...}} with
the device as the children's JAX reported it. Exit code 0 only when every
phase passed ON A TPU; a CPU run does the same work and then fails.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
REPORT_TAG = "SMOKE_REPORT "

# Deployment shapes (BASELINE.json / ISSUE 22): the sidecar dispatches ONE
# generic width; the committee is the original target's 64 validators.
WIDTH = 4096
VALIDATORS = 64
QUORUM = 43  # 2f+1 of 64
FULL = {"generic": 16_384, "qcs": 256, "width": WIDTH, "chunk": None, "rate": 4000, "duration": 20}
TINY = {"generic": 512, "qcs": 8, "width": 256, "chunk": 256, "rate": 4000, "duration": 10}


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Seeded data (child side: imports `cryptography`, never in the parent)


def _keys(n: int, seed: int):
    import random

    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

    rng = random.Random(seed)
    sks = [Ed25519PrivateKey.from_private_bytes(rng.randbytes(32)) for _ in range(n)]
    return sks, [sk.public_key().public_bytes_raw() for sk in sks]


def _off_curve_key() -> bytes:
    """A 32-byte y with no valid x (tests/test_ops_ed25519.py's scan)."""
    from hotstuff_tpu.ops import ed25519 as ed

    for cand in range(2, 50):
        u = (cand * cand - 1) % ed.P
        v = (ed.D_INT * cand * cand + 1) % ed.P
        x2 = u * pow(v, ed.P - 2, ed.P) % ed.P
        if pow(x2, (ed.P - 1) // 2, ed.P) == ed.P - 1:
            return cand.to_bytes(32, "little")
    raise AssertionError("no off-curve y below 50")


def _corrupt(i: int, kind: int, msgs, pks, sigs, rng) -> None:
    """The corruption kinds of tests/test_ops_ed25519.py, cycled."""
    from hotstuff_tpu.ops.ed25519 import L_ORDER

    sig = sigs[i]
    if kind == 0:  # bad s: one bit flipped
        s = bytearray(sig)
        s[32 + rng.randrange(31)] ^= 1 << rng.randrange(8)
        sigs[i] = bytes(s)
    elif kind == 1:  # bad R: another signature's R
        sigs[i] = sigs[i - 1][:32] + sig[32:]
    elif kind == 2:  # wrong key
        pks[i] = pks[i - 1] if pks[i - 1] != pks[i] else pks[i - 2]
    elif kind == 3:  # wrong message
        msgs[i] = rng.randbytes(32)
    elif kind == 4:  # non-canonical s: s + L verifies only under lax rules
        s = int.from_bytes(sig[32:], "little") + L_ORDER
        sigs[i] = sig[:32] + s.to_bytes(32, "little")
    elif kind == 5:  # key that is not a curve point
        pks[i] = _off_curve_key()
    else:  # null signature
        sigs[i] = bytes(64)


def generic_corpus(n: int, seed: int):
    """n signatures over DISTINCT 32-byte digests from 256 seeded client
    keys; every 53rd lane corrupted (309 of 16,384), kinds cycling."""
    import random

    rng = random.Random(seed)
    sks, pub = _keys(256, seed)
    msgs = [rng.randbytes(28) + i.to_bytes(4, "little") for i in range(n)]
    pks = [pub[i % 256] for i in range(n)]
    sigs = [sks[i % 256].sign(msgs[i]) for i in range(n)]
    bad = list(range(7, n, 53))
    for j, i in enumerate(bad):
        _corrupt(i, j % 7, msgs, pks, sigs, rng)
    return msgs, pks, sigs, len(bad)


def qc_corpus(n_qcs: int, seed: int):
    """QC-shaped batches: QUORUM distinct validators of the 64 sign one
    block digest. Every 8th QC carries one forged vote (bad s / bad R /
    wrong message — the keys stay registered, so the batch still rides the
    committee program and its rejection lanes)."""
    import random

    rng = random.Random(seed + 1)
    sks, pub = _keys(VALIDATORS, seed + 1)
    batches, forged = [], 0
    for q in range(n_qcs):
        digest = rng.randbytes(32)
        signers = rng.sample(range(VALIDATORS), QUORUM)
        msgs = [digest] * QUORUM
        pks = [pub[v] for v in signers]
        sigs = [sks[v].sign(digest) for v in signers]
        if q % 8 == 3:
            msgs = list(msgs)
            _corrupt(rng.randrange(1, QUORUM), (0, 1, 3)[q // 8 % 3], msgs, pks, sigs, rng)
            forged += 1
        batches.append((msgs, pks, sigs))
    return pub, batches, forged


def _wrap(pks, sigs):
    from hotstuff_tpu.crypto.primitives import PublicKey, Signature

    return [PublicKey(k) for k in pks], [Signature(s) for s in sigs]


def _device() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind, "count": len(d)}


def _staging() -> str:
    from hotstuff_tpu.crypto import native_staging

    return "native (C++)" if native_staging.get_lib() is not None else "Python"


def _report(phase: str, ok: bool, problems: list[str], **extra) -> int:
    for p in problems:
        say(f"[{phase}] FAIL: {p}")
    say(REPORT_TAG + json.dumps({"phase": phase, "ok": ok, "device": _device(), **extra}))
    return 0 if ok else 1


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Phase: verify plane at deployment width (child, one chip)


def phase_verify(size: dict, seed: int) -> int:
    from concurrent.futures import ThreadPoolExecutor

    from hotstuff_tpu.crypto.backend import CpuBackend, make_backend
    from hotstuff_tpu.utils import metrics

    dev = _device()
    say(f"[verify] device {dev}; staging {_staging()}")
    # Exactly the sidecar's construction (crypto/remote.py main): same
    # min_bucket and default chunk -> the same generic program, which the
    # served phase's sidecar then finds in the compile cache.
    backend = make_backend("tpu", min_bucket=size["width"], chunk=size["chunk"])
    say(f"[verify] compile cache: {backend.cache_dir}")
    names = backend.kernel_names
    say(f"[verify] programs: generic={names['generic']} committee={names['committee']}")

    t0 = time.perf_counter()
    msgs, pks, sigs, n_bad = generic_corpus(size["generic"], seed)
    committee, qcs, n_forged = qc_corpus(size["qcs"], seed)
    say(
        f"[verify] seeded corpus in {time.perf_counter() - t0:.1f} s: "
        f"{len(msgs)} generic sigs ({n_bad} corrupted), {len(qcs)} QCs x "
        f"{QUORUM} of {VALIDATORS} validators ({n_forged} with a forged vote)"
    )

    # Compile both programs side by side (XLA's compile is one thread per
    # program): the first dispatch of each IS its compile.
    with ThreadPoolExecutor(2) as pool:
        gen = pool.submit(_timed, backend.warmup)
        com = pool.submit(
            _timed, lambda: backend.register_committee(committee, warmup=True)
        )
        _, gen_s = gen.result()
        n_registered, com_s = com.result()
    say(
        f"[verify] smoke timing, compile + first dispatch (side by side): "
        f"{names['generic']}@{size['width']} {gen_s:.1f} s, "
        f"{names['committee']}@{size['width']} N={n_registered} {com_s:.1f} s"
    )
    table = backend._verifier.committee
    table_bytes = sum(
        a.nbytes for a in (table.ta_ypx, table.ta_ymx, table.ta_xy2d, table.valid, table.keys_u8)
    )
    where = sorted(table.ta_ypx.devices(), key=str)
    say(f"[verify] committee table resident: {table_bytes} B on {where}")

    cpu = CpuBackend()
    problems: list[str] = []
    keys, signatures = _wrap(pks, sigs)
    want, cpu_s = _timed(lambda: cpu.verify_batch_mask(msgs, keys, signatures))
    steady = []
    for _ in range(3):
        got, secs = _timed(lambda: backend.verify_batch_mask(msgs, keys, signatures))
        steady.append(secs)
        if got != want:
            diff = [i for i, (a, b) in enumerate(zip(got, want)) if a != b]
            problems.append(
                f"generic mask differs from CpuBackend on {len(diff)} lanes, "
                f"first {diff[:8]}"
            )
    n_rejected = want.count(False)
    if n_rejected != n_bad:
        problems.append(f"CpuBackend rejected {n_rejected} generic lanes, corpus corrupted {n_bad}")
    say(
        f"[verify] generic: {len(msgs)} sigs, {n_rejected} rejected, masks "
        f"{'EQUAL' if not problems else 'DIFFER'} to CpuBackend; smoke timing "
        f"steady {min(steady):.3f} s best of {[round(s, 3) for s in steady]} "
        f"(CpuBackend {cpu_s:.2f} s)"
    )

    wrapped = [(m, *_wrap(k, s)) for m, k, s in qcs]
    want_qc = [cpu.verify_batch_mask(m, k, s) for m, k, s in wrapped]
    qc_problems = 0
    t0 = time.perf_counter()
    for (m, k, s), w in zip(wrapped, want_qc):
        if backend.verify_batch_mask(m, k, s, committee=True) != w:
            qc_problems += 1
    qc_s = time.perf_counter() - t0
    if qc_problems:
        problems.append(f"{qc_problems} committee masks differ from CpuBackend")
    qc_rejected = sum(w.count(False) for w in want_qc)
    if qc_rejected != n_forged:
        problems.append(f"CpuBackend rejected {qc_rejected} votes, corpus forged {n_forged}")
    say(
        f"[verify] committee: {len(qcs)} QCs x {QUORUM} = {len(qcs) * QUORUM} sigs, "
        f"{qc_rejected} rejected, masks {'EQUAL' if not qc_problems else 'DIFFER'} "
        f"to CpuBackend; smoke timing steady {qc_s:.3f} s "
        f"({1000 * qc_s / len(qcs):.1f} ms per QC)"
    )

    rep = backend.report()
    counters = metrics.dump(include_buckets=False)["counters"]
    expect = 3 * len(msgs) + len(qcs) * QUORUM
    if rep["tpu_sigs"] != expect or rep["cpu_sigs"] != 0:
        problems.append(
            f"routing stats {rep['tpu_sigs']} device / {rep['cpu_sigs']} host "
            f"sigs, expected {expect} / 0"
        )
    if (
        counters["verifier.committee_sigs"] < len(qcs) * QUORUM
        or counters["verifier.committee_misses"]
    ):
        problems.append("committee batches did not all ride the committee program")
    if set(rep["dispatched"]) != set(names.values()):
        problems.append(f"unexpected programs dispatched: {rep['dispatched']}")
    if dev["platform"] == "tpu" and names["generic"] != "pallas_p128dh":
        problems.append(f"generic program on a TPU is {names['generic']}, not the Pallas one")
    say(f"[verify] backend report: {json.dumps(rep, sort_keys=True)}")
    backend.close()
    return _report(
        "verify",
        not problems,
        problems,
        compile_s={names["generic"]: round(gen_s, 1), names["committee"]: round(com_s, 1)},
        steady_s={"generic": round(min(steady), 4), "committee": round(qc_s, 4)},
    )


# ---------------------------------------------------------------------------
# Phase: four chips (child, --chips 4 only)


def phase_mesh(size: dict, seed: int) -> int:
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import numpy as np

    from hotstuff_tpu.crypto.backend import CpuBackend
    from hotstuff_tpu.ops import enable_persistent_cache
    from hotstuff_tpu.ops.ed25519 import Ed25519TpuVerifier
    from hotstuff_tpu.parallel.mesh import ShardedEd25519Verifier, default_mesh

    dev = _device()
    say(f"[mesh] device {dev}; staging {_staging()}; cache {enable_persistent_cache()}")
    if dev["count"] < 4:
        return _report("mesh", False, [f"need 4 devices, JAX has {dev['count']}"])
    kernel = "pallas" if dev["platform"] == "tpu" else "w4"
    kw = {"min_bucket": size["width"], "chunk": size["chunk"], "kernel": kernel}
    sharded = ShardedEd25519Verifier(mesh=default_mesh(4), **kw)
    single = Ed25519TpuVerifier(**kw)
    say(
        f"[mesh] sharded over {[str(d) for d in sharded.mesh.devices.flat]}: "
        f"bucket {sharded.min_bucket} (alignment {sharded.mesh_alignment}), chunk "
        f"{sharded.chunk}; one chip on {jax.devices()[0]}: bucket {single.min_bucket}"
    )

    msgs, pks, sigs, n_bad = generic_corpus(size["generic"], seed)
    committee, qcs, n_forged = qc_corpus(size["qcs"], seed)
    t_sh, t_one = sharded.set_committee(committee), single.set_committee(committee)

    # Placement: a sharded input must put a quarter on each chip and the
    # committee table a full replica on each — not everything on the first.
    wire = sharded._put(np.zeros((128, sharded.min_bucket), np.uint8))
    problems: list[str] = []
    for label, arr, want in (
        ("sharded (128, W) wire array", wire, wire.nbytes // 4),
        ("replicated committee table", t_sh.ta_ypx, t_sh.ta_ypx.nbytes),
    ):
        per_dev = {str(s.device): s.data.nbytes for s in arr.addressable_shards}
        say(f"[mesh] {label}: bytes per device {per_dev}")
        if len(per_dev) != 4 or set(per_dev.values()) != {want}:
            problems.append(f"{label} is not {want} B on each of 4 devices: {per_dev}")

    def first(v, committee_path: bool):
        n = v.min_bucket
        junk = [os.urandom(32)] * n, [os.urandom(64)] * n
        if committee_path:
            return _timed(lambda: v.verify_batch_mask_committee(junk[0], [0] * n, junk[1]))
        return _timed(lambda: v.verify_batch_mask(junk[0], [os.urandom(32)] * n, junk[1]))

    with ThreadPoolExecutor(4) as pool:
        jobs = {
            f"{name} {v.program_name(c, True)}": pool.submit(first, v, c)
            for name, v in (("sharded", sharded), ("one-chip", single))
            for c in (False, True)
        }
        compiled = {k: round(j.result()[1], 1) for k, j in jobs.items()}
    say(f"[mesh] smoke timing, compile + first dispatch (side by side): {compiled}")

    cpu = CpuBackend()
    keys, signatures = _wrap(pks, sigs)
    want = cpu.verify_batch_mask(msgs, keys, signatures)
    got_sh, sh_s = _timed(lambda: sharded.verify_batch_mask(msgs, pks, sigs).tolist())
    got_one, one_s = _timed(lambda: single.verify_batch_mask(msgs, pks, sigs).tolist())
    if got_sh != want:
        problems.append("sharded generic mask differs from CpuBackend")
    if got_one != want:
        problems.append("one-chip generic mask differs from CpuBackend")
    say(
        f"[mesh] generic: {len(msgs)} sigs ({n_bad} corrupted, {want.count(False)} rejected); "
        f"sharded {'EQUAL' if got_sh == want else 'DIFFERS'}, one-chip "
        f"{'EQUAL' if got_one == want else 'DIFFERS'} to CpuBackend; smoke timing "
        f"sharded {sh_s:.3f} s, one chip {one_s:.3f} s"
    )
    bad_qcs, sh_s, one_s = 0, 0.0, 0.0
    for m, k, s in qcs:
        w = cpu.verify_batch_mask(m, *_wrap(k, s))
        a, da = _timed(
            lambda: sharded.verify_batch_mask_committee(
                m, [t_sh.index[x] for x in k], s
            ).tolist()
        )
        b, db = _timed(
            lambda: single.verify_batch_mask_committee(
                m, [t_one.index[x] for x in k], s
            ).tolist()
        )
        sh_s, one_s = sh_s + da, one_s + db
        bad_qcs += a != w or b != w
    if bad_qcs:
        problems.append(f"{bad_qcs} committee QCs differ between sharded / one chip / CpuBackend")
    say(
        f"[mesh] committee: {len(qcs)} QCs x {QUORUM} ({n_forged} forged); sharded and "
        f"one-chip {'EQUAL' if not bad_qcs else 'DIFFER'} to CpuBackend; smoke timing "
        f"sharded {sh_s:.3f} s, one chip {one_s:.3f} s"
    )
    say(
        f"[mesh] dispatched: sharded {dict(sharded.dispatched)}, "
        f"one chip {dict(single.dispatched)}"
    )
    sharded.close()
    single.close()
    return _report("mesh", not problems, problems, compile_s=compiled)


# ---------------------------------------------------------------------------
# Phase: served path (runs in the PARENT: LocalBench imports no JAX; the
# sidecar it boots is the only process on the chip)


def phase_served(size: dict, out_dir: str) -> dict:
    work = os.path.join(out_dir, "served")
    os.makedirs(work, exist_ok=True)
    os.chdir(work)  # LocalBench keeps keys, stores and logs in the cwd
    from benchmark.fabfile import LOCAL_NODE_PARAMS
    from benchmark.local import BenchError, LocalBench

    # python -m benchmark.run_local --nodes 4 --rate 4000 --size 512
    #   --crypto tpu --benchmark-workload --mempool-payload-size 100000
    #   --min-block-delay 100 --duration 20
    node_params = {k: dict(v) for k, v in LOCAL_NODE_PARAMS.items()}
    node_params["mempool"]["benchmark_mode"] = True
    node_params["mempool"]["max_payload_size"] = 100_000
    # Block pacing: the node's own default and upstream's documented remote
    # setting (100 ms), not the local harness's 0. With 0 a fast host runs
    # >100 rounds/s, every payload is sealed on demand with a handful of
    # transactions, no batch reaches the 64-signature crossover and the
    # run commits with the chip idle (measured in this PR's rehearsal).
    node_params["consensus"]["min_block_delay"] = 100
    node_params["mempool"]["min_block_delay"] = 100
    bench = LocalBench(
        {
            "nodes": 4,
            "rate": size["rate"],
            "tx_size": 512,
            "faults": 0,
            "duration": size["duration"],
            "crypto": "tpu",
            "sidecar_min_bucket": size["width"],
            "sidecar_chunk": size["chunk"],
        },
        node_params,
    )
    problems: list[str] = []
    t0 = time.perf_counter()
    try:
        parser = bench.run()
    except BenchError as e:
        say(f"[served] FAIL: {e}")
        for name in ("sidecar.log", "node-0.log"):
            _tail(os.path.join(work, "logs", name))
        return {"phase": "served", "ok": False, "device": None}
    say(f"[served] LocalBench ran in {time.perf_counter() - t0:.1f} s")
    # the nodes' stores hold every payload (tens of MB each); the logs are
    # what the checks below read and what is worth bringing back
    for db in glob.glob(".db-*"):
        shutil.rmtree(db, ignore_errors=True)
    say(parser.result())

    with open(os.path.join("logs", "sidecar.log")) as f:
        sidecar_log = f.read()
    for pat in (
        r"(TpuBackend on .*)",
        r"(generic kernel warmup: .*)",
        r"(Crypto sidecar .* successfully booted.*)",
    ):
        m = re.search(pat, sidecar_log)
        say(f"[served] sidecar: {m.group(1) if m else 'no line matching ' + pat}")

    # every node committed, and the nodes agree on their common prefix
    chains = []
    for i in range(4):
        with open(os.path.join("logs", f"node-{i}.log")) as f:
            chains.append(re.findall(r"Committed B(\d+)\((\S+?)\)$", f.read(), re.M))
    say(f"[served] committed blocks per node: {[len(c) for c in chains]}")
    if not all(chains):
        problems.append("a node committed nothing")
    else:
        by_round = [dict(c) for c in chains]
        common = set.intersection(*(set(d) for d in by_round))
        forks = [r for r in common if len({d[r] for d in by_round}) != 1]
        say(
            f"[served] {len(common)} rounds committed by all four nodes, "
            f"{len(forks)} disagreements"
        )
        if forks or not common:
            problems.append(
                f"nodes disagree on rounds {sorted(forks, key=int)[:5]} "
                f"(common {len(common)})"
            )
    e_tps = parser.end_to_end_throughput()[0]
    if not e_tps > 0:
        problems.append(f"end-to-end TPS {e_tps}")

    # the sidecar's exit report: the DEVICE checked the signatures
    rep = ((parser.sidecar_metrics or {}).get("info") or {}).get("backend")
    say(f"[served] sidecar exit report: {json.dumps(rep, sort_keys=True)}")
    device = None
    if not rep:
        problems.append("sidecar log carries no METRICS exit report")
    else:
        device = {
            "platform": rep["platform"],
            "kind": rep["device_kind"],
            "count": rep["device_count"],
        }
        if not rep["tpu_sigs"] > 0:
            problems.append("sidecar routed no signature to the device")
        host_hash = {k: v for k, v in rep["dispatched"].items() if not k.endswith("dh")}
        if host_hash:
            problems.append(f"host-hash programs dispatched: {host_hash}")
    node_counters = parser.metrics["counters"]
    fallbacks = node_counters.get("crypto.remote_fallback_batches")
    say(
        f"[served] nodes: {node_counters.get('crypto.remote_sigs')} sigs in "
        f"{node_counters.get('crypto.remote_batches')} batches answered by the sidecar, "
        f"{fallbacks} above-crossover batches verified on a node's own CPU"
    )
    if fallbacks != 0 or not node_counters.get("crypto.remote_sigs"):
        problems.append("nodes fell back to their own CPU, or never reached the sidecar")
    for p in problems:
        say(f"[served] FAIL: {p}")
    return {"phase": "served", "ok": not problems, "device": device}


# ---------------------------------------------------------------------------
# Parent


def _tail(path: str, lines: int = 40) -> None:
    try:
        with open(path, errors="replace") as f:
            tail = f.readlines()[-lines:]
    except OSError as e:
        say(f"  (no {path}: {e})")
        return
    say(f"  --- last {len(tail)} lines of {path}")
    for line in tail:
        say("  | " + line.rstrip())


def run_child(phase: str, args, out_dir: str) -> dict:
    """One phase in its own process: stdout is relayed, stderr goes to
    <out>/<phase>.stderr.log and its tail is printed on failure."""
    err_path = os.path.join(out_dir, f"{phase}.stderr.log")
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase, "--seed", str(args.seed)]
    if args.tiny:
        cmd.append("--tiny")
    report = None
    with open(err_path, "w") as err:
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, stderr=err, text=True, cwd=ROOT)
        try:
            for line in proc.stdout:
                if line.startswith(REPORT_TAG):
                    report = json.loads(line[len(REPORT_TAG):])
                else:
                    say(line.rstrip())
            rc = proc.wait()
        finally:
            if proc.poll() is None:
                proc.kill()
    if rc != 0 or report is None or not report.get("ok"):
        say(f"[{phase}] child exited rc={rc}")
        _tail(err_path)
        return {"phase": phase, "ok": False, "device": (report or {}).get("device")}
    return report


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter
    )
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    ap.add_argument("--tiny", action="store_true", help="rehearsal sizes (CPU)")
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "smoke"))
    ap.add_argument("--phase", choices=["verify", "mesh"], help=argparse.SUPPRESS)
    args = ap.parse_args()
    size = TINY if args.tiny else FULL
    sys.path.insert(0, ROOT)

    if args.phase:  # child: owns the chip for its lifetime
        dev = _device()
        if dev["platform"] != "tpu" and not args.tiny:
            return _report(
                args.phase,
                False,
                [
                    f"JAX found no TPU ({dev}); the full sizes run only on "
                    "the chip (rehearse with --tiny)"
                ],
            )
        return {"verify": phase_verify, "mesh": phase_mesh}[args.phase](size, args.seed)

    os.makedirs(args.out, exist_ok=True)
    # children (and LocalBench's) import the repo from any cwd
    os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")
    say(
        f"chip_smoke: chips={args.chips} sizes={size} seed={args.seed} "
        f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r} "
        f"JAX_COMPILATION_CACHE_DIR={os.environ.get('JAX_COMPILATION_CACHE_DIR')!r}"
    )
    t0 = time.perf_counter()
    reports = []
    for phase in (["mesh"] if args.chips == 4 else ["verify", "served"]):
        t1 = time.perf_counter()
        if phase == "served":
            rep = phase_served(size, args.out)
        else:
            rep = run_child(phase, args, args.out)
        say(f"[{phase}] {'ok' if rep['ok'] else 'FAILED'} in {time.perf_counter() - t1:.1f} s")
        reports.append(rep)
        if not rep["ok"]:
            break
    devices = [r["device"] for r in reports if r.get("device")]
    device = devices[0] if devices else None
    ok = (
        all(r["ok"] for r in reports)
        and len(devices) == len(reports)
        and all(d == device for d in devices)
        and device["platform"] == "tpu"
        and device["count"] == args.chips
    )
    if devices and not ok and all(r["ok"] for r in reports):
        say(
            f"chip_smoke: every phase did its work, but on {devices} - "
            f"not {args.chips} TPU chip(s)"
        )
    say(f"chip_smoke: {time.perf_counter() - t0:.1f} s")
    if "jax" in sys.modules:  # a parent that touched JAX holds the chip
        say("chip_smoke: FAIL: the smoke's parent imported JAX")
        ok = False
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
