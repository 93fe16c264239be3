"""Decompose the end-to-end TPU verification pipeline into phases.

Resident-data kernel rate and end-to-end rate differ by the pipeline's
host legs; which leg dominates has to be measured, not asserted. This
profiler times each phase of `Ed25519TpuVerifier`'s packed
path in isolation and then the assembled pipeline, so the dominant term is
a number, not a guess:

  stage     C++ packed staging (prepare_batch_packed) per chunk
  upload    jax.device_put of the padded (128, W) u8 wire array
  dispatch  kernel call on a resident array (async issue cost)
  compute   device execution (dispatch + block on result)
  readback  device->host fetch of the (W,) bool mask
  e2e       the real verify_batch_mask loop

Usage:  python tools/profile_e2e.py [--batch 16384] [--chunk 4096]
Writes a human table to stdout; commit the output to data/profiles/.
"""

from __future__ import annotations

import argparse
import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))


def _t(fn, reps: int = 5) -> list[float]:
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        out.append(time.perf_counter() - t0)
    return out


def _fmt(name: str, times: list[float], n_items: int | None = None) -> str:
    med = statistics.median(times)
    rate = f"{n_items / med:>12,.0f}/s" if n_items else " " * 14
    return (
        f"{name:<28} med {med * 1e3:>8.2f} ms  min {min(times) * 1e3:>8.2f} ms"
        f"  {rate}"
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=16384)
    ap.add_argument("--chunk", type=int, default=4096)
    ap.add_argument("--kernel", default="pallas", choices=["w4", "pallas"])
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument(
        "--cpu", action="store_true", help="CPU smoke run (forces w4 kernel)"
    )
    ap.add_argument(
        "--mesh",
        type=int,
        nargs="?",
        const=0,
        default=None,
        metavar="N",
        help="add sharded phase rows over the first N attached devices "
        "(bare --mesh = all): generic sharded e2e plus the sharded "
        "committee path (replicated tables, 96 B + 4 B-index wire rows)",
    )
    ap.add_argument(
        "--pipeline",
        action="store_true",
        help="add serial-vs-pipelined A/B phase rows (ops/pipeline.py): "
        "the same e2e workload through DispatchPipeline depth=1 then "
        "depth=2, each with its own device occupancy / overlap headroom "
        "/ stall line",
    )
    ap.add_argument(
        "--metrics-out",
        default=None,
        help="write the in-process metrics dump (utils/metrics.py) here — "
        "the spans recorded by the e2e rows, committable next to the table",
    )
    ap.add_argument(
        "--timeline",
        default=None,
        metavar="OUT_JSON",
        help="write the device-occupancy timeline dump (ops/timeline.py) "
        "here: per-chunk stage/upload/dispatch/readback intervals plus "
        "occupancy / idle-gap / overlap-headroom summary. Feed it to "
        "tools/trace_report.py --chrome to see transfer/compute overlap "
        "as device rows in Perfetto",
    )
    args = ap.parse_args()

    import jax
    import numpy as np

    from hotstuff_tpu.ops import enable_persistent_cache
    from hotstuff_tpu.ops import ed25519 as ed

    enable_persistent_cache()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
        args.kernel = "w4"
    from __graft_entry__ import _signed_batch

    print(f"# devices: {jax.devices()}")
    msgs, pks, sigs = _signed_batch(args.batch)
    cm, ck, cs = msgs[: args.chunk], pks[: args.chunk], sigs[: args.chunk]
    n, c = args.batch, args.chunk

    verifier = ed.Ed25519TpuVerifier(
        max_bucket=8192, kernel=args.kernel, chunk=c
    )
    # Phase rows must time the SAME kernel the e2e row rides: 32-byte
    # messages auto-select the device-hash variant in verify_batch_mask.
    device_hash = all(len(m) == 32 for m in msgs)
    fn = verifier.programs[verifier.program_name(False, device_hash)]
    stage = (
        ed.prepare_batch_packed_dh if device_hash else ed.prepare_batch_packed
    )

    # warm: compile both widths, prime staging lib
    assert verifier.verify_batch_mask(msgs, pks, sigs).all()

    # --- phase timings -----------------------------------------------------
    rows = []

    staged = stage(cm, ck, cs)
    rows.append(
        _fmt(
            "stage (host-hash C++)",
            _t(lambda: ed.prepare_batch_packed(cm, ck, cs), args.reps),
            c,
        )
    )
    rows.append(
        _fmt(
            "stage (host-hash python)",
            _t(
                lambda: ed.prepare_batch_packed(cm, ck, cs, allow_native=False),
                2,
            ),
            c,
        )
    )
    rows.append(
        _fmt(
            "stage (device-hash, numpy)",
            _t(lambda: ed.prepare_batch_packed_dh(cm, ck, cs), args.reps),
            c,
        )
    )
    rows.append(f"{'  -> e2e rides':<28} {'device-hash' if device_hash else 'host-hash'} staging + kernel")

    padded = ed._pad(staged["packed"], verifier._bucket(c))

    def upload():
        jax.device_put(padded).block_until_ready()

    rows.append(_fmt(f"upload ({padded.nbytes} B)", _t(upload, args.reps), c))
    mb = padded.nbytes / 1e6
    up_med = statistics.median(_t(upload, args.reps))
    rows.append(f"{'  -> link bandwidth':<28} {mb / up_med:>8.1f} MB/s")

    dev = jax.device_put(padded)
    rows.append(_fmt("dispatch (async issue)", _t(lambda: fn(dev), 3), None))

    def compute():
        np.asarray(fn(dev))

    rows.append(_fmt("compute (resident)", _t(compute, args.reps), c))

    mask = fn(dev)
    rows.append(
        _fmt("readback ((W,) bool)", _t(lambda: np.asarray(mask), args.reps))
    )

    def e2e():
        verifier.verify_batch_mask(msgs, pks, sigs)

    rows.append(_fmt(f"e2e ({n} in {c}-chunks)", _t(e2e, args.reps), n))

    # --- committee-resident path -------------------------------------------
    # Keys registered once (device-resident window tables); lanes gather by
    # validator index — no per-batch decompression/table build, and the
    # wire row shrinks from 128 B to 96 B + 4 B index per signature.
    table = verifier.set_committee(sorted(set(pks)))
    idx = [table.index[k] for k in pks]
    cidx = idx[:c]
    cstage = (
        (lambda: ed.prepare_batch_committee_dh(cm, cidx, cs))
        if device_hash
        else (
            lambda: ed.prepare_batch_committee(
                cm, [table.keys[i] for i in cidx], cidx, cs
            )
        )
    )

    def committee_e2e():
        verifier.verify_batch_mask_committee(msgs, idx, sigs)

    committee_e2e()  # warm: compile the committee kernel widths
    rows.append(_fmt("stage (committee, numpy)", _t(cstage, args.reps), c))
    rows.append(
        _fmt(f"e2e (committee, {n} in {c}-chunks)", _t(committee_e2e, args.reps), n)
    )

    # --- sharded (mesh) path ------------------------------------------------
    # Batches shard over the dp axis; the committee tables ride as one
    # replicated copy per chip (pushed at set_committee), so the sharded
    # committee row should show the same zero-rebuild win as the
    # single-chip committee row, times the device count.
    if args.mesh is not None:
        from hotstuff_tpu.parallel.mesh import (
            ShardedEd25519Verifier,
            default_mesh,
        )

        sv = ShardedEd25519Verifier(
            mesh=default_mesh(args.mesh or None),
            max_bucket=8192,
            kernel=args.kernel,
            chunk=c,
        )

        def sharded_e2e():
            sv.verify_batch_mask(msgs, pks, sigs)

        sharded_e2e()  # warm: compile the sharded generic widths
        rows.append(
            _fmt(
                f"e2e (sharded, {sv._ndev} dev)", _t(sharded_e2e, args.reps), n
            )
        )

        stable = sv.set_committee(sorted(set(pks)))
        sidx = [stable.index[k] for k in pks]

        def sharded_committee_e2e():
            sv.verify_batch_mask_committee(msgs, sidx, sigs)

        sharded_committee_e2e()  # warm: compile the sharded committee widths
        rows.append(
            _fmt(
                f"e2e (sharded committee, {sv._ndev} dev)",
                _t(sharded_committee_e2e, args.reps),
                n,
            )
        )

    # --- dispatch pipeline A/B ----------------------------------------------
    # Serial (depth=1: stage/upload/dispatch/readback strictly in turn)
    # against the double-buffered window (depth=2: staging and readback
    # hidden under the neighbouring chunk's device phases). Each leg
    # resets the global device timeline so its occupancy / headroom /
    # stall numbers are its own.
    if args.pipeline:
        from hotstuff_tpu.ops import timeline as tl_mod

        for depth, label in ((1, "serial"), (2, "pipelined")):
            pv = ed.Ed25519TpuVerifier(
                max_bucket=8192, kernel=args.kernel, chunk=c,
                pipeline_depth=depth,
            )
            try:
                pv.verify_batch_mask(msgs, pks, sigs)  # warm the widths
                tl_mod.reset()
                times = _t(
                    lambda: pv.verify_batch_mask(msgs, pks, sigs), args.reps
                )
                leg = tl_mod.summary()
                rows.append(
                    _fmt(f"e2e ({label}, depth={depth})", times, n)
                )
                rows.append(
                    f"{'  -> leg occupancy':<28} "
                    f"{leg['occupancy'] * 100:>8.2f} %  "
                    f"headroom {leg['overlap_headroom'] * 100:.1f} %  "
                    f"stalls {pv.pipeline.stats['stalls']}"
                )
            finally:
                pv.close()

    per_chunk = n // c
    print(f"# batch={n} chunk={c} chunks={per_chunk} kernel={args.kernel}")
    for r in rows:
        print(r)

    # Device-occupancy attribution (ops/timeline.py): the pipeline-shape
    # numbers the phase medians above cannot give — how busy the device-
    # facing pipeline actually was, and how much of the upload cost a
    # double-buffered dispatch could hide (ROADMAP item 1's go/no-go).
    from hotstuff_tpu.ops import timeline

    tl = timeline.summary()
    print(
        f"# device occupancy {tl['occupancy'] * 100:.1f}%  "
        f"overlap headroom {tl['overlap_headroom'] * 100:.1f}%  "
        f"idle gaps {tl['idle']['count']} "
        f"(p50 {tl['idle']['p50_s'] * 1e3:.2f} ms, "
        f"max {tl['idle']['max_s'] * 1e3:.2f} ms)"
    )
    if args.timeline:
        timeline.write_json(args.timeline)
        print(f"# device timeline dump -> {args.timeline}")

    if args.metrics_out:
        from hotstuff_tpu.utils import metrics

        metrics.write_json(args.metrics_out)
        print(f"# metrics dump -> {args.metrics_out}")


if __name__ == "__main__":
    main()
