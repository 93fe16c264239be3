"""Run one cell of BENCHMARK.json once and print the result as the last line.

    python3 -m chipbench.run --workload fork-n4.flood --seed 7 --seconds 20 --trace 0

Everything is found by name: the cell in BENCHMARK.json `workloads`, its
configuration in `chipbench/configs/<config>.json`, its traffic in
`chipbench/traffic/<traffic>.json`, every metric's reader in
`chipbench/end_to_end/<metric>.py` or `chipbench/layer_metrics/<metric>.py`.
This process never imports JAX: the sidecar it starts is the one process on
the chip. A run that finds no TPU fails, unless JAX_PLATFORMS names `cpu`
(a rehearsal, and the result then names `cpu` as its device).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import time

T_PROCESS_START = time.time()

from . import arith, collect, drain, judge, launch, probe, trace_reduce  # noqa: E402
from . import reference as ref  # noqa: E402
from .traffic import Traffic  # noqa: E402

ROOT = launch.ROOT
HERE = os.path.dirname(os.path.abspath(__file__))
# Short, because one run of the verify program is some 60,000 device ops and
# the profiler takes minutes to write a second and a half of them (212 s on
# the v5e; 0.6 s of a busy device took 140 s, PR 25). The trace is the
# window's last stretch: stopping it stalls the sidecar while the profile is
# written, and that stall then falls into the drain, not into the window the
# per-layer counts are taken over.
TRACE_SECONDS = 0.4
TRACE_BEFORE_CLOSE_S = 0.9


def rehearsal() -> bool:
    """True only where the process was told to use the CPU and nothing else
    first (`JAX_PLATFORMS=cpu`); `tpu,cpu` is no such order."""
    return os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu"


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_benchmark(path: str | None = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


READERS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def load_reader(kind: str, name: str):
    path = os.path.join(HERE, READERS[kind], name + ".py")
    spec = importlib.util.spec_from_file_location(f"chipbench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_for(bench: dict, cell: str, kind: str) -> list[dict]:
    """The metrics of `kind` that this cell reports: those that list it
    under `workloads`; an end-to-end metric without the key, in every cell; a
    per-layer metric without it, in every cell that reports the end-to-end
    metric it `moves`. So a new cell edits no entry that is there."""
    e2e = {m["name"] for m in bench["end_to_end"] if cell in m.get("workloads", [cell])}
    return [
        m for m in bench[kind]
        if (cell in m["workloads"] if "workloads" in m else m.get("moves", m["name"]) in e2e)
    ]


def read_metrics(bench, cell, kind, src) -> dict:
    out = {}
    for m in metrics_for(bench, cell, kind):
        value = load_reader(kind, m["name"])(src)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def tidy_log(path: str, keep: bool) -> None:
    """A node's log is tens of MB a run: gzip it where asked for (or where
    the run failed), else drop it. The sidecar's stays as it is."""
    import gzip

    try:
        if keep:
            with open(path, "rb") as f, gzip.open(path + ".gz", "wb", compresslevel=3) as g:
                shutil.copyfileobj(f, g)
        os.remove(path)
    except OSError:
        pass


def reduce_trace(dep, work: str) -> None:
    """The trace to `trace.reduced.json`, in a process of its own held to
    the CPU (the sidecar has let go of the chip by now); the raw trace, tens
    of MB, is deleted."""
    rc = subprocess.run(
        [sys.executable, "-m", "chipbench.trace_reduce",
         os.path.join(work, "trace"), os.path.join(work, "trace.reduced.json"),
         "--summary", os.path.join(work, "trace.summary.txt")],
        env=dict(dep.env, JAX_PLATFORMS="cpu"), cwd=ROOT,
        stdout=subprocess.DEVNULL, stderr=sys.stderr,
    ).returncode
    shutil.rmtree(os.path.join(work, "trace"), ignore_errors=True)
    if rc != 0:
        raise launch.LaunchError("the trace could not be reduced")


def window_run(dep, cfg, tr: Traffic, seed: int, seconds: float, trace: bool,
               tag: str = "client") -> dict:
    """Ramp, window, drain on a deployment that is up. Returns the sources
    that belong to this window (records, window, probe)."""
    start = time.time() + 1.5  # the client connects before tick 0
    t0 = start + tr.ramp_s
    t1 = t0 + seconds
    records_path = os.path.join(dep.work, f"{tag}.records.jsonl")
    n_probe = max(1, int((seconds - 1.0) // tr.probe_every_s) + (1 if seconds >= 1 else 0))
    corpus = ref.probe_corpus(seed, n_probe, tr.probe_sigs, tr.probe_bad_every)
    client = dep.start_client(tr.rate, tr.tick_s * 1000.0, seed, start, t1 + 0.25,
                              records_path, name=tag)
    pr = probe.Probe(("127.0.0.1", launch.SIDECAR_PORT), corpus, t0, tr.probe_every_s)
    pr.start()
    if trace:
        time.sleep(max(0.0, max(t0 + 0.5, t1 - TRACE_BEFORE_CLOSE_S) - time.time()))
        dep.ask("trace.start")
    time.sleep(max(0.0, t1 + 0.25 - time.time()))
    try:
        client.wait(timeout=30)
    except subprocess.TimeoutExpired:
        raise launch.LaunchError("the load generator did not end:\n" + launch.tail(dep.log(tag)))
    if client.returncode != 0:
        raise launch.LaunchError("the load generator failed:\n" + launch.tail(dep.log(tag)))
    # answers due in the window are waited for, a minute if need be: the
    # probe's, and every transaction's commit (late is late, not failed)
    pr.join(timeout=60)
    records = collect.read_records(records_path)
    waited = drain.wait_committed(
        [dep.log(f"node-{i}") for i in range(dep.live)], cfg["tx_size"],
        drain.sent_by_client(records, dep.live), t1, tr.drain_s, tr.drain_quiet_s,
        tr.drain_most_s,
    )
    say(f"chipbench: drain {json.dumps(waited)}")
    end = time.time()
    return {
        "seed": seed,
        "config": cfg,
        "traffic": {"name": tr.name, "rate": tr.rate, "tick_s": tr.tick_s,
                    "drain_s": tr.drain_s, "attempted": tr.attempted},
        "window": {"start": start, "t0": t0, "t1": t1, "seconds": seconds, "end": end},
        "drain": waited,
        "records": records,
        "committee_pubs": dep.committee.pubs,
        "probe": {"corpus": corpus, "answers": pr.answers, "seconds": pr.seconds,
                  "errors": pr.errors},
        "probe_sigs_in_window": sum(
            len(c[0]) for c, a in zip(corpus, pr.answers) if a is not None
        ),
    }


def printable(metrics: dict, missing: list, traced: bool) -> bool:
    """Whether a result line is printed. A per-layer metric whose reader
    finds nothing to read is left out of the line; an end-to-end metric
    without a reading, or a line without any metric, prints no line."""
    return bool(metrics) and (traced or not missing)


def build_result(bench, cell, traced: bool, src, device, took, compared):
    """(the result line's object, the names of metrics this cell should
    report and no reader could read)."""
    kind = "per_layer" if traced else "end_to_end"
    metrics = read_metrics(bench, cell["name"], kind, src)
    missing = [m["name"] for m in metrics_for(bench, cell["name"], kind)
               if m["name"] not in metrics]
    dev_out = {k: device[k] for k in ("platform", "kind", "count", "memory_peak_bytes")}
    result = {
        "correct": judge.correct(compared),
        "attempted": src["attempted"],
        "failed": src["failed"],
        "metrics": metrics,
        "device": dev_out,
    }
    stretch = None
    if traced and src.get("trace"):
        t = src["trace"]
        dev_out["busy_s"] = t.get("busy_s", 0.0)
        dev_out["window_s"] = t.get("window_s", 0.0)
        # window_s is the longer of the two (`trace_reduce.stretch_seconds`)
        stretch = {"clocked_s": src["trace_done"]["window_s"], "span_s": t.get("span_s")}
        result["breakdown"] = {
            "device_ops": t.get("device_ops", []),
            "idle_gaps": t.get("idle_gaps", []),
        }
    result["notes"] = {
        "traced_stretch": stretch,
        "cpus": os.cpu_count(),
        "boot_s": took,
        "tx_checked": src["tx_checked"],
        "probe_lanes": src["probe_lanes"],
        "samples": len(arith.window_records(src)),
        "drain": src["drain"],
        "late_past_drain_s": src["late_past_drain_s"],
        "front_dropped": sum(arith.front_dropped(src)),
        "shed_at_front": src["shed_at_front"],
        "maker_shed": sum(n["maker_shed"] for n in src["nodes"]),
        "committed_tx": arith.committed_tx_in_window(src),
        "verified_shares": arith.verified_shares(src),
        "verified_share": arith.verified_share(src),
        "timeouts_in_window": sum(
            1 for n in src["nodes"] for t, _r in n["timeouts"]
            if src["window"]["t0"] <= t < src["window"]["t1"]
        ),
        "outages_s": sorted(round(b - a, 3) for a, b, _t in arith.outages(src)),
    }
    result["compared"] = compared  # last, beside the limits
    return result, missing


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    ap.add_argument("--fault", help=argparse.SUPPRESS)
    ap.add_argument("--bench-file", help=argparse.SUPPRESS)  # rehearsals and tests
    ap.add_argument("--keep-logs", action="store_true", help="keep the nodes' logs (gzipped)")
    ap.add_argument("--out", help="run directory (default chiprun_out/chipbench/<cell>)")
    args = ap.parse_args(argv)

    bench = load_benchmark(args.bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        say(f"no cell {args.workload!r} in BENCHMARK.json; cells: {sorted(cells)}")
        return 2
    cell = cells[args.workload]
    cfg = load_config(cell["config"])
    tr = Traffic.load(cell["traffic"])
    work = args.out or os.path.join(ROOT, "chiprun_out", "chipbench", cell["name"])
    work = os.path.abspath(work)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    say(
        f"chipbench: cell {cell['name']} seed {args.seed} seconds {args.seconds} "
        f"trace {args.trace} rate {tr.rate} tx/s nodes {cfg['nodes']} "
        f"faults {cfg.get('faults', 0)} cpus {os.cpu_count()} "
        f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}"
    )

    try:
        dep = launch.Deployment(work, cfg, args.seed, fault=args.fault)
    except launch.LaunchError as e:
        say(f"chipbench: FAILED: {e}")
        return 1
    code = 1
    try:
        dep.start(trace_seconds=TRACE_SECONDS if args.trace else None)
        took = dep.await_ready()
        say(f"chipbench: booted: sidecar {took['sidecar']:.1f} s, nodes "
            f"{max(v for k, v in took.items() if k != 'sidecar'):.1f} s")
        src = window_run(dep, cfg, tr, args.seed, args.seconds, bool(args.trace))
        src["setup_s"] = src["window"]["t0"] - T_PROCESS_START
        if args.trace:
            done = dep.reply("trace.done", timeout=240)
            if done is None or "error" in done:
                raise launch.LaunchError(
                    f"the sidecar wrote no trace ({done}):\n" + launch.tail(dep.log("sidecar"))
                )
            say(f"chipbench: trace of {done['window_s']:.2f} s written in {done['written_s']:.1f} s")
        dep.ask("device.ask")
        device = dep.reply("device.json", timeout=30)
        if device is None:
            raise launch.LaunchError("the sidecar did not name its device:\n"
                                     + launch.tail(dep.log("sidecar")))
        dep.stop()

        if device["platform"] != "tpu" and not rehearsal():
            raise launch.LaunchError(f"no TPU: the sidecar ran on {device}")
        if device["count"] < cell["chips"] and device["platform"] == "tpu":
            raise launch.LaunchError(f"cell needs {cell['chips']} chips, JAX has {device}")

        if args.trace:
            reduce_trace(dep, work)
        src.update(collect.gather(work, dep.live))
        if args.trace:
            tr = src.get("trace") or {}
            if device["platform"] == "tpu" and not tr.get("on_tpu"):
                raise launch.LaunchError(
                    f"the device is a TPU and the trace holds no TPU plane: {tr.get('planes')}")
            if not tr.get("busy_s"):
                raise launch.LaunchError(f"no operation ran on the device while traced: {tr}")
            tr["window_s"] = trace_reduce.stretch_seconds(src["trace_done"]["window_s"], tr)

        compared = judge.judge(src, work)
        result, missing = build_result(bench, cell, bool(args.trace), src, device, took, compared)
        for name, (value, limit) in compared.items():
            say(f"compared {name}: {value} (limit {limit})")
        if missing:
            say(f"chipbench: no reading for {missing}")
        if printable(result["metrics"], missing, bool(args.trace)):
            print(json.dumps(result), flush=True)
            code = 0
        else:
            say("chipbench: no result")
            code = 3
    except launch.LaunchError as e:
        say(f"chipbench: FAILED: {e}")
        code = 1
    finally:
        dep.stop()
        shutil.rmtree(os.path.join(work, "trace"), ignore_errors=True)
        for i in range(dep.live):
            shutil.rmtree(os.path.join(work, f".db-{i}"), ignore_errors=True)
            tidy_log(dep.log(f"node-{i}"), keep=args.keep_logs or code != 0)
    return code


if __name__ == "__main__":
    sys.exit(main())
