"""The `node` binary (reference node/src/main.rs:16-92).

Subcommands:
  * keys --filename F                      -- generate a keypair file
  * run --keys K --committee C --store S [--parameters P] [--crypto cpu|tpu]
  * deploy --nodes N                       -- in-process local testbed on
    ports 7000/7100/7200 (node/src/main.rs:94-153)

The --crypto flag selects the CryptoBackend (the BASELINE `fab ...
--crypto=...` requirement): `cpu` (OpenSSL ed25519 baseline) or `tpu`
(vmapped JAX batch verification).
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import sys

from ..utils.logging import setup_logging


def _cmd_keys(args) -> None:
    from .config import Secret

    Secret.new().write(args.filename)
    print(f"Wrote keypair to {args.filename}")


async def _run_node(args) -> None:
    from ..utils import metrics
    from ..utils.actors import spawn
    from .node import Node

    # how much of one core this process's event loop uses (runtime.loop_cpu_s)
    spawn(metrics.meter_loop_cpu(), name="loop-cpu-meter")
    backend = None
    if args.crypto != "cpu":
        from ..crypto.backend import make_backend, set_backend

        kwargs = {}
        if args.crypto == "remote":
            host, port = args.crypto_addr.rsplit(":", 1)
            kwargs["addr"] = (host, int(port))
            kwargs["crossover"] = args.crypto_crossover
        if args.crypto == "tpu" and args.crypto_sharded:
            # Multi-chip: shard verification batches over every attached
            # device. Committee registration below pushes one replicated
            # table copy per chip (parallel/mesh.py).
            kwargs["sharded"] = True
        backend = make_backend(args.crypto, **kwargs)
        set_backend(backend)  # returns the PREVIOUS backend — don't chain
        if not args.no_warmup:
            # Compile every device bucket BEFORE the pacemaker can arm:
            # lazy first-dispatch compilation (tens of seconds) otherwise
            # stalls early rounds past timeout_delay (see
            # TpuBackend.warmup). Runs before boot(), so nothing is stalled.
            from ..crypto.remote import warmup_backend

            warmup_backend(backend)
    node = Node(args.committee, args.keys, args.store, args.parameters)
    if args.ingress:
        # CLI override on top of the parameters file: boot the
        # authenticated client ingress (front port + ingress_port_offset).
        node.parameters.mempool.ingress_enabled = True
    # Committee registration at startup: validator keys become device-
    # resident verification precompute (decompression + window tables paid
    # once, not per batch), with the committee kernel compiled before the
    # node joins consensus. boot() re-asserts the registration (a no-op
    # for an unchanged key set); re-run node.register_committee on epoch
    # reconfiguration — a changed key set rebuilds the table.
    if backend is not None:
        node.register_committee(warmup=not args.no_warmup)
    node.boot()
    if args.telemetry_port is not None:
        # Live telemetry plane + framed-JSON scrape endpoint
        # (utils/telemetry.py): periodic delta snapshots over the metrics
        # registry, per-lane SLO burn evaluation against the node's
        # LaneStats, and the device-occupancy timeline summary — polled
        # by tools/telemetry_dash.py. The watchdog attach means every
        # --trace-out auto-dump embeds the last K snapshots.
        import os as _os

        from ..network import net as _net
        from ..ops import timeline
        from ..utils import telemetry
        from ..utils.actors import spawn

        plane = telemetry.TelemetryPlane(
            label=_os.path.splitext(_os.path.basename(args.keys))[0],
            lane_stats=node.verification_service.lane_stats,
            timeline_fn=timeline.summary,
            # Per-peer link/RTT ledger (network observatory): a process
            # has one node label, so the default-vantage snapshot is
            # exactly this node's directed links.
            peers_fn=_net.peer_snapshot,
        )
        plane.attach_watchdog()
        server = telemetry.TelemetryServer(
            ("0.0.0.0", args.telemetry_port), plane
        )
        server.launch()
        spawn(plane.run(), name="telemetry-plane")
    await node.analyze_block()


async def _deploy_testbed(args) -> None:
    """In-process local testbed (node/src/main.rs:94-153): N nodes on
    localhost ports consensus 7000+i, mempool 7100+i, front 7200+i."""
    import random

    from ..consensus.config import Committee as CCommittee
    from ..consensus.config import Parameters as CParameters
    from ..crypto import SignatureService, generate_keypair
    from ..mempool.config import MempoolCommittee, MempoolParameters
    from ..mempool import Mempool
    from ..consensus import Consensus
    from ..store import Store
    from ..utils.actors import channel, spawn

    n = args.nodes
    rng = random.Random(0)
    keys = [generate_keypair(rng) for _ in range(n)]
    consensus_committee = CCommittee.new(
        [(pk, 1, ("127.0.0.1", 7000 + i)) for i, (pk, _) in enumerate(keys)]
    )
    mempool_committee = MempoolCommittee.new(
        [
            (pk, ("127.0.0.1", 7200 + i), ("127.0.0.1", 7100 + i))
            for i, (pk, _) in enumerate(keys)
        ]
    )
    nodes = []
    for i, (pk, sk) in enumerate(keys):
        store = Store(f".db_{i}/log")
        sig = SignatureService(sk)
        cm_channel = channel()
        core_channel = channel()
        commit_channel = channel()
        Mempool.run(
            pk, mempool_committee, MempoolParameters(), store, sig, cm_channel, core_channel
        )
        Consensus.run(
            pk,
            consensus_committee,
            CParameters(),
            store,
            sig,
            cm_channel,
            commit_channel,
            core_channel=core_channel,
        )
        nodes.append(commit_channel)

    async def drain(ch):
        while True:
            await ch.get()

    await asyncio.gather(*(drain(c) for c in nodes))


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(prog="node", description=__doc__)
    parser.add_argument("-v", "--verbose", action="count", default=2)
    sub = parser.add_subparsers(dest="command", required=True)

    p_keys = sub.add_parser("keys", help="generate a keypair file")
    p_keys.add_argument("--filename", required=True)

    p_run = sub.add_parser("run", help="run a node")
    p_run.add_argument("--keys", required=True)
    p_run.add_argument("--committee", required=True)
    p_run.add_argument("--parameters", default=None)
    p_run.add_argument("--store", required=True)
    p_run.add_argument(
        "--crypto", default="cpu", choices=["cpu", "tpu", "remote"]
    )
    p_run.add_argument(
        "--crypto-addr",
        default="127.0.0.1:9700",
        help="sidecar address for --crypto remote (host:port)",
    )
    p_run.add_argument(
        "--crypto-crossover",
        type=int,
        default=64,
        help="batches below this size verify on the local CPU",
    )
    p_run.add_argument(
        "--crypto-sharded",
        action="store_true",
        help="with --crypto tpu: shard verification over every attached "
        "device (ShardedEd25519Verifier); committee registration then "
        "replicates the validator tables onto every chip",
    )
    p_run.add_argument(
        "--ingress",
        action="store_true",
        help="serve the authenticated client ingress (signed transactions, "
        "admission control with fee/priority lanes, retry-after "
        "backpressure) on front_port + mempool ingress_port_offset; "
        "equivalent to ingress_enabled in the mempool parameters",
    )
    p_run.add_argument(
        "--no-warmup",
        action="store_true",
        help="skip pre-compiling device kernels before joining consensus",
    )
    p_run.add_argument(
        "--telemetry-port",
        type=int,
        default=None,
        metavar="PORT",
        help="serve the live telemetry scrape endpoint (framed JSON "
        "request/response on the stack's 4-byte framing): periodic "
        "metric delta snapshots, SLO burn-rate alerts, lane queueing, "
        "and the device-occupancy timeline. Poll with "
        "tools/telemetry_dash.py --poll host:PORT",
    )
    p_run.add_argument(
        "--metrics-out",
        default=None,
        help="write the structured metrics dump (utils/metrics.py) to this "
        "path on exit/SIGTERM",
    )
    p_run.add_argument(
        "--trace-out",
        default=None,
        help="write the flight-recorder dump (utils/tracing.py) to this "
        "path on exit/SIGTERM; anomaly-watchdog dumps land next to it as "
        "<path>.watchdog-<reason>-<n>.json. HOTSTUFF_TRACE=0 disables "
        "recording, HOTSTUFF_TRACE_RING sizes the ring",
    )

    p_deploy = sub.add_parser("deploy", help="in-process local testbed")
    p_deploy.add_argument("--nodes", type=int, required=True)

    args = parser.parse_args(argv)
    if (
        args.command == "run"
        and args.crypto_sharded
        and args.crypto != "tpu"
    ):
        # A run that silently ignored the flag would record numbers under
        # a different config than the operator specified (same convention
        # as the sidecar's --multihost/--chunk guards).
        parser.error("--crypto-sharded requires --crypto tpu")
    setup_logging(args.verbose)

    # GIL switch interval: the saturated-node profile (data/profiles/)
    # shows ~5 ms stalls on every to_thread crypto dispatch — the default
    # sys.setswitchinterval(0.005) convoy between the event loop and the
    # verification worker threads. A shorter interval cuts the handoff
    # latency on single-core hosts.
    import os

    try:
        sys.setswitchinterval(
            float(os.environ.get("HOTSTUFF_SWITCH_INTERVAL", "0.001"))
        )
    except ValueError:
        logging.getLogger("hotstuff.node").warning(
            "ignoring malformed HOTSTUFF_SWITCH_INTERVAL"
        )

    # Exit-time flushers, shared by the profiler and --metrics-out: the
    # benchmark harness stops nodes with SIGTERM, which skips atexit by
    # default, so both hooks ride one SIGTERM handler + one atexit.
    flushers = []
    if args.command == "run":
        from ..utils import metrics

        # Periodic `METRICS {json}` snapshot line on hotstuff.metrics
        # (scraped by benchmark.logs.LogParser); <= 0 disables.
        metrics.start_periodic_emitter_from_env()
        # One last snapshot at exit: counters that matter for a run's
        # verdict (crypto.remote_fallback_batches among them) must not
        # stop at the last 5 s tick.
        flushers.append(metrics.emit_snapshot)
        if args.metrics_out:

            def _write_metrics():
                try:
                    metrics.write_json(args.metrics_out)
                except OSError as e:
                    logging.getLogger("hotstuff.metrics").warning(
                        "failed to write metrics dump: %r", e
                    )

            flushers.append(_write_metrics)
        if args.trace_out:
            from ..utils import tracing

            # Label this process's events with the keys-file stem so
            # multi-node dumps stitch with stable node names, and arm the
            # anomaly watchdog's auto-dump next to the exit dump.
            tracing.NODE_LABEL.set(os.path.splitext(
                os.path.basename(args.keys)
            )[0])
            tracing.WATCHDOG.set_auto_dump(args.trace_out)

            def _write_trace():
                try:
                    tracing.write_json(args.trace_out)
                except OSError as e:
                    logging.getLogger("hotstuff.tracing").warning(
                        "failed to write trace dump: %r", e
                    )

            flushers.append(_write_trace)

    # HOTSTUFF_PROFILE=<path>: run the node under cProfile and dump stats
    # to <path>.<pid> on SIGTERM/exit (SURVEY §5.5 observability; used by
    # the protocol-plane ceiling analysis in data/profiles/).
    if args.command == "run" and os.environ.get("HOTSTUFF_PROFILE"):
        import cProfile

        profile_path = f"{os.environ['HOTSTUFF_PROFILE']}.{os.getpid()}"
        profiler = cProfile.Profile()
        profiler.enable()

        def _dump_profile():
            profiler.disable()
            profiler.dump_stats(profile_path)

        flushers.append(_dump_profile)

    if args.command == "run":
        # Drain every live DispatchPipeline's workers on SIGTERM too —
        # the handler below exits via os._exit, which skips the
        # pipeline's own atexit hook (ops/pipeline.py close_all).
        from ..ops.pipeline import close_all as _drain_pipelines

        flushers.append(_drain_pipelines)

    if flushers:
        import atexit
        import signal

        def _flush_all():
            for flush in flushers:
                flush()

        def _on_term(*_a):
            _flush_all()
            os._exit(0)

        signal.signal(signal.SIGTERM, _on_term)
        atexit.register(_flush_all)

    if args.command == "keys":
        _cmd_keys(args)
    elif args.command == "run":
        asyncio.run(_run_node(args))
    elif args.command == "deploy":
        asyncio.run(_deploy_testbed(args))


if __name__ == "__main__":
    main()
