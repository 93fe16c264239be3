"""CLI driver for the local benchmark (Fabric-free `fab local`).

    python -m benchmark.run_local --nodes 4 --rate 1000 --size 512 \
        --duration 20 [--faults 0] [--crypto cpu|tpu]

The served run of chip_smoke.py, as a command:

    python -m benchmark.run_local --nodes 4 --rate 4000 --size 512 \
        --crypto tpu --benchmark-workload --mempool-payload-size 100000 \
        --min-block-delay 100 --duration 20
"""

from __future__ import annotations

import argparse

from .fabfile import LOCAL_NODE_PARAMS
from .local import LocalBench


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--nodes", type=int, default=4)
    p.add_argument("--rate", type=int, default=1_000)
    p.add_argument("--size", type=int, default=512)
    p.add_argument("--faults", type=int, default=0)
    p.add_argument("--duration", type=int, default=20)
    p.add_argument("--crypto", default="cpu", choices=["cpu", "tpu"])
    p.add_argument("--benchmark-workload", action="store_true",
                   help="enable the fork's synthetic batch-verification workload")
    p.add_argument("--mempool-payload-size", type=int, default=None,
                   help="override mempool max_payload_size (bytes); bigger "
                   "payloads = bigger verification batches (reference remote "
                   "config uses 500 kB, fabfile.py:107-120)")
    p.add_argument("--timeout-delay", type=int, default=None,
                   help="override consensus timeout_delay (ms)")
    p.add_argument("--min-block-delay", type=int, default=None,
                   help="override consensus AND mempool min_block_delay (ms). "
                   "The local default 0 lets a fast host seal payloads of a "
                   "handful of transactions, so no batch reaches the "
                   "64-signature crossover and --crypto tpu leaves the chip "
                   "idle; 100 is the node's own default and upstream's "
                   "remote setting (what chip_smoke.py runs)")
    p.add_argument("--sidecar-chunk", type=int, default=None,
                   help="TPU sidecar upload-pipeline chunk size (the device "
                   "chunk sweep's verdict); only with --crypto tpu")
    p.add_argument("--debug", action="store_true")
    args = p.parse_args()
    if args.sidecar_chunk is not None and args.crypto != "tpu":
        p.error("--sidecar-chunk requires --crypto tpu")
    if args.sidecar_chunk is not None and args.sidecar_chunk <= 0:
        # mirror the sidecar CLI's own check; failing here beats a
        # mid-benchmark "sidecar exited at startup"
        p.error("--sidecar-chunk must be positive")

    bench_params = {
        "nodes": args.nodes,
        "rate": args.rate,
        "tx_size": args.size,
        "faults": args.faults,
        "duration": args.duration,
        "crypto": args.crypto,
        "sidecar_chunk": args.sidecar_chunk,
    }
    node_params = {k: dict(v) for k, v in LOCAL_NODE_PARAMS.items()}
    if args.benchmark_workload:
        node_params["mempool"]["benchmark_mode"] = True
    if args.mempool_payload_size is not None:
        node_params["mempool"]["max_payload_size"] = args.mempool_payload_size
    if args.min_block_delay is not None:
        node_params["consensus"]["min_block_delay"] = args.min_block_delay
        node_params["mempool"]["min_block_delay"] = args.min_block_delay
    if args.timeout_delay is not None:
        node_params["consensus"]["timeout_delay"] = args.timeout_delay
    parser = LocalBench(bench_params, node_params).run(debug=args.debug)
    print(parser.result())


if __name__ == "__main__":
    main()
