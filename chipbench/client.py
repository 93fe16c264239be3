"""The benchmark's load generator: open loop, one process, one connection
per node (copied from hotstuff_tpu/node/client.py and changed).

    python -m chipbench.client --targets h:p,h:p --rate 6000 --size 512
        --seed 7 --start 1760000000.0 --stop 1760000030.0 --out records.jsonl

Client c sends to target c. At tick k (due instant start + k * tick) it
writes the burst that the schedule in `traffic.py` makes due then. The first
transaction of a burst is its sample (kind 0, id = client << 40 | tick), the
others are kind 1 with tag = client << 40 | sequence; the bytes come from
`reference.make_tx`, so the reference can make them again.

What differs from the program's client: every tick is timed from its DUE
instant, which is fixed when the schedule starts, and lateness is never
forgiven: a late client sends at once and stays late until it has caught up.
Nothing is logged per transaction. One record per tick and client goes to
`--out` when the run ends: [client, tick, due, sent, n, first_seq], where
`sent` is the wall clock after the burst was handed to the socket (drained).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import struct
import sys
import time

from . import reference as ref
from . import traffic


async def _connect(host: str, port: int, deadline: float):
    while True:
        try:
            return await asyncio.open_connection(host, port)
        except OSError:
            if time.time() > deadline:
                raise
            await asyncio.sleep(0.1)


async def _one_client(c, target, rate_c, tick_s, size, seed8, start, stop, records):
    host, port = target
    _, writer = await _connect(host, port, start)
    k, seq = 0, 0
    while True:
        due = start + k * tick_s
        if due >= stop:
            break
        now = time.time()
        if now < due:
            await asyncio.sleep(due - now)
        n = traffic.burst(rate_c, tick_s, k)
        if n:
            parts = []
            for x in range(n):
                if x == 0:
                    tx = ref.make_tx(seed8, ref.SAMPLE, ref.sample_id(c, k), size)
                else:
                    tx = ref.make_tx(seed8, 1, ref.tx_tag(c, seq + x), size)
                parts.append(struct.pack(">I", size))
                parts.append(tx)
            writer.write(b"".join(parts))
            await writer.drain()
        records.append((c, k, due, time.time(), n, seq))
        seq += n
        k += 1
    writer.close()


async def run(args) -> list:
    targets = []
    for t in args.targets.split(","):
        host, port = t.rsplit(":", 1)
        targets.append((host, int(port)))
    rate_c = args.rate / len(targets)
    seed8 = ref.seed_bytes(args.seed)
    records: list = []
    await asyncio.gather(
        *(
            _one_client(
                c, t, rate_c, args.tick_ms / 1000.0, args.size, seed8,
                args.start, args.stop, records,
            )
            for c, t in enumerate(targets)
        )
    )
    return records


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--targets", required=True)
    ap.add_argument("--rate", type=float, required=True, help="tx/s, all clients")
    ap.add_argument("--size", type=int, required=True)
    ap.add_argument("--tick-ms", type=float, default=50)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--start", type=float, required=True, help="epoch of tick 0")
    ap.add_argument("--stop", type=float, required=True, help="no tick due after")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    records = asyncio.run(run(args))
    with open(args.out + ".tmp", "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    os.replace(args.out + ".tmp", args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
