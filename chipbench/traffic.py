"""The one general traffic generator's arithmetic, read from a traffic file.

A traffic mix is a JSON file of parameters under `chipbench/traffic/`:

    {"rate_tx_per_s": 6000,     offered load, whole committee, open loop
     "tick_ms": 50,             clients send one burst per tick
     "ramp_s": 5,               load before the window opens (not measured)
     "drain_s": 5,              least wait after the window closes for commits
     "drain_quiet_s": 10,       give up when nothing more has committed this long
     "drain_most_s": 60,        and at the latest this long after the close
     "attempted": "admitted",   a flood: what the front ports shed (drop-oldest,
                                `mempool.front_dropped`) is not attempted; without
                                the key it is attempted and failed
     "probe": {"every_s": 2, "sigs": 320, "bad_every": 5}}

The offer is split evenly over one client per LIVE node (a configuration's
`faults` never boot: `arith.live_nodes`). Every client sends, at
tick k (due instant start + k * tick), the transactions that bring its total
to floor(rate_c * k * tick): the same schedule for every seed. The seed
changes the transactions' bytes, the client that carries a remainder first,
and the probe corpus, never the sizes or the arrivals.

Shared by the load generator (`client.py`), the metric arithmetic and the
reference, so that all three read one schedule.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass, replace

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Traffic:
    name: str
    rate: float  # tx/s over the whole committee
    tick_s: float
    ramp_s: float
    drain_s: float
    drain_quiet_s: float
    drain_most_s: float
    attempted: str  # "offered" or "admitted"
    probe_every_s: float
    probe_sigs: int
    probe_bad_every: int

    @staticmethod
    def load(name: str) -> "Traffic":
        path = os.path.join(HERE, "traffic", name + ".json")
        with open(path) as f:
            obj = json.load(f)
        probe = obj.get("probe", {})
        return Traffic(
            name=name,
            rate=float(obj["rate_tx_per_s"]),
            tick_s=float(obj.get("tick_ms", 50)) / 1000.0,
            ramp_s=float(obj.get("ramp_s", 5)),
            drain_s=float(obj.get("drain_s", 5)),
            drain_quiet_s=float(obj.get("drain_quiet_s", 10)),
            drain_most_s=float(obj.get("drain_most_s", 60)),
            attempted=str(obj.get("attempted", "offered")),
            probe_every_s=float(probe.get("every_s", 2)),
            probe_sigs=int(probe.get("sigs", 320)),
            probe_bad_every=int(probe.get("bad_every", 5)),
        )

    def with_rate(self, rate: float) -> "Traffic":
        return replace(self, rate=float(rate))


def due_count(rate_c: float, tick_s: float, k: int) -> int:
    """Transactions of one client due by the END of tick k (ticks from 0)."""
    return int(rate_c * tick_s * (k + 1) + 1e-9)


def burst(rate_c: float, tick_s: float, k: int) -> int:
    """Transactions of one client due AT tick k."""
    before = due_count(rate_c, tick_s, k - 1) if k else 0
    return due_count(rate_c, tick_s, k) - before


def ticks_between(start: float, tick_s: float, t0: float, t1: float) -> range:
    """The ticks whose due instant lies in [t0, t1)."""
    first = max(0, math.ceil((t0 - start) / tick_s - 1e-9))
    last = math.ceil((t1 - start) / tick_s - 1e-9)
    return range(first, max(first, last))
