"""Of the signatures the nodes' `RemoteBackend`s checked in the window, the
share that stayed on the nodes' own CPUs because its batch was under the
64-signature crossover (`crypto.remote_cpu_sigs` over it plus
`crypto.remote_sigs`, all nodes, between the two METRICS snapshots that
bracket the window): coalesced workload groups of under 64, and every
payload's one signature and every QC's votes, which never go over the wire.
A program older than the counter (the parent of PR 33) reads 0, and that 0
means ABSENT, not "none on the CPU": `collect.counter_delta` gives a counter
that no snapshot holds as 0, and `run.py` prints no result line where a due
metric reads None, so a parent's traced run has to read a number; the same
program with the counter reads about 17 % in `fork-n4-fablocal.flood`. So the
parent-against-change row of the PR that brought the counter says nothing.
`better` is `higher` because the one cell where the share is material reads it
so (its nodes' CPUs verify twice what the wire does, `PERF.md` section 4); in
the other cells it is under 1 % and the direction means nothing. None where
the snapshots do not bracket the window or the nodes checked no signature in
it."""
from chipbench import collect


def read(src):
    w = src["window"]
    cpu = wire = 0
    for node in src["nodes"]:
        c = collect.counter_delta(node["snapshots"], w["t0"], w["t1"], "crypto.remote_cpu_sigs")
        s = collect.counter_delta(node["snapshots"], w["t0"], w["t1"], "crypto.remote_sigs")
        if c is None or s is None:
            return None
        cpu += c
        wire += s
    return 100.0 * cpu / (cpu + wire) if cpu + wire else None
