"""Share of others' payload copies that reached a live node in the window and
were not checked again (counter `mempool.payloads_duplicate`, `mempool/
core.py` `_handle_others_payload`: the digest was stored, or digest and
signature bytes matched a copy in acceptance) of those copies plus the ones
accepted (`mempool.payloads_other`), pooled over the live nodes as
`mempool.requeued_per_s` is. Every such copy was verified and charged its
workload batch a second time before. None where a node's snapshots do not
bracket the window or do not hold the counter (a program that accepts every
copy), or no copy arrived."""
from chipbench import collect, spans

NAME = "mempool.payloads_duplicate"


def read(src):
    w = src["window"]
    for node in src["nodes"]:
        _first, last = collect.bracket(node["snapshots"], w["t0"], w["t1"])
        if last is None or NAME not in last["counters"]:
            return None
    dup = spans.counter_rate(src, "nodes", NAME)
    other = spans.counter_rate(src, "nodes", "mempool.payloads_other")
    if dup is None or not dup + other:
        return None
    return 100.0 * dup / (dup + other)
