"""95th percentile of commit minus due over the window's samples that DID
commit. Above the knee the front ports shed by design, so the tail over all
samples is the drain time; this one says how old committed work was."""
from chipbench import arith


def read(src):
    v = arith.percentile(arith.committed_latencies(src), 0.95)
    return None if v is None else 1000.0 * v
