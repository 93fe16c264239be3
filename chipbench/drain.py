"""The wait after the window has closed: until every transaction the clients
sent is in a payload that its own node committed, a minute past the close if
need be. A transaction that commits late is late, and its latency counts the
wait; only one that never commits has failed. A fixed wait of some seconds
called the tail of a flood's backlog failed in one run and not in the next
(PERF.md, PR 25 s3).

Each node's log is followed from its start by two of its lines: `Payload X
contains N B` (an own payload, sealed from what its client sent) and
`Committed B.. -> X`. The wait ends when every node has committed as many
own transactions as its client sent; or when no node has committed another
for `quiet_s` (what is still out then was shed and will never come); or
`most_s` after the close. It never ends before `least_s` after the close.
"""

from __future__ import annotations

import re
import time

_OWN = re.compile(rb"\] Payload (\S+) contains (\d+) B\n")
_COMMIT = re.compile(rb"\] Committed B\d+\(\S+?\) -> (\S+)\n")


class Tail:
    """Own transactions committed so far, by one node's log."""

    def __init__(self, path: str, tx_size: int) -> None:
        self.path, self.tx_size = path, tx_size
        self.offset = 0
        self.own: dict[bytes, int] = {}  # digest -> transactions, not yet committed
        self.committed_tx = 0

    def poll(self) -> int:
        try:
            with open(self.path, "rb") as f:
                f.seek(self.offset)
                data = f.read()
        except OSError:
            return self.committed_tx
        cut = data.rfind(b"\n") + 1
        data = data[:cut]
        self.offset += cut
        for m in _OWN.finditer(data):
            self.own.setdefault(m.group(1), int(m.group(2)) // self.tx_size)
        for m in _COMMIT.finditer(data):
            # a payload's first commit counts; `pop` forgets it after that
            self.committed_tx += self.own.pop(m.group(1), 0)
        return self.committed_tx


def sent_by_client(records, n: int) -> list[int]:
    sent = [0] * n
    for c, _k, _due, _sent, cnt, seq in records:
        sent[c] = max(sent[c], seq + cnt)
    return sent


def wait_committed(logs, tx_size: int, sent, t1: float, least_s: float,
                   quiet_s: float, most_s: float, sleep=time.sleep, clock=time.time) -> dict:
    """Returns what the wait saw: seconds past the close, why it ended, and
    the transactions still out per node."""
    tails = [Tail(p, tx_size) for p in logs]
    last_counts, last_change = None, clock()
    while True:
        counts = [t.poll() for t in tails]
        now = clock()
        if counts != last_counts:
            last_counts, last_change = counts, now
        out = [max(0, s - c) for s, c in zip(sent, counts)]
        why = None
        if not any(out):
            why = "all_committed"
        elif now - last_change >= quiet_s:
            why = "quiet"
        elif now >= t1 + most_s:
            why = "most_s"
        if why and now >= t1 + least_s:
            return {"past_close_s": now - t1, "why": why, "still_out": out}
        sleep(0.25)
