"""The payload digests a node may propose, and what became of each one that
left its queue (reference mempool/src/core.rs:50 `queue`, plus the commit
rule that upstream leaves out).

A digest leaves the queue when a block that holds it is proposed, or
received and verified, so that no other leader proposes it while that block
may still commit; it is then PENDING under the highest round of such a
block. Only a commit settles it: a committed digest is COMMITTED and never
queued again; a pending digest whose every block lies at or below the
committed round, and did not commit, is an orphan: with the 2-chain rule a
commit at round R commits exactly R's ancestors, so such a block never
commits. An orphan whose payload this node holds goes back to the FRONT of
the queue to be proposed again. One it never held is dropped: a block's
digests leave the queue when the block is verified, before its payloads are
fetched, and a digest that no node can serve (made up by a Byzantine leader,
or a payload whose maker crashed before gossiping it) would otherwise be
proposed by honest leaders for ever, each block of it timing out unverified.
Should its payload arrive after all, it is queued then.

Pure bookkeeping, no I/O: the mempool's core actor and the chaos plane's
payload mock share it.
"""

from __future__ import annotations

from typing import Iterable

from ..crypto import Digest
from .errors import QueueFullError, ensure


class PayloadQueue:
    def __init__(self, capacity: int) -> None:
        self.capacity = capacity
        # Undelivered digests, insertion-ordered: the front is proposed first.
        self.queue: dict[Digest, None] = {}
        # Digest -> highest round of a block holding it that was proposed,
        # verified or processed here.
        self.pending: dict[Digest, int] = {}
        # The pending digests whose payload this node holds: those that left
        # the queue, and those inserted while pending.
        self._stored: set[Digest] = set()
        # Digests that committed. A payload whose verification finishes after
        # its block committed must not be queued again, nor one re-made with
        # the same content. Bounded, insertion-ordered (evicts the oldest);
        # `pending` shares the bound.
        self.committed: dict[Digest, None] = {}
        self._cap = 4 * capacity
        self.committed_round = 0

    def __len__(self) -> int:
        return len(self.queue)

    def insert(self, digest: Digest) -> None:
        """Queue the digest of a payload this node now holds, once; one that
        is pending or committed is not queued again. Raises QueueFullError at
        capacity."""
        if digest in self.committed:
            return
        if digest in self.pending:
            self._stored.add(digest)
            return
        ensure(len(self.queue) < self.capacity, QueueFullError(self.capacity))
        self.queue[digest] = None

    def take(self, limit: int, round_: int) -> list[Digest]:
        """Pop up to `limit` digests from the front for the proposal of
        `round_`; they stay pending under that round."""
        out = []
        for digest in self.queue:
            if len(out) >= limit:
                break
            out.append(digest)
        for digest in out:
            del self.queue[digest]
            self._hold(digest, round_, stored=True)
        return out

    def note_block(self, round_: int, digests: Iterable[Digest]) -> None:
        """A block of `round_` was verified or processed: its digests leave
        the queue. At or below the committed round it is either committed
        already or an orphan that this node saw late; its digests are settled
        as committed, as they always were (a replayed block whose digests
        left the bounded `committed` set must not be proposed twice)."""
        for digest in digests:
            queued = digest in self.queue
            if queued:
                del self.queue[digest]
            if digest in self.committed:
                continue
            if round_ <= self.committed_round:
                self._unhold(digest)
                self._settle(digest)
            else:
                self._hold(digest, round_, stored=queued)

    def note_commit(self, round_: int, digests: Iterable[Digest]) -> list[Digest]:
        """Blocks up to `round_` committed, holding `digests`. Returns the
        orphans put back at the front of the queue, oldest first (those whose
        payload this node holds; the others are dropped); a digest that was
        admitted once is never refused for capacity here."""
        for digest in digests:
            self.queue.pop(digest, None)
            self._unhold(digest)
            self._settle(digest)
        self.committed_round = max(self.committed_round, round_)
        orphans = [d for d, r in self.pending.items() if r <= self.committed_round]
        back = [d for d in orphans if d in self._stored]
        for digest in orphans:
            self._unhold(digest)
        if back:
            self.queue = {**dict.fromkeys(back), **self.queue}
        return back

    def _hold(self, digest: Digest, round_: int, stored: bool) -> None:
        self.pending[digest] = max(round_, self.pending.get(digest, 0))
        if stored:
            self._stored.add(digest)
        while len(self.pending) > self._cap:
            self._unhold(next(iter(self.pending)))

    def _unhold(self, digest: Digest) -> None:
        self.pending.pop(digest, None)
        self._stored.discard(digest)

    def _settle(self, digest: Digest) -> None:
        self.committed[digest] = None
        while len(self.committed) > self._cap:
            del self.committed[next(iter(self.committed))]
