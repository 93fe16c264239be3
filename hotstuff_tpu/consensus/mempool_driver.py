"""Consensus-side proxy to the mempool (reference consensus/src/mempool.rs).

ConsensusMempoolMessage variants (mempool.rs:16-20):
  * Get(max, reply, round) -> payload digests for the block of `round`
  * Verify(block, reply)   -> payload availability: Accept / Reject / Wait;
                              the block's payloads leave the queue
  * Cleanup(b0, b1, block) -> the blocks' payloads leave the queue (processed)
  * Commit(round, digests) -> blocks up to `round` committed these payloads;
                              those of blocks that can no longer commit are
                              queued again (not in the reference)

On Wait the mempool synchronizer fetches missing payloads and loops the block
back to the consensus core when they arrive, so `verify` simply returns False
and the core drops the block for now (consensus/src/mempool.rs:41-60).
"""

from __future__ import annotations

import asyncio
from dataclasses import dataclass
from enum import Enum

from ..utils.actors import channel
from .messages import Block


class PayloadStatus(Enum):
    ACCEPT = "accept"
    REJECT = "reject"
    WAIT = "wait"


@dataclass(slots=True)
class MempoolGet:
    max_size: int
    reply: asyncio.Future
    round: int = 0


@dataclass(slots=True)
class MempoolVerify:
    block: Block
    reply: asyncio.Future


@dataclass(slots=True)
class MempoolCleanup:
    b0: Block
    b1: Block
    block: Block


@dataclass(slots=True)
class MempoolCommit:
    round: int
    digests: tuple


class MempoolDriver:
    def __init__(self, mempool_channel: asyncio.Queue) -> None:
        self._tx = mempool_channel

    async def get(self, max_size: int, round_: int) -> list:
        fut = asyncio.get_running_loop().create_future()
        await self._tx.put(MempoolGet(max_size, fut, round_))
        return await fut

    async def verify(self, block: Block) -> bool:
        """True iff all payloads are locally available (Accept). Reject raises;
        Wait returns False after the mempool registered a fetch+loopback."""
        if not block.payload:
            return True
        fut = asyncio.get_running_loop().create_future()
        await self._tx.put(MempoolVerify(block, fut))
        status = await fut
        if status == PayloadStatus.REJECT:
            from .errors import MalformedBlockError

            raise MalformedBlockError(f"invalid payload in {block}")
        return status == PayloadStatus.ACCEPT

    async def cleanup(self, b0: Block, b1: Block, block: Block) -> None:
        await self._tx.put(MempoolCleanup(b0, b1, block))

    async def commit(self, round_: int, blocks: list[Block]) -> None:
        await self._tx.put(
            MempoolCommit(round_, tuple(d for b in blocks for d in b.payload))
        )
