"""The timed path broken on purpose: the control of `correct`, and the
faults of `tests/test_faults.py`. Nothing here runs in a benchmark run; the
harness starts these only under its hidden `--fault` option.

  skip_half  the sidecar's backend does all its work and then reports every
             second lane as valid unchecked: the step that would tempt a
             later PR (check a sample of the lanes). The control.
  alter_tx   node 0 flips one bit in every 97th transaction before it seals
             the payload: what is committed is not what the client sent.
"""

from __future__ import annotations

import sys


def break_sidecar(fault: str) -> None:
    from hotstuff_tpu.crypto.tpu_backend import TpuBackend

    honest = TpuBackend.verify_batch_mask
    if fault != "skip_half":
        raise ValueError(f"no such sidecar fault: {fault!r}")

    def broken(self, messages, keys, signatures, committee=False):
        mask = list(honest(self, messages, keys, signatures, committee))
        for i in range(1, len(mask), 2):
            mask[i] = True
        return mask

    TpuBackend.verify_batch_mask = broken


def break_node() -> None:
    from hotstuff_tpu.mempool.payload_maker import PayloadMaker

    honest = PayloadMaker._ingest
    seen = {"n": 0}

    async def broken(self, tx, shed_ok=True):
        seen["n"] += 1
        if seen["n"] % 97 == 0:
            tx = tx[:-1] + bytes([tx[-1] ^ 1])
        await honest(self, tx, shed_ok)

    PayloadMaker._ingest = broken


if __name__ == "__main__":
    if sys.argv[1] == "node":
        break_node()
        from hotstuff_tpu.node.main import main

        main(sys.argv[2:])
