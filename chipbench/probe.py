"""The verify plane's probe: seeded signatures, some corrupted, sent to the
SAME sidecar the nodes use, over its wire protocol, while the window runs.
Each request is above the sidecar's urgent threshold, so it coalesces with
the nodes' workload batches and rides the same device dispatches.

Wire (crypto/remote.py): request u32 body_len, u32 n, n x {u32 mlen, msg,
32 B key, 64 B sig}, little-endian; response u32 n, n x u8 validity.
"""

from __future__ import annotations

import socket
import struct
import threading
import time


def encode(msgs, pks, sigs) -> bytes:
    parts = [struct.pack("<I", len(msgs))]
    for m, k, s in zip(msgs, pks, sigs):
        parts += [struct.pack("<I", len(m)), m, k, s]
    body = b"".join(parts)
    return struct.pack("<I", len(body)) + body


def _recv(sock: socket.socket, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("sidecar closed the connection")
        buf += chunk
    return bytes(buf)


class Probe(threading.Thread):
    """Sends request r at `t0 + offset + r * every_s`; keeps each answer with
    the time it took. An answer that never comes stays None."""

    def __init__(self, addr, corpus, t0: float, every_s: float, offset: float = 1.0):
        super().__init__(daemon=True)
        self.addr, self.corpus = addr, corpus
        self.t0, self.every_s, self.offset = t0, every_s, offset
        self.answers: list[list[bool] | None] = [None] * len(corpus)
        self.seconds: list[float | None] = [None] * len(corpus)
        self.errors: list[str] = []

    def run(self) -> None:
        sock = None
        for r, (msgs, pks, sigs) in enumerate(self.corpus):
            due = self.t0 + self.offset + r * self.every_s
            time.sleep(max(0.0, due - time.time()))
            try:
                if sock is None:
                    sock = socket.create_connection(self.addr, timeout=60)
                    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                t = time.time()
                sock.sendall(encode(msgs, pks, sigs))
                (n,) = struct.unpack("<I", _recv(sock, 4))
                mask = _recv(sock, n)
                self.seconds[r] = time.time() - t
                self.answers[r] = [b != 0 for b in mask]
            except OSError as e:
                self.errors.append(f"request {r}: {e!r}")
                if sock is not None:
                    sock.close()
                sock = None
        if sock is not None:
            sock.close()
