"""Crypto sidecar: one process owns the TPU, many nodes share it.

A TPU chip is process-exclusive under JAX, but a local committee (and any
co-located deployment) runs several node processes per machine. The
reference's answer to async crypto is the SignatureService request/reply
seam (crypto/src/lib.rs:226-252); this module generalises that seam ACROSS
processes: a sidecar process holds the TpuBackend and serves batch
verification over a local TCP socket, and nodes install a `RemoteBackend`
that ships large batches to the sidecar while verifying small
(consensus-critical, sub-crossover) batches on the local CPU — the same
crossover policy TpuBackend applies in-process (SURVEY.md §7 hard-part 3).

Server-side, requests from ALL nodes funnel through one
BatchVerificationService, so batches coalesce across the whole committee
before hitting the device — strictly better device utilisation than any
per-node dispatch could get.

Wire protocol (little-endian, one request per round-trip per connection):
  request:  u32 body_len, u32 n, then n x { u32 mlen, msg, 32 B pk, 64 B sig }
  response: u32 n, then n x u8 validity
The body-length prefix lets the server read the whole request in ONE
stream read — per-item stream awaits (4 per signature) measurably starved
the shared CPU at sustained load.

Two parses, chosen per request from its bytes alone (`_parse`). The protocol
signs 32-byte digests, so a request is as a rule n records of exactly 132
bytes: when `len(body) == 4 + 132 n` and every record's `mlen` word is 32,
the body is never taken apart. It stays one (n, 128) uint8 array, a
zero-copy view msg | pk | sig of the bytes the socket read returned, through
the service (`verify_rows`), the verified-signature cache (whose key, for
both parses, is the bytes msg + pk + sig: a row), `TpuBackend` and the
verifier's staging, and its answer comes back as one bool array. Any other
well-formed body (ragged or non-32-byte messages) takes `_parse_request`:
three Python objects a signature, the service's list path. A malformed
body is a ValueError from `_parse_request` and a dropped connection either
way. `sidecar.columnar_sigs` beside `sidecar.request_sigs` says how often
the first engaged.
"""

from __future__ import annotations

import asyncio
import itertools
import logging
import socket
import struct
import threading
import time
from typing import Sequence

import numpy as np

from ..ops import timeline
from ..utils import metrics
from .backend import ROW, CpuBackend, CryptoBackend
from .primitives import PublicKey, Signature

log = logging.getLogger("hotstuff.crypto")

# Mirror RemoteBackend.stats into the node's METRICS line. The fallback
# counter is the one a served run is judged by: an above-crossover batch a
# node verified on its OWN CPU because the sidecar did not answer.
_M_REMOTE_BATCHES = metrics.counter("crypto.remote_batches")
_M_REMOTE_SIGS = metrics.counter("crypto.remote_sigs")
_M_REMOTE_FALLBACKS = metrics.counter("crypto.remote_fallback_batches")
# What stayed on the node's own CPU because it was under the crossover: every
# caller's small batch (a payload's one signature, a QC's votes, a coalesced
# workload group of under 64), never a fallback.
_M_REMOTE_CPU_BATCHES = metrics.counter("crypto.remote_cpu_batches")
_M_REMOTE_CPU_SIGS = metrics.counter("crypto.remote_cpu_sigs")
# One successful round trip of a node's request: `sendall` to mask received.
_M_REMOTE_RTT = metrics.histogram("crypto.remote_rtt_s")
# The sidecar's side of the same request. parse and reply are synchronous
# sections of the event loop (spans: ops/timeline.py); request_s runs from
# the body read to the reply drained, across every wait in between.
_M_REQUESTS = metrics.counter("sidecar.requests")
_M_REQUEST_SIGS = metrics.counter("sidecar.request_sigs")
# of them, the signatures of requests that stayed columnar (`_parse`)
_M_COLUMNAR_SIGS = metrics.counter("sidecar.columnar_sigs")
_M_PARSE = metrics.histogram("sidecar.parse_s")
_M_REPLY = metrics.histogram("sidecar.reply_s")
_M_REQUEST = metrics.histogram("sidecar.request_s")
# Per-process request numbers: a request's parse and reply spans carry one.
_RIDS = itertools.count(1)


def _encode_request(
    messages: Sequence[bytes],
    keys: Sequence[PublicKey],
    signatures: Sequence[Signature],
) -> bytes:
    parts = [struct.pack("<I", len(messages))]
    for m, k, s in zip(messages, keys, signatures):
        parts.append(struct.pack("<I", len(m)))
        parts.append(m)
        parts.append(k.data if isinstance(k, PublicKey) else k)
        parts.append(s.data if isinstance(s, Signature) else s)
    body = b"".join(parts)
    return struct.pack("<I", len(body)) + body


def _parse_request(body: memoryview) -> tuple[list[bytes], list[tuple[PublicKey, Signature]]]:
    """Parse a request body (after the length prefix) without stream I/O.
    Raises ValueError on malformed framing or cap violations."""
    (n,) = struct.unpack("<I", body[:4])
    if n > MAX_REQUEST_ITEMS:
        raise ValueError(f"{n} items exceeds cap")
    off = 4
    msgs: list[bytes] = []
    pairs: list[tuple[PublicKey, Signature]] = []
    end = len(body)
    for _ in range(n):
        if off + 4 > end:
            raise ValueError("truncated item header")
        (mlen,) = struct.unpack("<I", body[off : off + 4])
        off += 4
        if mlen > MAX_MESSAGE_LEN or off + mlen + 96 > end:
            raise ValueError("item exceeds body")
        msgs.append(bytes(body[off : off + mlen]))
        off += mlen
        pairs.append(
            (
                PublicKey(bytes(body[off : off + 32])),
                Signature(bytes(body[off + 32 : off + 96])),
            )
        )
        off += 96
    if off != end:
        raise ValueError("trailing bytes in request body")
    return msgs, pairs


# One record of a 32-byte message: u32 mlen, then a row of backend.ROW bytes.
_RECORD = 4 + ROW
_MLEN_32 = np.frombuffer(struct.pack("<I", 32), np.uint8)


def _parse(body: bytes):
    """One request body -> its (n, 128) uint8 rows msg | pk | sig, a view of
    `body`, when every record carries a 32-byte message (decided from the
    bytes alone, module docstring); else what `_parse_request` makes of it,
    ValueError included."""
    (n,) = struct.unpack_from("<I", body)
    if 0 < n <= MAX_REQUEST_ITEMS and len(body) == 4 + _RECORD * n:
        records = np.frombuffer(body, np.uint8, offset=4).reshape(n, _RECORD)
        if (records[:, :4] == _MLEN_32).all():
            return records[:, 4:]
    return _parse_request(memoryview(body))


class RemoteBackend(CryptoBackend):
    """CryptoBackend that dispatches big batches to the sidecar process.

    Small batches (below `crossover`) verify on the local CPU: a localhost
    round-trip plus device dispatch would only add latency to the
    consensus-critical QC path. Falls back to CPU entirely if the sidecar
    is unreachable (a crypto sidecar outage must not halt the protocol);
    every such batch is logged and counted (`fallback_batches`,
    `crypto.remote_fallback_batches`) so a run cannot pass for a device
    run while the nodes' CPUs did the work."""

    name = "remote"

    # Requests below this ride the dedicated urgent lane (socket + slot),
    # mirroring the sidecar's `urgent_below` service-side split: a
    # consensus-critical QC check must never queue behind workload-sized
    # transfers occupying every pooled socket.
    URGENT_BELOW = 256

    def __init__(
        self,
        addr: tuple[str, int],
        crossover: int = 64,
        timeout: float = 30.0,
        pool_size: int = 5,
    ):
        self.addr = addr
        self.crossover = crossover
        self.timeout = timeout
        self._cpu = CpuBackend()
        # Connection pool: concurrent service dispatches each borrow a
        # socket, so a second batch streams into the sidecar while the first
        # is on the device (one socket would serialize the round trips).
        # Sized above BatchVerificationService's max_concurrent_dispatches
        # (4) so in-flight workload round trips can never exhaust it.
        self._pool: list[socket.socket] = []
        self._pool_lock = threading.Lock()
        self._pool_sem = threading.BoundedSemaphore(pool_size)
        # Urgent lane: one reserved socket + slot for small requests.
        self._urgent_sem = threading.BoundedSemaphore(1)
        self._urgent_sock: socket.socket | None = None
        self.stats = {
            "remote_batches": 0,
            "remote_sigs": 0,
            "cpu_batches": 0,
            "cpu_sigs": 0,
            "fallback_batches": 0,
        }

    def _dial(self) -> socket.socket:
        s = socket.create_connection(self.addr, timeout=self.timeout)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        return s

    def _borrow(self, urgent: bool) -> socket.socket:
        with self._pool_lock:
            if urgent:
                if self._urgent_sock is not None:
                    sock, self._urgent_sock = self._urgent_sock, None
                    return sock
            elif self._pool:
                return self._pool.pop()
        return self._dial()

    def _give_back(self, sock: socket.socket, urgent: bool) -> None:
        with self._pool_lock:
            if urgent and self._urgent_sock is None:
                self._urgent_sock = sock
            else:
                self._pool.append(sock)

    def _flush_pool(self) -> None:
        with self._pool_lock:
            stale, self._pool = self._pool, []
            if self._urgent_sock is not None:
                stale.append(self._urgent_sock)
                self._urgent_sock = None
        for s in stale:
            try:
                s.close()
            except OSError:
                pass

    def _recv_exact(self, sock: socket.socket, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            chunk = sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("sidecar closed connection")
            buf += chunk
        return bytes(buf)

    def verify_batch_mask(
        self,
        messages: Sequence[bytes],
        keys: Sequence[PublicKey],
        signatures: Sequence[Signature],
    ) -> list[bool]:
        n = len(messages)
        if n == 0:
            return []
        if n < self.crossover:
            self.stats["cpu_batches"] += 1
            self.stats["cpu_sigs"] += n
            _M_REMOTE_CPU_BATCHES.inc()
            _M_REMOTE_CPU_SIGS.inc(n)
            return self._cpu.verify_batch_mask(messages, keys, signatures)
        payload = _encode_request(messages, keys, signatures)
        urgent = n < self.URGENT_BELOW
        sem = self._urgent_sem if urgent else self._pool_sem
        with sem:  # bound concurrent round-trips per lane
            for attempt in (0, 1):
                sock = None
                try:
                    if attempt == 0:
                        sock = self._borrow(urgent)
                    else:
                        # Pooled sockets may ALL be stale (sidecar restart);
                        # the final attempt must dial fresh, and the rest of
                        # the suspect pool is dropped below.
                        self._flush_pool()
                        sock = self._dial()
                    t0 = time.perf_counter()
                    sock.sendall(payload)
                    (count,) = struct.unpack("<I", self._recv_exact(sock, 4))
                    if count != n:
                        raise ConnectionError("sidecar count mismatch")
                    mask = self._recv_exact(sock, n)
                    _M_REMOTE_RTT.record(time.perf_counter() - t0)
                    self._give_back(sock, urgent)
                    self.stats["remote_batches"] += 1
                    self.stats["remote_sigs"] += n
                    _M_REMOTE_BATCHES.inc()
                    _M_REMOTE_SIGS.inc(n)
                    return [b != 0 for b in mask]
                except (OSError, ConnectionError) as e:
                    if sock is not None:
                        try:
                            sock.close()
                        except OSError:
                            pass
                    if attempt == 1:
                        log.warning(
                            "sidecar unreachable (%s); falling back to CPU", e
                        )
        self.stats["cpu_batches"] += 1
        self.stats["cpu_sigs"] += n
        self.stats["fallback_batches"] += 1
        _M_REMOTE_FALLBACKS.inc()
        return self._cpu.verify_batch_mask(messages, keys, signatures)


# ---------------------------------------------------------------------------
# Sidecar server


async def _read_exact(reader: asyncio.StreamReader, n: int) -> bytes:
    return await reader.readexactly(n)


# Ingress caps: the sidecar is a local trusted surface, but a buggy or
# compromised co-tenant must not be able to OOM the process that owns the
# accelerator (SURVEY §5.3: verify-everything-at-ingress discipline).
# Per-item caps alone don't bound a request's aggregate size, so the
# cumulative bytes buffered per request are capped too.
MAX_REQUEST_ITEMS = 1_000_000
MAX_MESSAGE_LEN = 16 * 1024 * 1024
# Largest legitimate request is one fully-coalesced batch (~8192 items of
# ~200 B ≈ 1.6 MB); 64 MiB caps the parse-time peak (body + item copies)
# at ~128 MiB on the accelerator-owning host.
MAX_REQUEST_BYTES = 64 * 1024 * 1024


async def _handle_connection(reader, writer, service, urgent_below: int):
    peer = writer.get_extra_info("peername")
    log.debug("sidecar connection from %s", peer)
    try:
        while True:
            try:
                (body_len,) = struct.unpack("<I", await _read_exact(reader, 4))
            except (asyncio.IncompleteReadError, ConnectionResetError):
                break
            if body_len > MAX_REQUEST_BYTES:
                log.warning(
                    "dropping connection %s: %s B request exceeds %s B cap",
                    peer,
                    body_len,
                    MAX_REQUEST_BYTES,
                )
                break
            if body_len < 4:
                log.warning("dropping connection %s: runt request", peer)
                break
            body = await _read_exact(reader, body_len)
            t_read = time.perf_counter()
            rid = next(_RIDS)
            # the item count is the body's first word: known before the parse
            (n,) = struct.unpack_from("<I", body)
            try:
                with timeline.span("parse", rid, 0, n, hist=_M_PARSE, rid=rid):
                    parsed = _parse(body)
            except ValueError as e:
                log.warning("dropping connection %s: malformed request (%s)", peer, e)
                break
            _M_REQUESTS.inc()
            _M_REQUEST_SIGS.inc(n)
            del body  # the list parse copied it; rows keep it alive themselves
            # Small requests are consensus-critical (QC/TC checks above the
            # client's crossover but still latency-bound): they ride the
            # service's preemptive lane, which flushes at once while fewer
            # than two critical device programs are in flight and else
            # with the next one, together with whatever else waits there
            # (crypto/scheduler.py, the critical lane's dispatch window).
            if isinstance(parsed, np.ndarray):
                _M_COLUMNAR_SIGS.inc(n)
                mask = await service.verify_rows(
                    parsed, urgent=n < urgent_below, rid=rid
                )
            else:
                mask = await service.verify_group(
                    *parsed, urgent=n < urgent_below, rid=rid
                )
            del parsed
            with timeline.span("reply", rid, 0, n, hist=_M_REPLY, rid=rid):
                writer.write(
                    struct.pack("<I", n) + np.asarray(mask, np.uint8).tobytes()
                )
            await writer.drain()  # an await: outside the span
            _M_REQUEST.record(time.perf_counter() - t_read)
    finally:
        writer.close()


def warmup_backend(backend: CryptoBackend) -> None:
    """Pre-compile every verifier bucket width BEFORE serving: a cold jit
    specialisation (minutes for one whole verify program) hitting mid-run
    would stall the whole committee's verification pipeline. With the
    persistent compilation cache a later boot skips the compile but still
    traces, lowers and loads the program (about a minute for the ~270 MB
    Pallas one on a v5e host). Delegates to the backend's own warmup; backends without one
    (CpuBackend) need none. A program the device refuses raises here."""
    warm = getattr(backend, "warmup", None)
    if warm is not None:
        secs = warm()
        log.info("backend warmup finished in %.1f s", secs)


def _describe(backend: CryptoBackend) -> str:
    """`name` for host backends; name, platform, device kind and count for
    one that holds a device (TpuBackend) — the boot line says what the
    sidecar actually runs on, never just what it was asked for."""
    if not hasattr(backend, "platform"):
        return backend.name
    return (
        f"{backend.name}: platform={backend.platform} "
        f"kind={backend.device_kind!r} count={backend.device_count}"
    )


async def serve(
    addr: tuple[str, int],
    backend: CryptoBackend,
    max_batch: int = 8192,
    urgent_below: int = 256,
) -> None:
    """Run the sidecar server forever. One BatchVerificationService shared by
    every connection: batches coalesce across the whole committee."""
    from .batch_service import BatchVerificationService

    service = BatchVerificationService(backend, max_batch=max_batch)

    async def handler(reader, writer):
        await _handle_connection(reader, writer, service, urgent_below)

    from ..utils.actors import spawn

    # how much of one core this process's event loop uses (runtime.loop_cpu_s)
    meter = spawn(metrics.meter_loop_cpu(), name="loop-cpu-meter")
    server = await asyncio.start_server(handler, addr[0], addr[1])
    # NOTE: parsed by the benchmark harness to detect readiness.
    log.info(
        "Crypto sidecar (%s) successfully booted on %s:%s",
        _describe(backend),
        addr[0],
        addr[1],
    )
    try:
        async with server:
            await server.serve_forever()
    finally:
        meter.cancel()


def main(argv: list[str] | None = None) -> None:
    import argparse

    from ..utils.logging import setup_logging
    from .backend import make_backend

    p = argparse.ArgumentParser(description="crypto verification sidecar")
    p.add_argument("-v", "--verbose", action="count", default=2)
    p.add_argument("--port", type=int, required=True)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--backend", default="tpu", choices=["cpu", "tpu"])
    p.add_argument("--max-batch", type=int, default=8192)
    p.add_argument(
        "--min-bucket",
        type=int,
        default=1024,
        help="smallest jit bucket width; fewer widths = faster warmup "
        "(small urgent batches pad up, ~12 ms device time at 1024 lanes)",
    )
    p.add_argument(
        "--multihost",
        action="store_true",
        help="join a multi-host JAX job (parallel.mesh.init_multihost; "
        "coordinator from the standard JAX_COORDINATOR_ADDRESS env) and "
        "shard verification batches over every chip in the job",
    )
    p.add_argument(
        "--committee",
        default=None,
        metavar="PATH",
        help="node committee file (node/config.py Committee JSON): register "
        "the consensus validator keys as device-resident verification "
        "precompute at boot — on a --multihost mesh this pushes one "
        "replicated table copy per chip, so committee-tagged batches ride "
        "the zero-decompression kernel on every device",
    )
    p.add_argument(
        "--chunk",
        type=int,
        default=None,
        help="upload-pipeline chunk size (clamped to the bucket cap)",
    )
    p.add_argument(
        "--no-warmup", action="store_true", help="skip bucket pre-compilation"
    )
    args = p.parse_args(argv)
    if args.chunk is not None and args.chunk <= 0:
        # 0 would silently fall back to the default chunk downstream and a
        # negative value breaks the upload loop — neither may record a
        # sweep under a config the operator didn't specify.
        p.error("--chunk must be positive")
    setup_logging(args.verbose)
    if args.backend == "tpu":
        if args.multihost:
            from ..parallel.mesh import init_multihost

            mesh = init_multihost()
            backend = make_backend(
                args.backend,
                mesh=mesh,
                min_bucket=args.min_bucket,
                chunk=args.chunk,
            )
        else:
            backend = make_backend(
                args.backend, min_bucket=args.min_bucket, chunk=args.chunk
            )
    else:
        # A sweep that silently ignored these flags would record numbers
        # under a different config than the operator specified.
        if args.multihost:
            p.error("--multihost requires --backend tpu")
        if args.min_bucket != p.get_default("min_bucket"):
            p.error("--min-bucket requires --backend tpu")
        if args.chunk is not None:
            p.error("--chunk requires --backend tpu")
        if args.committee is not None:
            p.error("--committee requires --backend tpu")
        backend = make_backend(args.backend)
    from ..utils.logging import quiet_jax_logs

    quiet_jax_logs(args.verbose)
    if not args.no_warmup:
        warmup_backend(backend)
        quiet_jax_logs(args.verbose)  # device init may reconfigure logging
    if args.committee is not None:
        # The table is built on the host and uploaded: no compile. The
        # committee kernel family is NOT warmed here: the sidecar's wire
        # protocol carries no committee tag, so no request can reach that
        # family, and one program of it is minutes of cold compile.
        from ..node.config import Committee as NodeCommittee

        backend.register_committee(
            NodeCommittee.read(args.committee).consensus.sorted_keys()
        )
    _install_exit_report(backend)
    asyncio.run(
        serve(
            (args.host, args.port),
            backend,
            max_batch=args.max_batch,
        )
    )


def _install_exit_report(backend: CryptoBackend) -> None:
    """What `node run` has (node/main.py): the periodic `METRICS {json}`
    line LogParser scrapes, and one last snapshot on SIGTERM (the harness
    stops every process with it). The backend's own report — device,
    dispatched programs, routing stats — rides under `info.backend`."""
    import atexit
    import os
    import signal

    from ..ops.pipeline import close_all

    report = getattr(backend, "report", None)
    if report is not None:
        metrics.set_info("backend", report)
    metrics.start_periodic_emitter_from_env()

    def _on_term(*_a):
        metrics.emit_snapshot()
        close_all()
        os._exit(0)

    signal.signal(signal.SIGTERM, _on_term)
    atexit.register(metrics.emit_snapshot)


if __name__ == "__main__":
    main()
