"""Crypto sidecar: RemoteBackend <-> serve() round-trip and fallback."""

import asyncio
import functools
import random

import pytest

pytest.importorskip("cryptography")

from hotstuff_tpu.crypto import Digest, Signature, generate_keypair
from hotstuff_tpu.crypto.backend import CpuBackend
from hotstuff_tpu.crypto.remote import RemoteBackend, serve


@pytest.fixture
def triples():
    rng = random.Random(3)
    out = []
    for i in range(8):
        pk, sk = generate_keypair(rng)
        d = Digest.of(b"msg-%d" % i)
        out.append((d.data, pk, Signature.new(d, sk)))
    return out


def test_round_trip_and_mask(triples, run_async, base_port):
    async def body():
        server = asyncio.create_task(
            serve(("127.0.0.1", base_port), CpuBackend())
        )
        await asyncio.sleep(0.2)
        backend = RemoteBackend(("127.0.0.1", base_port), crossover=1)
        msgs = [m for m, _, _ in triples]
        keys = [k for _, k, _ in triples]
        sigs = [s for _, _, s in triples]
        mask = await asyncio.to_thread(
            backend.verify_batch_mask, msgs, keys, sigs
        )
        assert mask == [True] * len(triples)
        # corrupt one signature: only that item flips
        bad_sigs = list(sigs)
        bad_sigs[3] = sigs[4]
        mask2 = await asyncio.to_thread(
            backend.verify_batch_mask, msgs, keys, bad_sigs
        )
        assert mask2[3] is False
        assert [m for i, m in enumerate(mask2) if i != 3] == [True] * 7
        assert backend.stats["remote_batches"] == 2
        # two sequential requests reuse one connection
        server.cancel()

    run_async(body())


def test_small_batches_stay_local(triples, run_async, base_port):
    async def body():
        backend = RemoteBackend(("127.0.0.1", base_port + 7), crossover=64)
        m, k, s = triples[0]
        # below crossover: CPU path, no connection attempted (port is dead)
        mask = await asyncio.to_thread(backend.verify_batch_mask, [m], [k], [s])
        assert mask == [True]
        assert backend.stats["cpu_batches"] == 1
        assert backend.stats["remote_batches"] == 0
        assert backend.stats["fallback_batches"] == 0  # by policy, not outage

    run_async(body())


def test_unreachable_sidecar_falls_back_to_cpu(triples, run_async, base_port):
    async def body():
        backend = RemoteBackend(
            ("127.0.0.1", base_port + 8), crossover=1, timeout=0.5
        )
        msgs = [m for m, _, _ in triples]
        keys = [k for _, k, _ in triples]
        sigs = [s for _, _, s in triples]
        mask = await asyncio.to_thread(
            backend.verify_batch_mask, msgs, keys, sigs
        )
        assert mask == [True] * len(triples)
        assert backend.stats["cpu_batches"] == 1
        # ...and the fallback is COUNTED: a served run where this is
        # non-zero did not verify on the device (chip_smoke.py fails it)
        assert backend.stats["fallback_batches"] == 1

    run_async(body())


def test_oversized_request_dropped_server_survives(triples, run_async, base_port):
    """A request claiming an absurd item count or message length must drop
    the connection without killing the sidecar; honest clients keep working."""
    import socket
    import struct

    async def body():
        server = asyncio.create_task(
            serve(("127.0.0.1", base_port), CpuBackend())
        )
        await asyncio.sleep(0.2)
        try:
            await _attacks(base_port)
        finally:
            server.cancel()

    async def _attacks(base_port):
        def attack(payload: bytes) -> bytes:
            # server must close on us without replying
            s = socket.create_connection(("127.0.0.1", base_port), timeout=5)
            s.sendall(payload)
            s.settimeout(2)
            data = s.recv(4)
            s.close()
            return data

        # body length beyond the aggregate cap
        assert await asyncio.to_thread(attack, struct.pack("<I", 0xFFFFFFFF)) == b""
        # item count beyond the cap (valid body length)
        body = struct.pack("<I", 0xFFFFFFFF) + b"\x00" * 4
        assert (
            await asyncio.to_thread(
                attack, struct.pack("<I", len(body)) + body
            )
            == b""
        )
        # malformed: one item claiming a message longer than the body
        body = struct.pack("<I", 1) + struct.pack("<I", 0x7FFFFF)
        assert (
            await asyncio.to_thread(
                attack, struct.pack("<I", len(body)) + body
            )
            == b""
        )

        # honest client still served after both attacks
        backend = RemoteBackend(("127.0.0.1", base_port), crossover=1)
        msgs = [m for m, _, _ in triples]
        keys = [k for _, k, _ in triples]
        sigs = [s for _, _, s in triples]
        mask = await asyncio.to_thread(backend.verify_batch_mask, msgs, keys, sigs)
        assert mask == [True] * len(triples)

    run_async(body())


def test_parse_request_enforces_per_message_cap():
    """_parse_request must reject an item whose claimed length exceeds
    MAX_MESSAGE_LEN even when the body actually contains that many bytes
    (the framing check alone would accept it)."""
    import struct

    import pytest as _pytest

    from hotstuff_tpu.crypto.remote import (
        MAX_MESSAGE_LEN,
        MAX_REQUEST_ITEMS,
        _parse_request,
    )

    mlen = MAX_MESSAGE_LEN + 1
    body = struct.pack("<I", 1) + struct.pack("<I", mlen) + b"\x00" * (mlen + 96)
    with _pytest.raises(ValueError):
        _parse_request(memoryview(body))
    # item-count cap now lives in the parser too
    with _pytest.raises(ValueError):
        _parse_request(memoryview(struct.pack("<I", MAX_REQUEST_ITEMS + 1)))


def test_boot_line_and_exit_report_name_the_device(monkeypatch, caplog):
    """The sidecar's boot line carries platform, kind and count (not just
    the name it was asked for), and its METRICS line carries the backend's
    report under `info.backend` — the line LogParser scrapes."""
    import json
    import logging

    from benchmark.logs import LogParser
    from hotstuff_tpu.crypto import remote
    from hotstuff_tpu.utils import metrics

    class FakeDeviceBackend(CpuBackend):
        name = "tpu"
        platform, device_kind, device_count = "tpu", "TPU v5 lite", 1

        def report(self):
            return {
                "platform": self.platform,
                "device_kind": self.device_kind,
                "device_count": self.device_count,
                "kernels": {"generic": "pallas_p128dh"},
                "dispatched": {"pallas_p128dh": 7},
                "tpu_sigs": 1234,
                "cpu_sigs": 5,
            }

    assert remote._describe(CpuBackend()) == "cpu"
    assert remote._describe(FakeDeviceBackend()) == (
        "tpu: platform=tpu kind='TPU v5 lite' count=1"
    )
    metrics.set_info("backend", FakeDeviceBackend().report)
    try:
        with caplog.at_level(logging.INFO, logger="hotstuff.metrics"):
            metrics.emit_snapshot()
    finally:
        metrics.REGISTRY._info.clear()
    line = [r.getMessage() for r in caplog.records if "METRICS" in r.getMessage()][-1]
    snap = json.loads(line.split("METRICS ", 1)[1])
    assert snap["info"]["backend"]["tpu_sigs"] == 1234
    assert "info" not in metrics.dump()  # absent unless a process sets it
    parser = LogParser([], [], sidecar=f"[2026-01-01T00:00:00.000Z INFO x] {line}\n")
    assert parser.sidecar_metrics["info"]["backend"]["platform"] == "tpu"
    assert "Sidecar device: tpu (TPU v5 lite x1), 1,234 sigs on device" in (
        parser._sidecar_line()
    )


# -- the two parses of one request body (crypto/remote.py `_parse`) -----------


def _body(records, n=None, tail=b""):
    """A request body (after the length prefix) of (msg, pk, sig) bytes
    records; `n` overrides the count word."""
    import struct

    parts = [struct.pack("<I", len(records) if n is None else n)]
    for m, pk, sig in records:
        parts += [struct.pack("<I", len(m)), m, pk, sig]
    return b"".join(parts) + tail


def _seeded_records(n, seed, mlen=lambda i: 32):
    rng = random.Random(seed)
    return [
        (rng.randbytes(mlen(i)), rng.randbytes(32), rng.randbytes(64))
        for i in range(n)
    ]


@functools.lru_cache(maxsize=None)
def _parse_cases():
    import struct

    from hotstuff_tpu.crypto.remote import MAX_MESSAGE_LEN, MAX_REQUEST_ITEMS

    fixed = {
        f"fixed-{n}": (_body(_seeded_records(n, n)), True)
        for n in (1, 63, 64, 255, 256, 4097)
    }
    odd = lambda bad: lambda i: bad if i == 5 else 32  # noqa: E731
    return {
        **fixed,
        "empty": (_body([]), False),
        "ragged": (_body(_seeded_records(40, 7, lambda i: i % 70)), False),
        "one-mlen-31": (_body(_seeded_records(9, 8, odd(31))), False),
        "one-mlen-33": (_body(_seeded_records(9, 9, odd(33))), False),
        # 132-byte framing by accident: two records whose lengths sum to 64
        "31-and-33": (_body(_seeded_records(2, 10, lambda i: 31 + 2 * i)), False),
        "truncated": (_body(_seeded_records(6, 11))[:-1], None),
        "truncated-record": (_body(_seeded_records(6, 12))[:-132], None),
        "trailing": (_body(_seeded_records(6, 13), tail=b"\x00"), None),
        "trailing-record": (_body(_seeded_records(6, 14), n=5), None),
        "count-over-cap": (struct.pack("<I", MAX_REQUEST_ITEMS + 1), None),
        "mlen-over-cap": (
            struct.pack("<II", 1, MAX_MESSAGE_LEN + 1) + bytes(128),
            None,
        ),
    }


@pytest.mark.parametrize("case", sorted(_parse_cases()))
def test_columnar_parse_equals_list_parse(case):
    """`_parse` accepts and rejects exactly what `_parse_request` does and
    yields the same triples; only bodies of 32-byte-message records alone
    stay columnar."""
    import numpy as np

    from hotstuff_tpu.crypto.backend import columns_to_lists, row_columns
    from hotstuff_tpu.crypto.remote import _parse, _parse_request

    body, columnar = _parse_cases()[case]
    if columnar is None:
        with pytest.raises(ValueError):
            _parse_request(memoryview(body))
        with pytest.raises(ValueError):
            _parse(body)
        return
    msgs, pairs = _parse_request(memoryview(body))
    parsed = _parse(body)
    assert isinstance(parsed, np.ndarray) == columnar
    if columnar:
        assert parsed.shape == (len(msgs), 128) and parsed.dtype == np.uint8
        assert not parsed.flags.owndata  # a view of the body, no copy
        m, k, s = columns_to_lists(*row_columns(parsed))
        parsed = (m, list(zip(k, s)))
    assert parsed == (msgs, pairs)


@pytest.mark.parametrize("mlen", [32, 31], ids=["columnar", "list"])
def test_live_serve_same_mask_whichever_parse(mlen, run_async, base_port):
    """One request with invalid lanes through a live `serve`: 32-byte
    messages take the columnar parse, 31-byte ones the list parse; the mask
    is OpenSSL's either way, and the counter says which parse ran."""
    from hotstuff_tpu.crypto import PublicKey
    from hotstuff_tpu.utils import metrics

    rng = random.Random(mlen)
    msgs, keys, sigs, want = [], [], [], []
    for i in range(70):
        pk, sk = generate_keypair(rng)
        m = rng.randbytes(mlen)
        sig = sk.to_crypto().sign(m)
        if i % 7 == 3:  # a corrupted signature
            sig = sig[:9] + bytes([sig[9] ^ 1]) + sig[10:]
        elif i % 7 == 5:  # the wrong key
            pk = PublicKey(rng.randbytes(32))
        want.append(i % 7 not in (3, 5))
        msgs.append(m), keys.append(pk), sigs.append(Signature(sig))
    assert CpuBackend().verify_batch_mask(msgs, keys, sigs) == want
    columnar = metrics.counter("sidecar.columnar_sigs")
    arrived = metrics.counter("sidecar.request_sigs")

    async def body():
        server = asyncio.create_task(
            serve(("127.0.0.1", base_port), CpuBackend())
        )
        await asyncio.sleep(0.2)
        c0, a0 = columnar.value, arrived.value
        try:
            backend = RemoteBackend(("127.0.0.1", base_port), crossover=1)
            for _ in range(2):  # the second time the cache answers the valid
                mask = await asyncio.to_thread(
                    backend.verify_batch_mask, msgs, keys, sigs
                )
                assert mask == want
            assert backend.stats["fallback_batches"] == 0
        finally:
            server.cancel()
        assert arrived.value - a0 == 140
        assert columnar.value - c0 == (140 if mlen == 32 else 0)

    run_async(body())
