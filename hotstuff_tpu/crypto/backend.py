"""Pluggable crypto execution backends.

This is the `CryptoBackend` seam called for by the north star: the reference
hard-wires ed25519_dalek's `verify_batch` (crypto/src/lib.rs:194-220); here
every batch verification dispatches through an interchangeable backend so the
hot path can run either on host CPU (baseline) or as a vmapped JAX kernel on
TPU (hotstuff_tpu.ops.ed25519), sharded over a device mesh at scale.
"""

from __future__ import annotations

import abc
import threading
from typing import Sequence

import numpy as np

from .primitives import InvalidSignature, PublicKey, Signature

# A verify request kept columnar (crypto/remote.py): n wire records of a
# 32-byte message as ONE (n, 128) uint8 array, msg | pk | sig in the wire's
# own order. It reaches a backend as the array's three column views.
ROW = 128


def row_columns(rows: np.ndarray) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(n, 128) rows -> their message, key and signature columns (views)."""
    return rows[:, :32], rows[:, 32:64], rows[:, 64:]


def columns_to_lists(
    messages: np.ndarray, keys: np.ndarray, signatures: np.ndarray
) -> tuple[list[bytes], list[PublicKey], list[Signature]]:
    """The three columns as the sequences every backend takes: where a
    columnar batch meets code that wants objects (a host backend, a
    bucket shared with list groups), it is taken apart here."""
    m, k, s = messages.tobytes(), keys.tobytes(), signatures.tobytes()
    return (
        [m[i : i + 32] for i in range(0, len(m), 32)],
        [PublicKey(k[i : i + 32]) for i in range(0, len(k), 32)],
        [Signature(s[i : i + 64]) for i in range(0, len(s), 64)],
    )


class CryptoBackend(abc.ABC):
    """Batch signature verification engine.

    Contract (matching ed25519_dalek `verify_batch`): returns True iff ALL
    (message, key, signature) triples verify. `verify_batch_mask` additionally
    reports per-item validity (needed to avoid re-verifying a whole QC when
    one Byzantine vote is bad)."""

    name: str = "abstract"
    # True on a backend whose `verify_batch_mask` also takes the three uint8
    # column arrays of a columnar batch (`row_columns`) and may then answer
    # with a bool array; BatchVerificationService probes it and hands every
    # other backend `columns_to_lists` of them.
    accepts_columns = False

    @abc.abstractmethod
    def verify_batch_mask(
        self,
        messages: Sequence[bytes],
        keys: Sequence[PublicKey],
        signatures: Sequence[Signature],
    ) -> list[bool]: ...

    def verify_batch(
        self,
        messages: Sequence[bytes],
        keys: Sequence[PublicKey],
        signatures: Sequence[Signature],
    ) -> bool:
        if not messages:
            return True
        return all(self.verify_batch_mask(messages, keys, signatures))


class CpuBackend(CryptoBackend):
    """Host ed25519 via OpenSSL (`cryptography`) -- the parity baseline,
    equivalent to the reference's ed25519_dalek CPU path."""

    name = "cpu"

    def verify_batch_mask(
        self,
        messages: Sequence[bytes],
        keys: Sequence[PublicKey],
        signatures: Sequence[Signature],
    ) -> list[bool]:
        out = []
        for msg, pk, sig in zip(messages, keys, signatures, strict=True):
            try:
                pk.to_crypto().verify(sig.data, msg)
                out.append(True)
            except (InvalidSignature, ValueError):
                out.append(False)
        return out


_lock = threading.Lock()
_backend: CryptoBackend = CpuBackend()


def get_backend() -> CryptoBackend:
    return _backend


def set_backend(backend: CryptoBackend) -> CryptoBackend:
    """Install the active backend (e.g. TpuBackend); returns the previous one."""
    global _backend
    with _lock:
        prev, _backend = _backend, backend
    return prev


def make_backend(kind: str, **kwargs) -> CryptoBackend:
    """Factory used by the node CLI's --crypto flag (cpu | tpu | remote)."""
    if kind == "cpu":
        return CpuBackend()
    if kind == "tpu":
        from .tpu_backend import TpuBackend

        return TpuBackend(**kwargs)
    if kind == "remote":
        from .remote import RemoteBackend

        return RemoteBackend(**kwargs)
    raise ValueError(f"unknown crypto backend {kind!r}")
