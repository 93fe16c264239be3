"""ctypes bridge to the C++ batch-staging plane (native/staging.cpp).

Builds the shared library on first use (g++ -O3, cached next to the
source), falling back to the pure-Python staging in ops/ed25519 when a
toolchain is unavailable. This is the native data-plane component the
reference gets from Rust (SURVEY.md §2: each crate maps to a native
equivalent); the control flow stays in Python, the per-byte work in C++.
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import pathlib
import subprocess
import sys
import threading

import numpy as np

log = logging.getLogger(__name__)

_NATIVE_DIR = pathlib.Path(__file__).resolve().parents[2] / "native"
_SO_PATH = _NATIVE_DIR / "libhotstuff_native.so"
_lock = threading.Lock()
_lib = None
_tried = False


def _build() -> pathlib.Path | None:
    srcs = [_NATIVE_DIR / "staging.cpp", _NATIVE_DIR / "store.cpp"]
    hdr = _NATIVE_DIR / "constants.h"
    srcs = [s for s in srcs if s.exists()]
    if not srcs:
        return None
    try:
        if not hdr.exists():
            subprocess.run(
                [sys.executable, str(_NATIVE_DIR / "gen_constants.py")],
                check=True,
                capture_output=True,
            )
        # Gate rebuilds on a content hash of the sources, not mtimes:
        # git checkouts reset mtimes, so an mtime check can silently load
        # a stale artifact that no longer matches the sources.
        digest = hashlib.sha256()
        for s in [*srcs, hdr]:
            digest.update(s.name.encode())
            digest.update(s.read_bytes())
        want = digest.hexdigest()
        stamp = _SO_PATH.with_suffix(".so.hash")
        # Cross-PROCESS lock: a local committee boots N nodes concurrently
        # and each may attempt the build; without it, parallel g++ runs
        # clobber the .so while another process dlopens it.
        import fcntl

        with open(_NATIVE_DIR / ".build.lock", "w") as lock_f:
            fcntl.flock(lock_f, fcntl.LOCK_EX)
            have = stamp.read_text().strip() if stamp.exists() else None
            if not _SO_PATH.exists() or have != want:
                tmp = _SO_PATH.with_suffix(f".so.tmp{os.getpid()}")
                subprocess.run(
                    ["g++", "-O3", "-shared", "-fPIC", "-std=c++17"]
                    + [str(s) for s in srcs]
                    + ["-o", str(tmp)],
                    check=True,
                    capture_output=True,
                )
                tmp.replace(_SO_PATH)
                stamp.write_text(want + "\n")
        return _SO_PATH
    except (subprocess.CalledProcessError, OSError) as e:
        log.warning("native build failed, using Python path: %s", e)
        return None


def get_lib():
    """The loaded native library, or None (build failure / no toolchain)."""
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        so = _build()
        if so is None:
            return None
        try:
            lib = ctypes.CDLL(str(so))
        except OSError as e:  # corrupt/partial artifact must not kill boot
            log.warning("loading native library failed, using Python path: %s", e)
            return None
        lib.hs_stage_batch.restype = ctypes.c_int
        lib.hs_stage_batch_packed.restype = ctypes.c_int
        # store engine (native/store.cpp)
        u8p = ctypes.POINTER(ctypes.c_uint8)
        lib.hs_store_open.restype = ctypes.c_void_p
        lib.hs_store_open.argtypes = [ctypes.c_char_p, ctypes.c_int]
        lib.hs_store_write.restype = ctypes.c_int
        lib.hs_store_write.argtypes = [
            ctypes.c_void_p, u8p, ctypes.c_int64, u8p, ctypes.c_int64,
        ]
        lib.hs_store_read.restype = ctypes.c_int64
        lib.hs_store_read.argtypes = [
            ctypes.c_void_p, u8p, ctypes.c_int64, ctypes.POINTER(u8p),
        ]
        lib.hs_store_contains.restype = ctypes.c_int
        lib.hs_store_contains.argtypes = [ctypes.c_void_p, u8p, ctypes.c_int64]
        lib.hs_store_len.restype = ctypes.c_int64
        lib.hs_store_len.argtypes = [ctypes.c_void_p]
        lib.hs_store_compact.restype = ctypes.c_int64
        lib.hs_store_compact.argtypes = [ctypes.c_void_p]
        lib.hs_store_close.restype = None
        lib.hs_store_close.argtypes = [ctypes.c_void_p]
        lib.hs_free.restype = None
        lib.hs_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def stage_batch_packed(messages, keys, signatures) -> dict | None:
    """Native packed staging: one (128, n) u8 wire array (rows 0-31 A,
    32-63 R, 64-95 S, 96-127 h) + host-side s<L mask. 128 B/signature on
    the host->device link vs 772 B for the f32 form (`stage_batch`)."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(messages)
    msg_blob = b"".join(messages)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum([len(m) for m in messages], out=offsets[1:])
    msgs = np.frombuffer(msg_blob, np.uint8)
    keys_arr = np.frombuffer(b"".join(keys), np.uint8)
    sigs_arr = np.frombuffer(b"".join(signatures), np.uint8)

    packed = np.empty((128, n), np.uint8)
    s_ok = np.empty(n, np.uint8)

    u8p = ctypes.POINTER(ctypes.c_uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    rc = lib.hs_stage_batch_packed(
        msgs.ctypes.data_as(u8p),
        offsets.ctypes.data_as(i64p),
        keys_arr.ctypes.data_as(u8p),
        sigs_arr.ctypes.data_as(u8p),
        ctypes.c_int64(n),
        packed.ctypes.data_as(u8p),
        s_ok.ctypes.data_as(u8p),
    )
    if rc != 0:
        return None
    return dict(packed=packed, s_ok=s_ok.astype(bool))


def stage_batch(messages, keys, signatures) -> dict | None:
    """Native equivalent of ops.ed25519.prepare_batch (same dict contract,
    minus the bit arrays used only by the legacy bit-ladder kernel)."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(messages)
    msg_blob = b"".join(messages)
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum([len(m) for m in messages], out=offsets[1:])
    msgs = np.frombuffer(msg_blob, np.uint8)
    keys_arr = np.frombuffer(b"".join(keys), np.uint8)
    sigs_arr = np.frombuffer(b"".join(signatures), np.uint8)

    a_y = np.empty((32, n), np.float32)
    a_sign = np.empty(n, np.float32)
    r_enc = np.empty((32, n), np.float32)
    s_digits = np.empty((64, n), np.float32)
    h_digits = np.empty((64, n), np.float32)
    s_ok = np.empty(n, np.uint8)

    u8p = ctypes.POINTER(ctypes.c_uint8)
    i64p = ctypes.POINTER(ctypes.c_int64)
    f32p = ctypes.POINTER(ctypes.c_float)

    def p(arr, ty):
        return arr.ctypes.data_as(ty)

    rc = lib.hs_stage_batch(
        p(msgs, u8p),
        p(offsets, i64p),
        p(keys_arr, u8p),
        p(sigs_arr, u8p),
        ctypes.c_int64(n),
        p(a_y, f32p),
        p(a_sign, f32p),
        p(r_enc, f32p),
        p(s_digits, f32p),
        p(h_digits, f32p),
        p(s_ok, u8p),
    )
    if rc != 0:
        return None
    return dict(
        a_y=a_y,
        a_sign=a_sign,
        r_enc=r_enc,
        s_digits=s_digits,
        h_digits=h_digits,
        s_ok=s_ok.astype(bool),
    )
