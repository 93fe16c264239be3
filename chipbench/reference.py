"""The plain reference: what the served path has to produce, worked out
without a line of the program (`hotstuff_tpu` is never imported here).

  * transactions: the bytes every client sends, from (seed, client, sequence)
  * payload digest: SHA-512/256-style digest over author and transactions,
    as the wire format defines it (mempool/messages.py is the program's copy)
  * store reader: a node's append-only log, `u32 klen, u32 vlen, key, value`
  * probe corpus: ed25519 signatures made from the seed, every `bad_every`-th
    corrupted in one of seven ways, and their validity by OpenSSL
    (`cryptography`), strict RFC 8032 rules

Data takes the place of weights here, and all of it is made from `--seed`.
"""

from __future__ import annotations

import hashlib
import random
import struct

SAMPLE = 0  # first byte of a sample transaction; the rest carry 1
CLIENT_SHIFT = 40  # ids and tags: client index above, sequence below

P = 2**255 - 19
L_ORDER = 2**252 + 27742317777372353535851937790883648493
D = -121665 * pow(121666, P - 2, P) % P


def seed_bytes(seed: int) -> bytes:
    return struct.pack(">Q", seed & 0xFFFFFFFFFFFFFFFF)


def make_tx(seed8: bytes, kind: int, ident: int, size: int) -> bytes:
    """One transaction: kind byte, big-endian u64 id, then a body that only
    (seed, kind, id) determine. Two clients or two ticks never share bytes."""
    head = bytes([kind]) + struct.pack(">Q", ident)
    return head + hashlib.shake_256(seed8 + head).digest(size - 9)


def sample_id(client: int, tick: int) -> int:
    return (client << CLIENT_SHIFT) | tick


def tx_tag(client: int, seq: int) -> int:
    return (client << CLIENT_SHIFT) | seq


def payload_digest(author: bytes, transactions) -> bytes:
    h = hashlib.sha512()
    h.update(b"HSPAYLOAD")
    h.update(author)
    h.update(struct.pack("<I", len(transactions)))
    for tx in transactions:
        h.update(struct.pack("<I", len(tx)))
        h.update(tx)
    return h.digest()[:32]


def read_store(path: str, prefix: bytes = b"payload:") -> dict[bytes, bytes]:
    """key (without prefix) -> value for every record under `prefix`; a torn
    tail is dropped, a later record of a key replaces an earlier one."""
    with open(path, "rb") as f:
        buf = f.read()
    out, pos, n = {}, 0, len(buf)
    while pos + 8 <= n:
        klen, vlen = struct.unpack_from("<II", buf, pos)
        end = pos + 8 + klen + vlen
        if end > n:
            break
        key = buf[pos + 8 : pos + 8 + klen]
        if key.startswith(prefix):
            out[key[len(prefix) :]] = buf[pos + 8 + klen : end]
        pos = end
    return out


def decode_payload(value: bytes) -> tuple[list[bytes], bytes, bytes]:
    """(transactions, author, signature) of a stored payload."""
    (n,) = struct.unpack_from("<I", value, 0)
    pos, txs = 4, []
    for _ in range(n):
        (ln,) = struct.unpack_from("<I", value, pos)
        txs.append(value[pos + 4 : pos + 4 + ln])
        pos += 4 + ln
    author, signature = value[pos : pos + 32], value[pos + 32 : pos + 96]
    if pos + 96 != len(value):
        raise ValueError("trailing bytes after payload")
    return txs, author, signature


# ---------------------------------------------------------------------------
# Committee keys and the probe corpus (OpenSSL signs and judges)


def keypair(seed32: bytes) -> tuple[bytes, object]:
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

    sk = Ed25519PrivateKey.from_private_bytes(seed32)
    return sk.public_key().public_bytes_raw(), sk


def committee_seeds(seed: int, n: int) -> list[bytes]:
    rng = random.Random(f"chipbench-committee-{seed}")
    return [rng.randbytes(32) for _ in range(n)]


def _off_curve_key() -> bytes:
    """A 32-byte y for which x^2 = (y^2-1)/(d y^2+1) is no square."""
    for y in range(2, 200):
        u, v = (y * y - 1) % P, (D * y * y + 1) % P
        x2 = u * pow(v, P - 2, P) % P
        if pow(x2, (P - 1) // 2, P) == P - 1:
            return y.to_bytes(32, "little")
    raise AssertionError("no off-curve y below 200")


def _corrupt(i: int, kind: int, msgs, pks, sigs, rng) -> None:
    sig = sigs[i]
    if kind == 0:  # bad s: one bit flipped
        s = bytearray(sig)
        s[32 + rng.randrange(31)] ^= 1 << rng.randrange(8)
        sigs[i] = bytes(s)
    elif kind == 1:  # bad R: another signature's R
        sigs[i] = sigs[i - 1][:32] + sig[32:]
    elif kind == 2:  # wrong key
        pks[i] = pks[i - 1] if pks[i - 1] != pks[i] else pks[i - 2]
    elif kind == 3:  # wrong message
        msgs[i] = rng.randbytes(32)
    elif kind == 4:  # non-canonical s: s + L passes only under lax rules
        s = int.from_bytes(sig[32:], "little") + L_ORDER
        sigs[i] = sig[:32] + s.to_bytes(32, "little")
    elif kind == 5:  # key that is not a curve point
        pks[i] = _off_curve_key()
    else:  # null signature
        sigs[i] = bytes(64)


def probe_corpus(seed: int, requests: int, sigs: int, bad_every: int):
    """`requests` lists of (msg, key, sig) triples over distinct 32-byte
    messages from 64 seeded keys. Every `bad_every`-th lane is corrupted,
    kinds cycling. From the second request on, the first eighth of each
    request repeats lanes of the request before it, good and bad alike, so
    the sidecar's verified-signature cache is on the path as well."""
    rng = random.Random(f"chipbench-probe-{seed}")
    keys = [keypair(rng.randbytes(32)) for _ in range(64)]
    out, serial, kind = [], 0, 0
    for r in range(requests):
        msgs, pks, sg = [], [], []
        for i in range(sigs):
            pub, sk = keys[(serial + i) % 64]
            m = rng.randbytes(28) + struct.pack("<I", serial + i)
            msgs.append(m)
            pks.append(pub)
            sg.append(sk.sign(m))
        serial += sigs
        for i in range(3, sigs, bad_every):
            _corrupt(i, kind % 7, msgs, pks, sg, rng)
            kind += 1
        if r:
            prev = out[-1]
            for i in range(sigs // 8):
                j = sigs - 1 - i
                msgs[i], pks[i], sg[i] = prev[0][j], prev[1][j], prev[2][j]
        out.append((msgs, pks, sg))
    return out


def verify_strict(msg: bytes, key: bytes, sig: bytes) -> bool:
    """RFC 8032 verification by OpenSSL: rejects s >= L and keys off the
    curve. The reference's answer for one probe lane."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

    try:
        Ed25519PublicKey.from_public_bytes(key).verify(sig, msg)
    except (InvalidSignature, ValueError):
        return False
    return True
