"""One-shot device tuning session: run every TPU microbenchmark that the
round-4 perf work needs, in one process (the chip is process-exclusive and
has been intermittently reachable — batch everything).

Sections (each skippable):
  --vpu        int32 vs f32 elementwise multiply rate (decides whether a
               radix-2^13 int32 limb field is worth building)
  --phases     wall-time decomposition of the pallas verify: decompress +
               table build vs ladder vs compress (where the non-ladder 14%
               of ops actually lands in wall-clock)
  --field      f32 radix-256 vs u32 radix-2^12 field sqr-chain rate
  --chunks     e2e rate vs pipeline chunk size (2048/4096/8192, plus a
               single-dispatch 16384-chunk/16384-bucket config)
  --dh         device-hash vs host-hash packed e2e comparison

Usage: python tools/tune_device.py [--all] [--vpu] [--phases] ...
"""

from __future__ import annotations

import argparse
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import numpy as np


def _sync(x):
    return np.asarray(x)


def bench_vpu(reps: int = 20) -> None:
    import jax
    import jax.numpy as jnp

    shape = (64, 4096)

    def chain_f32(x):
        for _ in range(64):
            x = x * x + 1.0
        return x

    def chain_i32(x):
        for _ in range(64):
            x = x * x + 1
        return x

    def chain_u32_logic(x):
        for _ in range(64):
            x = (x ^ (x >> 7)) + (x << 3)
        return x

    for name, fn, arr in (
        ("f32 mul+add", chain_f32, jnp.ones(shape, jnp.float32) * 1.0001),
        ("i32 mul+add", chain_i32, jnp.ones(shape, jnp.int32) * 3),
        ("u32 xor/shift/add", chain_u32_logic, jnp.ones(shape, jnp.uint32) * 3),
    ):
        jit = jax.jit(fn)
        _sync(jit(arr))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = jit(arr)
        _sync(out)
        dt = time.perf_counter() - t0
        ops = 64 * 2 * shape[0] * shape[1] * reps
        print(f"vpu {name:<20} {ops / dt / 1e12:8.3f} T op/s")


def bench_field(batch: int = 4096, chain: int = 64, reps: int = 10) -> None:
    """f32 radix-256 field vs experimental uint32 radix-2^12 field: a
    chain of `chain` squarings, batched — the kernel-shaped workload.
    Decides whether the 2.1x-fewer-products int field is worth porting
    the verify kernel to (depends on the VPU's int32 multiply rate)."""
    import jax

    from hotstuff_tpu.ops import field as f32f
    from hotstuff_tpu.ops import field12 as f12

    import random

    rng = random.Random(5)
    vals = [rng.randrange(f32f.P) for _ in range(batch)]

    from jax import lax

    for name, mod in (("f32 radix-256", f32f), ("u32 radix-2^12", f12)):
        arr = jax.device_put(
            np.concatenate([mod.limbs_of_int(v) for v in vals[:batch]], axis=1)
        )
        # Chain the REAL sqr (symmetric convolution) — sqr_n uses mul(x,x)
        # in both fields, which would measure the wrong op for the
        # sqr-heavy kernel (pow chains, doublings).
        fn = jax.jit(
            lambda x, m=mod: lax.fori_loop(
                0, chain, lambda _, y: m.sqr(y), x
            )
        )
        _sync(fn(arr))
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(arr)
        _sync(out)
        dt = time.perf_counter() - t0
        rate = batch * chain * reps / dt
        print(f"field {name:<16} {rate / 1e6:8.2f} M field-sqr/s")


def bench_phases(batch: int = 4096, reps: int = 5) -> None:
    import jax
    import jax.numpy as jnp

    from __graft_entry__ import _signed_batch
    from hotstuff_tpu.ops import ed25519 as ed
    from hotstuff_tpu.ops import pallas_ladder as pl_mod
    from hotstuff_tpu.ops import sha512 as sha

    msgs, pks, sigs = _signed_batch(batch)
    staged = ed.prepare_batch(msgs, pks, sigs)
    a_y = jax.device_put(staged["a_y"])
    a_sign = jax.device_put(staged["a_sign"])
    r_enc = jax.device_put(staged["r_enc"])
    s_d = jax.device_put(staged["s_digits"])
    h_d = jax.device_put(staged["h_digits"])

    decomp = jax.jit(lambda y, s: ed.decompress(y, s))
    table = jax.jit(
        lambda y, s: ed._build_neg_a_table(ed.decompress(y, s)[1], y)
    )
    full = pl_mod._verify_pallas_jit

    ta = table(a_y, a_sign)
    ladder = jax.jit(
        lambda sd, hd, t0, t1, t2, t3: pl_mod.ladder_pallas(
            sd, hd, t0, t1, t2, t3
        )
    )
    lad_out = ladder(s_d, h_d, *ta)
    comp = jax.jit(lambda p: ed.compress(p))

    dhm = jax.device_put(
        np.frombuffer(b"".join(msgs), np.uint8).reshape(batch, 32).T.copy()
    )
    dha = jax.device_put(
        np.frombuffer(b"".join(pks), np.uint8).reshape(batch, 32).T.copy()
    )
    dhr = jax.device_put(
        np.frombuffer(b"".join(s[:32] for s in sigs), np.uint8)
        .reshape(batch, 32)
        .T.copy()
    )
    hashfn = jax.jit(sha.h_digits_on_device)

    rows = [
        ("decompress", lambda: decomp(a_y, a_sign)),
        ("decompress+table", lambda: table(a_y, a_sign)),
        ("ladder (pallas)", lambda: ladder(s_d, h_d, *ta)),
        ("compress", lambda: comp(lad_out)),
        ("sha512+modL (dh)", lambda: hashfn(dhr, dha, dhm)),
        ("full verify", lambda: full(a_y, a_sign, r_enc, s_d, h_d)),
    ]
    for name, fn in rows:
        _sync(jax.tree_util.tree_leaves(fn())[0])  # warm/compile
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn()
        _sync(jax.tree_util.tree_leaves(out)[0])
        dt = (time.perf_counter() - t0) / reps
        print(f"phase {name:<18} {dt * 1e3:8.2f} ms  {batch / dt:>10,.0f}/s")


def bench_chunks(batch: int = 16384, iters: int = 3, kernel: str = "pallas") -> None:
    from __graft_entry__ import _signed_batch
    from hotstuff_tpu.ops import ed25519 as ed

    msgs, pks, sigs = _signed_batch(batch)
    # chunk == batch means ONE upload + ONE dispatch: if per-transfer
    # latency dominates, fewer bigger transfers win even though
    # pipelining overlap shrinks.
    for chunk, bucket in (
        (2048, 8192),
        (4096, 8192),
        (8192, 8192),
        (16384, 16384),
    ):
        v = ed.Ed25519TpuVerifier(max_bucket=bucket, kernel=kernel, chunk=chunk)
        assert v.verify_batch_mask(msgs, pks, sigs).all()
        t0 = time.perf_counter()
        for _ in range(iters):
            v.verify_batch_mask(msgs, pks, sigs)
        rate = batch * iters / (time.perf_counter() - t0)
        print(f"chunk {chunk:>5} (bucket {bucket:>5})  e2e {rate:>10,.0f} sigs/s")


def bench_dh(batch: int = 8192, iters: int = 4, kernel: str = "pallas") -> None:
    """Device-hash vs host-hash e2e on the same batch."""
    from __graft_entry__ import _signed_batch
    from hotstuff_tpu.ops import ed25519 as ed

    msgs, pks, sigs = _signed_batch(batch)
    v = ed.Ed25519TpuVerifier(max_bucket=8192, kernel=kernel, chunk=4096)

    # Time both wire formats directly (staging + upload + kernel), bypassing
    # verify_batch_mask's auto-selection so each path is measured alone.
    for name, stage, fn in (
        ("host-hash", ed.prepare_batch_packed, v._packed_fn()),
        ("device-hash", ed.prepare_batch_packed_dh, v._packed_dh_fn()),
    ):
        import jax

        staged = stage(msgs[:4096], pks[:4096], sigs[:4096])
        padded = ed._pad(staged["packed"], 4096)
        mask = np.asarray(fn(jax.device_put(padded)))
        assert mask.all()
        t0 = time.perf_counter()
        for _ in range(iters):
            s = stage(msgs[:4096], pks[:4096], sigs[:4096])
            out = fn(jax.device_put(ed._pad(s["packed"], 4096)))
        np.asarray(out)
        rate = 4096 * iters / (time.perf_counter() - t0)
        print(f"dh-compare {name:<12} {rate:>10,.0f} sigs/s (serial, no pipeline)")


def main() -> None:
    ap = argparse.ArgumentParser()
    for flag in ("all", "vpu", "field", "phases", "chunks", "dh", "cpu"):
        ap.add_argument(f"--{flag}", action="store_true")
    args = ap.parse_args()
    from hotstuff_tpu.ops import enable_persistent_cache

    enable_persistent_cache()
    import jax

    if args.cpu:
        jax.config.update("jax_platforms", "cpu")
    print(f"# devices: {jax.devices()}")
    if args.all or args.vpu:
        bench_vpu()
    if args.all or args.field:
        bench_field()
    if args.all or args.phases:
        bench_phases()
    kernel = "w4" if args.cpu else "pallas"
    if args.all or args.chunks:
        bench_chunks(kernel=kernel)
    if args.all or args.dh:
        bench_dh(kernel=kernel)


if __name__ == "__main__":
    main()
