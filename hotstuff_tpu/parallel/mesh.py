"""Device-mesh parallelism for the crypto plane.

The reference's only compute-dense kernel is batched signature verification
(SURVEY.md §2.8 item 3); at committee scale (64-100 nodes, 100k tx/s input,
BASELINE.json configs) one chip is not enough. This module shards the
verification batch across a `jax.sharding.Mesh`:

  * axis "dp" — data parallel over the vote/signature batch. Each device
    verifies its shard; masks stay sharded; quorum counting rides ICI via
    `psum` collectives inside `shard_map` (never DCN — consensus/mempool
    control traffic stays host-side, SURVEY.md §5.8).
  * axis "qc" — independent QCs / payload batches verified concurrently
    (one QC's votes never wait on another's), the committee-facing axis.

The reference's analogue is thread-level parallelism inside ed25519_dalek's
`verify_batch` (crypto/src/lib.rs:194-207); here the same SPMD shape is
expressed once with shard_map and compiled by XLA for any mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..ops import ed25519 as ed


def shard_map(f, mesh, in_specs, out_specs):
    """`jax.shard_map` without the varying-manual-axes check (the kernels
    mix replicated constants with sharded lanes freely)."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def default_mesh(n_devices: int | None = None, axis: str = "dp") -> Mesh:
    """1-D data-parallel mesh over the available devices."""
    devs = jax.devices()[: n_devices or len(jax.devices())]
    return Mesh(np.array(devs), (axis,))


def init_multihost(
    coordinator: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> Mesh:
    """Initialize the multi-host crypto plane and return the global mesh.

    The reference scales its committee across hosts with one process per
    node and NO cross-host accelerator fabric; here the CRYPTO plane can
    additionally span hosts: each sidecar process calls this once, JAX's
    distributed runtime forms the global device set (ICI within a slice,
    DCN across slices), and the returned 1-D "dp" mesh shards verification
    batches over every chip in the job (`sharded_verify_fn`). Consensus/
    mempool control traffic stays on host-side TCP (SURVEY §5.8) — only
    the batch-verification collectives ride the accelerator fabric.

    Args default from the standard JAX env (JAX_COORDINATOR_ADDRESS etc.)
    when None; single-process callers can skip this entirely and use
    `default_mesh()`.
    """
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    return default_mesh()


def mesh_2d(n_qc: int, n_dp: int, devices=None) -> Mesh:
    """(qc, dp) mesh: independent QC batches x vote data-parallel."""
    devs = np.array(devices if devices is not None else jax.devices())
    assert devs.size >= n_qc * n_dp, "not enough devices for mesh"
    return Mesh(devs[: n_qc * n_dp].reshape(n_qc, n_dp), ("qc", "dp"))


def _kernel_fn(kernel: str):
    if kernel == "pallas":
        from ..ops.pallas_ladder import _verify_kernel_pallas

        return _verify_kernel_pallas
    return ed._verify_kernel_w4 if kernel == "w4" else ed._verify_kernel


def sharded_verify_fn(mesh: Mesh, dp_axis: str = "dp", kernel: str = "w4"):
    """Jitted (a_y, a_sign, r_enc, s_scalars, h_scalars) -> (mask, n_valid).

    Inputs are sharded over the batch (lane) dimension on `dp_axis`; each
    device runs the full ladder on its shard; n_valid is an ICI psum.
    """
    batch_spec = P(None, dp_axis)
    flat_spec = P(dp_axis)
    base_kernel = _kernel_fn(kernel)

    def local(a_y, a_sign, r_enc, s_scalars, h_scalars):
        mask = base_kernel(a_y, a_sign, r_enc, s_scalars, h_scalars)
        n_valid = jax.lax.psum(
            jnp.sum(mask.astype(jnp.int32)), axis_name=dp_axis
        )
        return mask, n_valid

    mapped = shard_map(
        local,
        mesh=mesh,
        in_specs=(batch_spec, flat_spec, batch_spec, batch_spec, batch_spec),
        out_specs=(flat_spec, P()),
    )
    return jax.jit(mapped)


def sharded_qc_verify_fn(mesh: Mesh):
    """Two-axis QC verification over a (qc, dp) mesh.

    Inputs carry a leading QC dimension: shapes (Q, 32, B), (Q, B), ... .
    Q shards over "qc", the vote batch over "dp". Returns per-QC valid-vote
    counts (Q,) — the quorum-side reduction (`Aggregator::append`'s
    weight-sum, consensus/src/aggregator.rs:78-94) as a dp-axis psum.
    """

    def local(a_y, a_sign, r_enc, s_scalars, h_scalars, s_ok):
        # vmap the single-QC kernel over this shard's QC slice
        mask = jax.vmap(ed._verify_kernel_w4)(
            a_y, a_sign, r_enc, s_scalars, h_scalars
        )
        mask = mask & s_ok  # host-checked s < L canonicality (malleability)
        counts = jax.lax.psum(
            jnp.sum(mask.astype(jnp.int32), axis=1), axis_name="dp"
        )
        return mask, counts

    spec_limb = P("qc", None, "dp")
    spec_flat = P("qc", "dp")
    mapped = shard_map(
        local,
        mesh=mesh,
        in_specs=(
            spec_limb,
            spec_flat,
            spec_limb,
            spec_limb,
            spec_limb,
            spec_flat,
        ),
        out_specs=(spec_flat, P("qc")),
    )
    return jax.jit(mapped)


def sharded_packed_fn(
    mesh: Mesh,
    dp_axis: str = "dp",
    kernel: str = "w4",
    device_hash: bool = False,
):
    """Jitted (128, B) u8 packed wire array -> (B,) bool, batch sharded on
    `dp_axis`. Each device unpacks and verifies its shard — the SAME 6x-
    smaller wire format and unpack-on-device recipe as the single-chip
    packed path (`ed._verify_kernel_w4_packed128`), so the pipelined
    uploader and bucketing machinery work unchanged over a mesh. With
    `device_hash`, rows 96-127 carry 32-byte messages and each device also
    computes h = SHA-512(R||A||M) mod L for its shard (ops.sha512)."""
    if kernel == "pallas":
        from ..ops import pallas_ladder as pl_mod

        base = (
            pl_mod._verify_kernel_pallas_packed128_dh
            if device_hash
            else pl_mod._verify_kernel_pallas_packed128
        )
    else:
        base = (
            ed._verify_kernel_w4_packed128_dh
            if device_hash
            else ed._verify_kernel_w4_packed128
        )

    mapped = shard_map(
        base, mesh=mesh, in_specs=P(None, dp_axis), out_specs=P(dp_axis)
    )
    return jax.jit(mapped)


def sharded_committee_fn(mesh: Mesh, dp_axis: str = "dp", device_hash: bool = False):
    """Committee-resident verification over the mesh.

    The `CommitteeTable` arrays ride as REPLICATED operands (`P()` specs —
    one device-resident copy per chip, pushed once at registration by
    `ShardedEd25519Verifier.set_committee`); the (96, B) u8 wire rows and
    (B,) i32 validator indices shard on `dp_axis`. Each device gathers its
    lanes' precomputed -A window tables from its local replica — the
    multi-chip steady state performs zero per-batch decompressions or table
    builds, exactly like the single-chip committee path. With `device_hash`
    the replicated committee `keys_u8` gather feeds the on-device SHA-512
    (rows 64-95 carry 32-byte messages instead of host-computed h)."""
    base = (
        ed._verify_kernel_w4_committee_packed96_dh
        if device_hash
        else ed._verify_kernel_w4_committee_packed96
    )
    # (ta_ypx, ta_ymx, ta_xy2d, valid[, keys_u8]) replicated, then idx + wire
    table_specs = (P(),) * (5 if device_hash else 4)
    mapped = shard_map(
        base,
        mesh=mesh,
        in_specs=(*table_specs, P(dp_axis), P(None, dp_axis)),
        out_specs=P(dp_axis),
    )
    return jax.jit(mapped)


class ShardedEd25519Verifier(ed.Ed25519TpuVerifier):
    """Drop-in Ed25519TpuVerifier that shards batches over a mesh.

    Uses the packed (128 B/signature) wire format and the base class's
    owned DispatchPipeline (ops/pipeline.py: bounded in-flight window,
    pooled staging buffers, streamed per-chunk readback — single-process
    meshes only; a multi-process mesh forces the serial depth=1 window,
    see __init__); chunks are device_put with an explicit batch-axis
    NamedSharding so the transfer lands sharded (no device-0 staging +
    reshard). `packed=False` restores the f32-argument
    `sharded_verify_fn` path (used by the legacy bit-ladder kernel).

    The committee-resident path (`set_committee` /
    `verify_batch_mask_committee`) is first-class: registration pushes one
    replicated copy of the `CommitteeTable` arrays to every chip, and the
    committee kernels are shard_map-wrapped with the tables as replicated
    operands while the 96 B wire rows + 4 B indices shard on the dp axis —
    multi-chip deployments inherit the single-chip zero-decompression
    steady state, with the same snapshot-pinned reconfig-safety contract
    (an epoch re-registration never swaps tables under in-flight chunks)."""

    def __init__(self, mesh: Mesh | None = None, **kw):
        super().__init__(**kw)
        self.mesh = mesh or default_mesh()
        self._ndev = int(np.prod([self.mesh.shape[a] for a in self.mesh.axis_names]))
        me = jax.process_index()
        self._multiprocess = any(
            d.process_index != me for d in np.asarray(self.mesh.devices).flat
        )
        if self._multiprocess:
            # Streamed per-chunk readback is an ALLGATHER on a multi-
            # process mesh, and the pipeline's readback worker would race
            # its collective launches against the upload worker's kernel
            # launches — every process must issue collectives in one
            # global order, so the deeper window is single-process-only.
            # depth=1 keeps the launch order (dispatch k, dispatch k+1,
            # ...) identical on every process, and DEFERRED readback
            # restores the pre-pipeline multihost shape: every chunk's
            # dispatch is queued async (compute still overlaps later
            # chunks' staging), then ONE end-of-batch allgather
            # materializes all masks — per-transfer latency is paid
            # once, not per chunk.
            self.pipeline.set_depth(1)
            self._defer_readback = True
        # per-device shard keeps full lanes (and pallas BLOCK alignment)
        lane = 128
        if self.kernel == "pallas":
            from ..ops.pallas_ladder import BLOCK

            lane = BLOCK
        # Every bucket must stay a multiple of lane*ndev: shard_map splits
        # the batch axis evenly across devices, and each per-device shard
        # must keep full lanes (pallas additionally needs BLOCK-aligned
        # shards). min_bucket rounds UP to the alignment grid (a plain max
        # would let an off-grid user value through); max_bucket rounds down
        # (e.g. 3 devices: doubling 384 overshoots a 8192 cap that 384 does
        # not divide). `mesh_alignment` is the published floor — TpuBackend
        # scales the committee crossover with it so sub-alignment quorum
        # batches route to host CPU instead of padding up to a full mesh
        # bucket.
        align = lane * self._ndev
        self.mesh_alignment = align
        self.min_bucket = -(-max(self.min_bucket, align) // align) * align
        self.max_bucket = max(align, self.max_bucket // align * align)
        self.chunk = min(self.chunk, self.max_bucket)
        dp = self.mesh.axis_names[0]
        from jax.sharding import NamedSharding

        # Three placement lanes: batch-axis sharded 2-D wire arrays,
        # sharded 1-D lane vectors (committee indices), and fully
        # replicated arrays (committee tables — one copy per chip).
        self._put = functools.partial(
            jax.device_put, device=NamedSharding(self.mesh, P(None, dp))
        )
        self._put_lanes = functools.partial(
            jax.device_put, device=NamedSharding(self.mesh, P(dp))
        )
        self._replicate = functools.partial(
            jax.device_put, device=NamedSharding(self.mesh, P())
        )
        self._sharded_committee = sharded_committee_fn(self.mesh, dp)
        self._sharded_committee_dh = sharded_committee_fn(
            self.mesh, dp, device_hash=True
        )
        if self.packed:
            self._sharded_packed = sharded_packed_fn(self.mesh, dp, self.kernel)
            self._sharded_packed_dh = sharded_packed_fn(
                self.mesh, dp, self.kernel, device_hash=True
            )
        else:
            self._fn = sharded_verify_fn(self.mesh, dp, self.kernel)

    def _packed_fn(self):
        return self._sharded_packed

    def _packed_dh_fn(self):
        return self._sharded_packed_dh

    def _build_committee_table(self, keys):
        """Registration-time replication: every chip in the mesh gets its
        own device-resident copy of the window tables / validity mask /
        key bytes, so the sharded committee kernels consume them as
        replicated shard_map operands with zero per-batch movement."""
        return ed.CommitteeTable(keys, put=self._replicate)

    def _upload_dispatch_committee(self, ct, packed, idx, device_hash, tlkey):
        """Uploader-thread leg of the committee path over the mesh: the
        (96, W) wire rows and (W,) index vector land SHARDED on the dp axis
        (no device-0 staging + reshard) and dispatch against the PINNED
        replicated tables of `ct` — a concurrent epoch re-registration must
        not swap replicas under in-flight sharded chunks. `tlkey` threads
        the chunk's device-timeline key (ops/timeline.py) through, same as
        the single-chip leg."""
        tl = ed.timeline
        with tl.span("upload", *tlkey, hist=ed._M_UPLOAD):
            dev_p = self._put(packed)
            dev_i = self._put_lanes(idx)
        with tl.span("dispatch", *tlkey, hist=ed._M_DISPATCH):
            if device_hash:
                return self._sharded_committee_dh(
                    ct.ta_ypx,
                    ct.ta_ymx,
                    ct.ta_xy2d,
                    ct.valid,
                    ct.keys_u8,
                    dev_i,
                    dev_p,
                )
            return self._sharded_committee(
                ct.ta_ypx, ct.ta_ymx, ct.ta_xy2d, ct.valid, dev_i, dev_p
            )

    def _materialize(self, masks) -> np.ndarray:
        """Multi-host mesh: the mask is sharded across PROCESSES, so a
        plain np.asarray raises ('spans non-addressable devices'); gather
        the global value first. Every process calls verify_batch_mask
        with the same inputs (SPMD) and the multi-process window runs
        depth=1 with DEFERRED readback (__init__), so this allgather is
        reached once per batch in the same order on every process —
        collective-safe."""
        full = masks[0] if len(masks) == 1 else jnp.concatenate(masks)
        if self._multiprocess:
            from jax.experimental import multihost_utils

            # Called ONCE per batch (`_defer_readback` batches every
            # chunk handle into this single allgather); every process
            # reaches it in the same SPMD order — collective-safe.
            return np.asarray(
                multihost_utils.process_allgather(full, tiled=True)
            )
        return np.asarray(full)

    def _run_chunk(self, messages, keys, signatures) -> np.ndarray:
        n = len(messages)
        staged = ed.prepare_batch(
            messages, keys, signatures, want_bits=self.kernel == "bits"
        )
        width = self._bucket(n)
        ed._M_PAD_LANES.inc(width - n)
        mask, _ = self._fn(*ed.kernel_args(staged, width, self.kernel))
        return self._materialize([mask])[:n] & staged["s_ok"]
