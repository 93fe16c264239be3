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
    batches over every chip in the job (`sharded_program`). Consensus/
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


@functools.lru_cache(maxsize=None)
def sharded_program(mesh: Mesh, name: str, dp_axis: str = "dp"):
    """The verify program `name` (a key of `ed.KERNELS` or of
    `pallas_ladder.KERNELS`) over the mesh, jitted: same operands, same
    (B,) bool mask, the batch sharded on `dp_axis`.

    Generic family: the (128, B) u8 wire array shards on its lane axis and
    each device unpacks and verifies its shard — the SAME wire format and
    unpack-on-device recipe as on one chip, so the chunk loop, the pooled
    buffers and the bucketing work unchanged over a mesh; with device hash
    each device also computes h = SHA-512(R||A||M) mod L for its shard
    (ops.sha512).

    Committee family (`w4c96*`): the `CommitteeTable` arrays ride as
    REPLICATED operands (`P()` specs — one device-resident copy per chip,
    pushed once at registration by `ShardedEd25519Verifier.set_committee`);
    the (B,) i32 validator indices and the (96, B) u8 wire rows shard on
    `dp_axis`. Each device gathers its lanes' precomputed -A window tables
    from its local replica — the multi-chip steady state performs zero
    per-batch decompressions or table builds, exactly like the single-chip
    committee path. With device hash the replicated committee `keys_u8`
    gather feeds the on-device SHA-512 (rows 64-95 carry 32-byte messages
    instead of host-computed h).

    One callable per (mesh, name): verifiers over one mesh share it, as
    one-chip verifiers share the modules' PROGRAMS."""
    if name in ed.KERNELS:
        base = ed.KERNELS[name]
    else:
        from ..ops import pallas_ladder

        base = pallas_ladder.KERNELS[name]
    in_specs = P(None, dp_axis)
    if name.startswith("w4c96"):
        # (ta_ypx, ta_ymx, ta_xy2d, valid[, keys_u8]) replicated, then idx + wire
        tables = (P(),) * (5 if name.endswith("dh") else 4)
        in_specs = (*tables, P(dp_axis), in_specs)
    mapped = shard_map(
        base, mesh=mesh, in_specs=in_specs, out_specs=P(dp_axis)
    )
    return jax.jit(mapped)


class ShardedEd25519Verifier(ed.Ed25519TpuVerifier):
    """Drop-in Ed25519TpuVerifier that shards batches over a mesh.

    It changes WHERE arrays land and WHICH callables the program table
    holds, and nothing of dispatch: the base class's one chunk loop and
    owned DispatchPipeline (ops/pipeline.py: bounded in-flight window,
    pooled staging buffers, streamed per-chunk readback — single-process
    meshes only; a multi-process mesh forces the serial depth=1 window,
    see __init__) run as on one chip. `programs` holds the same four names
    (`program_name`; `kernel` is "w4" or "pallas") `shard_map`-wrapped
    (`sharded_program`); the placement hooks device_put a chunk's wire
    array and lane vector with an explicit batch-axis NamedSharding so the
    transfer lands sharded (no device-0 staging + reshard).

    The committee-resident path (`set_committee` /
    `verify_batch_mask_committee`) is first-class: registration pushes one
    replicated copy of the `CommitteeTable` arrays to every chip
    (`_replicate`), and the committee programs take the tables
    as replicated operands while the 96 B wire rows + 4 B indices shard on
    the dp axis — multi-chip deployments inherit the single-chip
    zero-decompression steady state, with the same snapshot-pinned
    reconfig-safety contract (an epoch re-registration never swaps tables
    under in-flight chunks)."""

    def __init__(self, mesh: Mesh | None = None, **kw):
        # set first: the base class builds the program table over it
        self.mesh = mesh or default_mesh()
        super().__init__(**kw)
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

    def _program_table(self) -> dict:
        dp = self.mesh.axis_names[0]
        return {
            name: sharded_program(self.mesh, name, dp)
            for name in super()._program_table()
        }

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
