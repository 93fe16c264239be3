"""Compile rehearsal for the chip this repo targets (one TPU v5e, or the
2x2 host of four), run WITHOUT the chip.

The TPU compiler is installed alongside jax and compiles for a topology
that is described, not attached. Nothing here runs a kernel: a test that
passes says the chip's compiler accepts the program (tiling, VMEM budget,
partitioning), not that the result is right — `chip_smoke.py` is the
check that runs.

This is the ONLY file that describes a chip. The topology is described
inside a module-scoped fixture (never at import, never in conftest.py):
only one process may hold libtpu, so under pytest-xdist exactly the
worker that is handed this file loads it. Code that asks
`jax.default_backend()` sees the CPU here, so the tests compile the
jitted kernels themselves with shapes placed on the described devices.

Tier-1 holds the kernels (seconds each); the whole verify programs take
minutes each to compile and are marked `slow`:

    JAX_PLATFORMS=cpu python -m pytest tests/test_chip_compile.py -m slow -s
"""

import functools
import os
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

# The width the sidecar and chip_smoke.py dispatch (--min-bucket 4096,
# chunk 4096 -> one generic program) and the smoke's committee size.
SERVED_WIDTH = 4096
COMMITTEE = 64


@pytest.fixture(scope="module")
def topo():
    """The described v5e 2x2 host. A compile for a described chip is
    written to the persistent cache but cannot be read back without the
    chip (the next run warns and recompiles), so the cache is off while
    this module's tests run."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _ladder_shapes(width: int, sharding):
    """`ladder_pallas`'s arguments: both digit arrays, then -x and y of the
    decompressed key (its -A table is built inside the kernel)."""
    f32 = jnp.float32
    digits = jax.ShapeDtypeStruct((64, width), f32, sharding=sharding)
    element = jax.ShapeDtypeStruct((32, width), f32, sharding=sharding)
    return (digits, digits, element, element)


def _compile(name: str, fn, *shapes):
    """Compile for the described chip; print seconds and sizes (-s)."""
    t0 = time.perf_counter()
    compiled = fn.lower(*shapes).compile()
    secs = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    print(
        f"\n[chip-compile] {name}: {secs:.1f} s, "
        f"code {mem.generated_code_size_in_bytes / 1e6:.1f} MB, "
        f"temp {mem.temp_size_in_bytes / 1e6:.1f} MB, "
        f"args {mem.argument_size_in_bytes / 1e6:.1f} MB, "
        f"out {mem.output_size_in_bytes / 1e6:.1f} MB"
    )
    return compiled


# --- tier-1: the kernels --------------------------------------------------


@pytest.mark.parametrize("width", ["BLOCK", SERVED_WIDTH])
def test_ladder_pallas_compiles_for_v5e(one_chip, width):
    """The Pallas ladder at one grid program and at the served width."""
    from hotstuff_tpu.ops import pallas_ladder

    if width == "BLOCK":
        width = pallas_ladder.LADDER_BLOCK
    compiled = _compile(
        f"ladder_pallas@{width}",
        pallas_ladder.ladder_pallas,
        *_ladder_shapes(width, one_chip),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tail", ["invert", "pow2523"])
@pytest.mark.parametrize("width", ["BLOCK", SERVED_WIDTH])
def test_chain_pallas_compiles_for_v5e(one_chip, width, tail):
    """The fixed-exponent chain kernel (decompress's square root, compress's
    inversion) at one grid program and at the served width."""
    from hotstuff_tpu.ops import pallas_ladder

    if width == "BLOCK":
        width = pallas_ladder.BLOCK
    compiled = _compile(
        f"chain_pallas {tail}@{width}",
        jax.jit(functools.partial(pallas_ladder.chain_pallas, tail=tail)),
        jax.ShapeDtypeStruct((32, width), jnp.float32, sharding=one_chip),
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "mesh_shape,axes",
    [((4,), ("dp",)), ((2, 2), ("qc", "dp"))],
    ids=["dp4", "qc2xdp2"],
)
def test_ladder_pallas_compiles_under_shard_map_on_2x2(topo, mesh_shape, axes):
    """The ladder inside `shard_map` over the four chips of the 2x2 host:
    the 1-D dp mesh is what `ShardedEd25519Verifier` builds
    (`default_mesh(4)`); the (qc, dp) mesh is `mesh_2d(2, 2)`."""
    from hotstuff_tpu.ops import pallas_ladder
    from hotstuff_tpu.parallel.mesh import shard_map

    mesh = Mesh(np.array(topo.devices[:4]).reshape(mesh_shape), axes)
    lanes = P(None, axes)  # (64 | 32, B): batch axis over every mesh axis
    fn = jax.jit(
        shard_map(
            pallas_ladder.ladder_pallas,
            mesh=mesh,
            in_specs=(lanes,) * 4,
            out_specs=(lanes,) * 4,
        )
    )
    width = 4 * pallas_ladder.LADDER_BLOCK  # one grid program per chip
    shapes = _ladder_shapes(width, NamedSharding(mesh, lanes))
    compiled = _compile(f"ladder_pallas shard_map {mesh_shape}", fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()
    # each chip holds a quarter of the lanes, not all of them
    per_dev = compiled.memory_analysis().argument_size_in_bytes
    full = (2 * 64 + 2 * 32) * width * 4
    assert per_dev == full // 4


def test_committee_gather_compiles_for_v5e(one_chip):
    """The committee family's distinguishing step: lanes gather their -A
    window tables from the device-resident (16, 32, N) precompute by
    validator index (`jnp.take` on the lane axis) — the smallest program
    that contains it; the whole committee program is in the slow set."""

    def gather(ta_ypx, ta_ymx, ta_xy2d, valid, keys_u8, idx):
        return (
            jnp.take(ta_ypx, idx, axis=2),
            jnp.take(ta_ymx, idx, axis=2),
            jnp.take(ta_xy2d, idx, axis=2),
            jnp.take(valid, idx),
            jnp.take(keys_u8, idx, axis=1),
        )

    compiled = _compile(
        f"committee gather N={COMMITTEE}@{SERVED_WIDTH}",
        jax.jit(gather),
        *_committee_table_shapes(one_chip),
        jax.ShapeDtypeStruct((SERVED_WIDTH,), jnp.int32, sharding=one_chip),
    )
    out = compiled.memory_analysis().output_size_in_bytes
    assert out >= 3 * 16 * 32 * SERVED_WIDTH * 4


def _committee_table_shapes(sharding):
    table = jax.ShapeDtypeStruct(
        (16, 32, COMMITTEE), jnp.float32, sharding=sharding
    )
    valid = jax.ShapeDtypeStruct((COMMITTEE,), jnp.bool_, sharding=sharding)
    keys = jax.ShapeDtypeStruct((32, COMMITTEE), jnp.uint8, sharding=sharding)
    return (table, table, table, valid, keys)


# --- slow: the whole programs chip_smoke.py dispatches ---------------------


@pytest.mark.slow
@pytest.mark.parametrize("program", ["pallas_p128dh", "pallas_p128", "w4c96dh"])
def test_whole_verify_program_compiles_for_v5e(one_chip, program):
    """The three whole programs the smoke dispatches, at its one width.
    Minutes each — run by hand before a chip call (-m slow -s prints the
    seconds and `memory_analysis()` sizes)."""
    from hotstuff_tpu.ops import ed25519 as ed
    from hotstuff_tpu.ops import pallas_ladder

    u8 = jnp.uint8
    fn = {**ed.PROGRAMS, **pallas_ladder.PROGRAMS}[program]
    if program == "w4c96dh":
        shapes = (
            *_committee_table_shapes(one_chip),
            jax.ShapeDtypeStruct((SERVED_WIDTH,), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((96, SERVED_WIDTH), u8, sharding=one_chip),
        )
    else:
        shapes = (
            jax.ShapeDtypeStruct((128, SERVED_WIDTH), u8, sharding=one_chip),
        )
    compiled = _compile(f"{program}@{SERVED_WIDTH}", fn, *shapes)
    assert ("tpu_custom_call" in compiled.as_text()) == (program != "w4c96dh")


@pytest.mark.slow
@pytest.mark.parametrize("program", ["pallas_p128dh", "w4c96dh"])
def test_sharded_verify_program_compiles_for_2x2(topo, program):
    """What `chip_smoke.py --chips 4` dispatches: the whole programs under
    `ShardedEd25519Verifier`'s wrappers on the 1-D dp mesh of the four
    chips, at the served width (1024 lanes per chip), tables replicated."""
    from hotstuff_tpu.parallel import mesh as pm

    mesh = Mesh(np.array(topo.devices[:4]), ("dp",))
    lanes = NamedSharding(mesh, P("dp"))
    wire = NamedSharding(mesh, P(None, "dp"))
    fn = pm.sharded_program(mesh, program, "dp")
    if program == "w4c96dh":
        shapes = (
            *_committee_table_shapes(NamedSharding(mesh, P())),
            jax.ShapeDtypeStruct((SERVED_WIDTH,), jnp.int32, sharding=lanes),
            jax.ShapeDtypeStruct((96, SERVED_WIDTH), jnp.uint8, sharding=wire),
        )
    else:
        shapes = (
            jax.ShapeDtypeStruct((128, SERVED_WIDTH), jnp.uint8, sharding=wire),
        )
    compiled = _compile(f"sharded {program}@{SERVED_WIDTH} on dp4", fn, *shapes)
    assert ("tpu_custom_call" in compiled.as_text()) == (program != "w4c96dh")
