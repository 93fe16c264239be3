"""Test env: force JAX onto a virtual 8-device CPU mesh BEFORE jax imports.

Multi-chip shardings are validated on this virtual mesh; the four-chip
path itself runs through `chip_smoke.py --chips 4`.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tests always run on eight virtual CPU devices, even with a chip attached:
# mesh and sharding logic is checked on the virtual mesh, the jnp `w4`
# kernel stands in for the Pallas one (which has no CPU lowering), and the
# chip itself is exercised by chip_smoke.py, one process at a time.
import jax

jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache: the w4/committee ladder kernels take
# minutes each to compile on the CPU backend; repeat test runs on the same
# host hit the on-disk cache instead.
from hotstuff_tpu.ops import enable_persistent_cache

enable_persistent_cache()

import asyncio

import pytest


@pytest.fixture
def run_async():
    """Run an async test body in a fresh event loop."""

    def _run(coro, timeout=60.0):
        return asyncio.run(asyncio.wait_for(coro, timeout))

    return _run


_PORT_COUNTER = [0]


@pytest.fixture
def base_port():
    """Per-test port offset to avoid collisions, mirroring the reference's
    increment_base_port (consensus/src/tests/common.rs:34-41)."""
    _PORT_COUNTER[0] += 40
    return 11_000 + (os.getpid() % 500) * 50 + _PORT_COUNTER[0]
