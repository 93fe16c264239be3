"""TPU compute kernels: GF(2^255-19) limb arithmetic and batched ed25519
verification (the reference crypto hot path, crypto/src/lib.rs:194-220,
rebuilt as JAX SPMD kernels).

The jax-backed submodules (`field`, `ed25519`, ...) load LAZILY (PEP 562):
`hotstuff_tpu.ops.timeline` (device-occupancy timeline) and
`hotstuff_tpu.ops.pipeline` (async dispatch pipeline) are
dependency-free, and the telemetry plane,
chaos runner, and the graftlint tool import them on hosts with no jax
at all. `from hotstuff_tpu.ops import ed25519 as ed` still works unchanged
(submodule imports bypass this shim); only attribute access on the package
goes through __getattr__.
"""

import os

from . import pipeline, timeline  # dependency-free; eager on purpose

__all__ = [
    "field",
    "ed25519",
    "bls",
    "pipeline",
    "timeline",
    "Ed25519TpuVerifier",
    "prepare_batch",
    "prepare_batch_packed",
    "enable_persistent_cache",
    "cpu_requested",
]

# Package attributes resolved lazily so `import hotstuff_tpu.ops` (and the
# timeline/telemetry modules) never pull jax.
_LAZY_MODULES = ("field", "field12", "ed25519", "sha512", "pallas_ladder", "bls")
_LAZY_ED25519 = ("Ed25519TpuVerifier", "prepare_batch", "prepare_batch_packed")


def __getattr__(name: str):
    if name in _LAZY_MODULES:
        import importlib

        return importlib.import_module(f".{name}", __name__)
    if name in _LAZY_ED25519:
        from . import ed25519

        return getattr(ed25519, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# The one in-checkout compile-cache directory (listed in .gitignore). A
# directory that moves between runs never hits, so it is fixed: never
# derived from a pid, a time or tempfile.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_cache() -> str:
    """Persistent XLA compilation cache: each verifier bucket width is a
    separate jit specialisation and one whole verify program takes minutes
    to compile for the chip, so every process that dispatches one shares
    the on-disk cache. Call once per process that uses JAX.

    Where `JAX_COMPILATION_CACHE_DIR` is set JAX reads it itself and no
    directory is set in code; otherwise the cache is `CACHE_DIR` inside the
    checkout. Returns the directory in use."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def cpu_requested() -> bool:
    """True when the process was TOLD to run JAX on the CPU: `JAX_PLATFORMS`
    names `cpu` (the tests' commands), or the config was set so after import
    (tests/conftest.py). A device entry point that finds no chip carries on
    only then; otherwise it fails rather than pass CPU work for the chip's."""
    import jax

    return "cpu" in str(jax.config.jax_platforms or "").split(",")
