"""Device-mesh parallelism: sharded batch verification over jax.sharding
meshes with ICI collectives (SURVEY.md §2.8, §5.7)."""

from .mesh import (
    ShardedEd25519Verifier,
    default_mesh,
    init_multihost,
    mesh_2d,
    sharded_program,
    sharded_qc_verify_fn,
)

__all__ = [
    "ShardedEd25519Verifier",
    "default_mesh",
    "init_multihost",
    "mesh_2d",
    "sharded_program",
    "sharded_qc_verify_fn",
]
