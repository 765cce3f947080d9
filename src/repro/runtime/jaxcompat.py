"""One home for the mesh, shard_map and axis-name collective calls.

Every shard_mapped caller (core.islands ring migration, evolve.run_islands,
launch.mesh) goes through `shard_map`, `make_mesh` and the uniform
axis-name API below (`axis_index` / `axis_size` / `psum` / `ppermute` /
`all_gather`, plus `ring_perm` for the canonical champion-ring
permutation), so a jax API change is a one-file change.
"""
from __future__ import annotations

from typing import List, Tuple

import jax


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, check_vma=False,
                         in_specs=in_specs, out_specs=out_specs)


def make_mesh(shape, names) -> jax.sharding.Mesh:
    """`jax.make_mesh` with Auto axis types."""
    return jax.make_mesh(
        shape, names, axis_types=(jax.sharding.AxisType.Auto,) * len(names))


# ------------------------------------------------ axis-name collectives
#
# Thin wrappers: callers never import jax.lax collectives directly, so a
# future rename stays a one-file change.

def axis_index(axis: str) -> jax.Array:
    """This shard's index along a shard_map/pmap axis name."""
    return jax.lax.axis_index(axis)


def axis_size(axis: str) -> int:
    """Number of shards along an axis name."""
    return jax.lax.axis_size(axis)


def psum(x, axis: str):
    """Sum `x` across all shards of an axis name."""
    return jax.lax.psum(x, axis_name=axis)


def ppermute(x, axis: str, perm: List[Tuple[int, int]]):
    """Send `x` along (source, destination) pairs over an axis name."""
    return jax.lax.ppermute(x, axis_name=axis, perm=perm)


def all_gather(x, axis, tiled: bool = False):
    """Gather `x` from every shard along one axis name (or a tuple of
    names, flattened into one leading dim)."""
    return jax.lax.all_gather(x, axis, tiled=tiled)


def ring_perm(n: int) -> List[Tuple[int, int]]:
    """The champion-ring permutation: shard i sends to shard (i+1) % n,
    so every receiver adopts its *left* neighbour's payload -- the same
    direction as `jnp.roll(x, 1, axis=0)` on an unsharded stack."""
    return [(i, (i + 1) % n) for i in range(n)]
