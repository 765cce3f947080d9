"""Padding contracts shared by the placement evaluation kernels.

Every Pallas kernel in this package tiles fixed (sublane x lane) blocks over
inputs whose real extents are arbitrary, so each wrapper pads up to tile
multiples.  The padding must be *neutral under the kernel's reduction* --
a padded element contributing anything would silently corrupt results for
exactly the shapes that cross a tile boundary.  These helpers centralise
the contracts (re-exported by `kernels.ops`, unit-tested directly in
`tests/test_fused_eval.py`):

  * **nets** (weighted-sum reduction, Eq. 1): padded nets carry ``w == 0``
    so their squared length contributes 0.  Endpoint *values* pad with
    zeros (`pad_net_endpoints`); endpoint *indices* pad with gid 0
    (`pad_net_indices`) -- any in-range gid is safe once the weight is 0.
  * **units / blocks** (min/max reduction, Eq. 2): padded blocks replicate
    a real block of their unit (neutral under min/max); padded units
    replicate a real unit -- or, in the fused gather layout
    (`pad_unit_index`), point every block at gid 0, a degenerate unit of
    bbox exactly 0, neutral under the final max because every real bbox
    is >= 0.
  * **population rows** (batch axis): padded rows compute garbage that the
    wrapper slices off; zeros keep the arithmetic finite.
  * **domination rows** (`pad_objs_inf`): padded candidates sit at +inf on
    every objective, so they dominate nothing (their matrix columns are
    sliced off; their matrix rows and count contributions are all zero).
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp


def pad_multiple(a: jnp.ndarray, axis: int, mult: int,
                 mode: str = "zero") -> jnp.ndarray:
    """Pad `axis` of `a` up to the next multiple of `mult`.

    mode="zero" appends zeros (for padding sliced off or weighted out);
    mode="edge" replicates the boundary element (neutral under min/max).
    """
    extra = -a.shape[axis] % mult
    if extra == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, extra)
    if mode == "edge":
        return jnp.pad(a, widths, mode="edge")
    return jnp.pad(a, widths)


def pad_pop(a: jnp.ndarray, bp: int) -> jnp.ndarray:
    """Zero-pad the leading population/batch axis; callers slice off the
    padded rows, so their (finite) garbage is never observed."""
    return pad_multiple(a, 0, bp, mode="zero")


def pad_net_endpoints(x1, y1, x2, y2, w, bn: int
                      ) -> Tuple[jnp.ndarray, ...]:
    """Pad the net axis (last) of endpoint-value arrays to a `bn` multiple.

    Contract: padded nets have weight 0, so ``((|dx|+|dy|) * w)^2 == 0``
    regardless of the (zero) coordinates -- neutral under the Eq. 1 sum.
    """
    return (pad_multiple(x1, -1, bn), pad_multiple(y1, -1, bn),
            pad_multiple(x2, -1, bn), pad_multiple(y2, -1, bn),
            pad_multiple(w, -1, bn))


def pad_net_indices(src, dst, w, bn: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pad gather-index nets for the fused layout: indices to gid 0 (any
    in-range gid is safe), weights to 0 (the neutrality guarantee)."""
    return (pad_multiple(src, -1, bn), pad_multiple(dst, -1, bn),
            pad_multiple(w, -1, bn))


def pad_unit_blocks(ux, uy, bb: int, bu: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Replicate-pad [..., B, U] unit-grouped coordinates (bbox layout).

    Padded blocks (axis -2) replicate the boundary block of their unit --
    neutral under per-unit min/max; padded units (axis -1) replicate the
    boundary unit, whose bbox is a real unit's bbox -- neutral under the
    final max."""
    ux = pad_multiple(pad_multiple(ux, -2, bb, "edge"), -1, bu, "edge")
    uy = pad_multiple(pad_multiple(uy, -2, bb, "edge"), -1, bu, "edge")
    return ux, uy


def pad_unit_index(uidx: jnp.ndarray, bu: int, bb: int = 8) -> jnp.ndarray:
    """Pad a [U, B] unit gather table for the fused layout.

    Padded blocks (axis 1) replicate the unit's last block (duplicate
    coordinates never move a min/max); padded units (axis 0) point every
    block at gid 0 -- a degenerate unit whose bbox is exactly 0, neutral
    under the final max because every real bbox is >= 0.
    """
    uidx = pad_multiple(uidx, 1, bb, mode="edge")
    return pad_multiple(uidx, 0, bu, mode="zero")


def pad_objs_inf(objs: jnp.ndarray, bi: int) -> jnp.ndarray:
    """Pad a [P, M] objective table with +inf rows for the domination
    kernels: a +inf candidate is dominated by everything and dominates
    nothing, so padded rows add 0 to every dominated-by count."""
    return jnp.pad(objs.astype(jnp.float32),
                   ((0, -objs.shape[0] % bi), (0, 0)),
                   constant_values=jnp.inf)
