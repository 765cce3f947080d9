"""Pallas TPU kernel: population-batched max-bounding-box reduction (Eq. 2).

Input: block coordinates grouped per conv unit, laid out [P, B, U]
(population, blocks-per-unit on sublanes, units on lanes) so the unit axis --
the long one -- rides the 128-wide lane dimension.  Each grid step reduces a
(BP, B, BU) tile: min/max over the block axis, width+height per unit, max
over the unit tile, then max-accumulates into out[p].

The output is a lane-dense (BP, 128) tile per population block (the TPU
lowering refuses a rank-1 (BP,) block): every lane holds the row's max and
the wrapper keeps lane 0.

Padding contract (enforced by ops.py): padded *units* replicate a real
column of coordinates (bbox 0 -> neutral under max); padded *blocks*
replicate block 0 of their unit (neutral under min/max).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import _padding as P

BP, BU = 8, 128
LANES = 128       # lane-dense output tile width
NEG = -3.4e38


def _kernel(ux, uy, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, NEG)

    x = ux[...].astype(jnp.float32)
    y = uy[...].astype(jnp.float32)
    w = jnp.max(x, axis=1) - jnp.min(x, axis=1)       # [BP, BU]
    h = jnp.max(y, axis=1) - jnp.min(y, axis=1)
    o_ref[...] = jnp.maximum(o_ref[...],
                             jnp.max(w + h, axis=1, keepdims=True))


@functools.partial(jax.jit, static_argnames=("interpret",))
def maxbbox_pallas(ux: jnp.ndarray, uy: jnp.ndarray,
                   interpret: bool = False) -> jnp.ndarray:
    """ux, uy: [P, U, B] -> [P] fp32 max over units of (w + h)."""
    p, u, b = ux.shape
    # lay out as [P, B, U]; replicate-pad blocks to a sublane multiple
    ux = jnp.swapaxes(ux, 1, 2)
    uy = jnp.swapaxes(uy, 1, 2)
    ux, uy = P.pad_unit_blocks(ux, uy, 8, BU)
    # edge-pad the population rows too: replicated rows are sliced off
    ux = P.pad_multiple(ux, 0, BP, mode="edge")
    uy = P.pad_multiple(uy, 0, BP, mode="edge")
    pp, pu, bb = ux.shape[0] - p, ux.shape[2] - u, ux.shape[1] - b
    grid = ((p + pp) // BP, (u + pu) // BU)
    spec = pl.BlockSpec((BP, b + bb, BU), lambda i, j: (i, 0, j))
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec, spec],
        out_specs=pl.BlockSpec((BP, LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((p + pp, LANES), jnp.float32),
        interpret=interpret,
    )(ux, uy)
    return out[:p, 0]
