"""Pallas TPU kernel: population-batched squared-wirelength reduction.

The EA's hot loop evaluates Eq. 1 for a whole population every generation:
given gathered per-net endpoint coordinates [P, N] (population x nets), fuse

    dl = (|x1-x2| + |y1-y2|) * w ;  out[p] = sum_n dl^2

into one VMEM-tiled pass -- no [P, N] intermediate ever hits HBM.  The grid
walks (population tiles, net tiles); the net axis is innermost so each output
tile is revisited and accumulated in place (TPU sequential-grid guarantee).

Tiling: BP x BN = 8 x 512 fp32 tiles -> 5 inputs * 16 KiB = 80 KiB VMEM per
step, MXU-free pure-VPU workload, lane dim 512 = 4x128 registers.  The
output is a lane-dense (BP, 128) tile per population block (the TPU lowering
refuses a rank-1 (BP,) block): every lane holds the row's sum and the
wrapper keeps lane 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import _padding as P

BP, BN = 8, 512
LANES = 128       # lane-dense output tile width


def _kernel(x1, y1, x2, y2, w, o_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    dl = (jnp.abs(x1[...] - x2[...]) + jnp.abs(y1[...] - y2[...])) * w[...]
    dl = dl.astype(jnp.float32)
    o_ref[...] += jnp.sum(dl * dl, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wirelength2_pallas(x1: jnp.ndarray, y1: jnp.ndarray, x2: jnp.ndarray,
                       y2: jnp.ndarray, w: jnp.ndarray,
                       interpret: bool = False) -> jnp.ndarray:
    """x*, y*, w: [P, N] -> [P] fp32.  Pads internally; w==0 on padding."""
    p, n = x1.shape
    x1, y1, x2, y2, w = P.pad_net_endpoints(x1, y1, x2, y2, w, BN)
    x1, y1, x2, y2, w = (P.pad_pop(a, BP) for a in (x1, y1, x2, y2, w))
    pp, pn = x1.shape[0] - p, x1.shape[1] - n
    grid = ((p + pp) // BP, (n + pn) // BN)
    spec = pl.BlockSpec((BP, BN), lambda i, j: (i, j))
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[spec] * 5,
        out_specs=pl.BlockSpec((BP, LANES), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((p + pp, LANES), jnp.float32),
        interpret=interpret,
    )(x1, y1, x2, y2, w)
    return out[:p, 0]
