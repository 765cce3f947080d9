"""Pallas TPU kernel: pairwise Pareto-domination matrix for NSGA-II.

Non-dominated sorting needs, every generation, the P x P boolean matrix
  dom[i, j] = (f(i) <= f(j) elementwise) and (f(i) < f(j) somewhere).
For the paper's two objectives (wirelength^2, max bbox) this unrolls to four
broadcast compares per tile.  Objectives arrive as two row/column vectors so
tiles are rank-2 (BI, 1) x (1, BJ) -> (BI, BJ) int8 -- a pure-VPU outer
product walk over the population grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import _padding as P

BI, BJ = 128, 128


def _kernel(a0, a1, b0, b1, o_ref):
    ra0, ra1 = a0[...], a1[...]          # (BI, 1)
    cb0, cb1 = b0[...], b1[...]          # (1, BJ)
    le = (ra0 <= cb0) & (ra1 <= cb1)
    lt = (ra0 < cb0) | (ra1 < cb1)
    o_ref[...] = (le & lt).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def domination_pallas(objs: jnp.ndarray, interpret: bool = False
                      ) -> jnp.ndarray:
    """objs: [P, 2] fp32 -> int8 [P, P]; out[i,j]=1 iff i dominates j."""
    p = objs.shape[0]
    # +inf rows dominate nothing; padded cols are sliced off
    o = P.pad_objs_inf(objs, BI)
    o0r = o[:, 0:1]                       # [P, 1]
    o1r = o[:, 1:2]
    o0c = o[:, 0].reshape(1, -1)          # [1, P]
    o1c = o[:, 1].reshape(1, -1)
    n = o.shape[0]
    grid = (n // BI, n // BJ)
    out = pl.pallas_call(
        _kernel,
        name="domination_pallas",
        grid=grid,
        in_specs=[
            pl.BlockSpec((BI, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((BI, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, BJ), lambda i, j: (0, j)),
            pl.BlockSpec((1, BJ), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((BI, BJ), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, n), jnp.int8),
        interpret=interpret,
    )(o0r, o1r, o0c, o1c)
    return out[:p, :p]
