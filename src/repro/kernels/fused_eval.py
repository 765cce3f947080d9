"""Pallas TPU kernels: fused placement evaluation for the full service batch.

The separate `wirelength` / `bbox` kernels are two launches over the same
decoded coordinates, each with its own pass over the population.  This
kernel reduces both objectives in ONE launch over the stacked (slots x
islands x pop) batch:

    coords cx, cy : [P, G]   (population x gids, decode order)
    nets src, dst : [N] int32 gather indices into G, weights w : [N]
    units uidx    : [U, B] int32 gather table (block b of unit u -> gid)

The endpoint and unit gathers run in XLA ahead of the `pallas_call` (the
TPU kernel compiler has no lane gather), and the gathered tiles stream in
through `BlockSpec`s:

    x1, y1, x2, y2 : [P, N]     endpoint coordinates of every net
    ux, uy         : [P, B, U]  unit blocks, units on the 128-lane axis

    grid (i, j) = (population tiles, max(net tiles, unit tiles))
      step: wl[i] += sum_n ((|x1-x2| + |y1-y2|) * w)^2        (j < net tiles)
            bb[i]  = max(bb[i], max_u (max-min)x + (max-min)y) (j < unit tiles)

The j axis is innermost, so both output tiles are revisited on consecutive
grid steps (TPU sequential-grid accumulation guarantee); step j == 0
initialises wl to 0 and bb to -inf.  The shorter of the two walks clamps
its block index to its last tile (the pipeline does not re-fetch an
unchanged block) and skips the step under `pl.when`.  Outputs are
lane-dense (BP, 128) tiles -- every lane holds the row's value and the
wrapper keeps lane 0.  Padding follows `kernels._padding`: padded nets
carry w == 0, padded unit rows gather the degenerate gid-0 unit whose bbox
is exactly 0, padded blocks replicate their unit's last block.

A second kernel fuses the NSGA-II domination matrix with its column
reduction (dominated-by counts), saving the [P, P] int32 round-trip that
`nondominated_rank` otherwise pays before its peeling loop.

Like every kernel here, `ops.py` dispatches to the `ref.py` oracle off-TPU;
the tests run these bodies in interpret mode on CPU.
"""
from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import _padding as P

BP = 8          # population sublane tile
BN = 512        # nets per grid step (lane dim, 4x128)
BU = 128        # units per grid step (lane dim)
BB = 8          # blocks-per-unit padding multiple (sublane dim)
LANES = 128     # lane-dense output tile width
NEG = -3.4e38


def _eval_kernel(n_net, n_unit, x1, y1, x2, y2, w, ux, uy, wl_ref, bb_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        wl_ref[...] = jnp.zeros_like(wl_ref)
        bb_ref[...] = jnp.full_like(bb_ref, NEG)

    @pl.when(j < n_net)
    def _nets():                                     # Eq. 1 partial
        dl = (jnp.abs(x1[...] - x2[...])
              + jnp.abs(y1[...] - y2[...])) * w[...]  # padded nets: w == 0
        wl_ref[...] += jnp.sum(dl * dl, axis=1, keepdims=True)

    @pl.when(j < n_unit)
    def _units():                                    # Eq. 2 partial
        x, y = ux[...], uy[...]                      # [BP, Bp, BU]
        wd = jnp.max(x, axis=1) - jnp.min(x, axis=1)
        ht = jnp.max(y, axis=1) - jnp.min(y, axis=1)
        bb_ref[...] = jnp.maximum(
            bb_ref[...], jnp.max(wd + ht, axis=1, keepdims=True))


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_eval_pallas(cx: jnp.ndarray, cy: jnp.ndarray, src: jnp.ndarray,
                      dst: jnp.ndarray, w: jnp.ndarray, uidx: jnp.ndarray,
                      interpret: bool = False) -> jnp.ndarray:
    """cx, cy: [..., G]; src/dst/w: [N]; uidx: [U, B] -> [..., 2] fp32.

    Column 0 is wirelength^2 (Eq. 1), column 1 max bbox (Eq. 2).  Leading
    batch axes (slots x islands x pop) are flattened into one population
    axis -- the whole service batch is a single grid.
    """
    batch = cx.shape[:-1]
    g = cx.shape[-1]
    cx = P.pad_pop(cx.reshape(-1, g).astype(jnp.float32), BP)
    cy = P.pad_pop(cy.reshape(-1, g).astype(jnp.float32), BP)
    p = math.prod(batch)
    pp = cx.shape[0]

    src, dst, w = P.pad_net_indices(src, dst, w, BN)
    uidx = P.pad_unit_index(uidx, BU, bb=BB).astype(jnp.int32)
    n_net, n_unit = src.shape[-1] // BN, uidx.shape[0] // BU

    # gathers in XLA; the kernel streams the gathered tiles
    ends = [c[:, i] for c in (cx, cy) for i in (src, dst)]   # x1 x2 y1 y2
    ux = jnp.swapaxes(cx[:, uidx], 1, 2)                     # [P, Bp, Up]
    uy = jnp.swapaxes(cy[:, uidx], 1, 2)
    bp_u = uidx.shape[1]

    net = pl.BlockSpec((BP, BN), lambda i, j: (i, jnp.minimum(j, n_net - 1)))
    unit = pl.BlockSpec((BP, bp_u, BU),
                        lambda i, j: (i, 0, jnp.minimum(j, n_unit - 1)))
    out = pl.BlockSpec((BP, LANES), lambda i, j: (i, 0))
    wl, bb = pl.pallas_call(
        functools.partial(_eval_kernel, n_net, n_unit),
        grid=(pp // BP, max(n_net, n_unit)),
        in_specs=[net, net, net, net,
                  pl.BlockSpec((1, BN),
                               lambda i, j: (0, jnp.minimum(j, n_net - 1))),
                  unit, unit],
        out_specs=[out, out],
        out_shape=[jax.ShapeDtypeStruct((pp, LANES), jnp.float32)] * 2,
        interpret=interpret,
    )(ends[0], ends[2], ends[1], ends[3],
      w.reshape(1, -1).astype(jnp.float32), ux, uy)
    return jnp.stack([wl[:p, 0], bb[:p, 0]], axis=-1).reshape(*batch, 2)


# --------------------------------------------- fused domination + counts

BI, BJ = 128, 128


def _dom_kernel(a0, a1, b0, b1, dom_ref, cnt_ref):
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    ra0, ra1 = a0[...], a1[...]          # (BI, 1)  rows: candidate i
    cb0, cb1 = b0[...], b1[...]          # (1, BJ)  cols: candidate j
    le = (ra0 <= cb0) & (ra1 <= cb1)
    lt = (ra0 < cb0) | (ra1 < cb1)
    d = le & lt
    dom_ref[...] = d.astype(jnp.int8)
    cnt_ref[...] += jnp.sum(d.astype(jnp.int32), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def domination_counts_pallas(objs: jnp.ndarray, interpret: bool = False
                             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """objs: [P, 2] -> (dom int8 [P, P], dominated-by counts int32 [P]).

    Same tiling as `domination.domination_pallas`, but the row axis i is
    the *inner* grid dim so each (1, BJ) count tile is revisited on
    consecutive steps and the column sum never leaves VMEM.  The counts
    are a rank-2 (1, n) row: under a vmap (the service's slot axis) a
    rank-1 block would become a (squeezed, BJ) block, which the TPU
    lowering refuses.
    """
    p = objs.shape[0]
    o = P.pad_objs_inf(objs, BI)
    n = o.shape[0]
    o0r, o1r = o[:, 0:1], o[:, 1:2]
    o0c, o1c = o[:, 0].reshape(1, -1), o[:, 1].reshape(1, -1)
    grid = (n // BJ, n // BI)            # (j cols outer, i rows inner)
    dom, cnt = pl.pallas_call(
        _dom_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BI, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((BI, 1), lambda j, i: (i, 0)),
            pl.BlockSpec((1, BJ), lambda j, i: (0, j)),
            pl.BlockSpec((1, BJ), lambda j, i: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((BI, BJ), lambda j, i: (i, j)),
            pl.BlockSpec((1, BJ), lambda j, i: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, n), jnp.int8),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        interpret=interpret,
    )(o0r, o1r, o0c, o1c)
    return dom[:p, :p], cnt[0, :p]
