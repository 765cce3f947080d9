"""Objective evaluation for placement genotypes (paper Eqs. 1-2).

`evaluate` maps one genotype to the two objectives; `evaluate_population`
vmaps the whole population through decode + objectives in a single jitted
program (the paper's per-candidate Java evaluation becomes one fused batch).
Hot reductions route through `repro.kernels.ops` (Pallas on TPU).
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import genotype as G
from repro.fpga.netlist import BLOCKS_PER_UNIT, Problem
from repro.kernels import ops, ref


def unit_index(problem: Problem) -> jnp.ndarray:
    """[U, B] gid gather table for the fused kernel.

    Coordinates decode in gid order, which is unit-major, so the table is
    just arange reshaped -- but the fused layout keeps it an explicit
    gather so padded unit rows can point at the neutral gid 0.
    """
    g = problem.n_units * BLOCKS_PER_UNIT
    return jnp.arange(g, dtype=jnp.int32).reshape(
        problem.n_units, BLOCKS_PER_UNIT)


@functools.partial(jax.jit, static_argnums=(0, 3))
def objectives_from_coords(problem: Problem, bx: jnp.ndarray, by: jnp.ndarray,
                           fused: bool = False
                           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(wirelength^2, max bbox) from logical block coordinates [..., G].

    `fused=False` (default) is the original two-op path, bit-for-bit;
    `fused=True` routes through `ops.fused_eval` -- one kernel launch
    reduces both objectives.
    """
    s, d = jnp.asarray(problem.net_src), jnp.asarray(problem.net_dst)
    w = jnp.asarray(problem.net_w)
    if fused:
        res = ops.fused_eval(bx, by, s, d, w, unit_index(problem))
        return res[..., 0], res[..., 1]
    wl2 = ops.wirelength2(bx[s], by[s], bx[d], by[d], w)
    ux = bx.reshape(problem.n_units, BLOCKS_PER_UNIT)
    uy = by.reshape(problem.n_units, BLOCKS_PER_UNIT)
    bb = ops.maxbbox(ux, uy)
    return wl2, bb


@functools.partial(jax.jit, static_argnums=(0, 2))
def evaluate(problem: Problem, g: G.Genotype, fused: bool = False
             ) -> jnp.ndarray:
    """Genotype -> objectives [2] = (wl^2, max bbox)."""
    bx, by = G.decode(problem, g)
    wl2, bb = objectives_from_coords(problem, bx, by, fused)
    return jnp.stack([wl2, bb])


@functools.partial(jax.jit, static_argnums=(0, 2))
def evaluate_population(problem: Problem, pop: G.Genotype,
                        fused: bool = False) -> jnp.ndarray:
    """Batched genotypes (leading population axis on every leaf) -> [P, 2].

    Fused path: vmap only the decode, then evaluate the whole [P, G]
    coordinate block in a single `ops.fused_eval` call -- outer vmaps
    (slots, islands) stack further batch axes onto the same launch.
    """
    if fused:
        bx, by = jax.vmap(lambda g: G.decode(problem, g))(pop)
        s, d = jnp.asarray(problem.net_src), jnp.asarray(problem.net_dst)
        w = jnp.asarray(problem.net_w)
        return ops.fused_eval(bx, by, s, d, w, unit_index(problem))
    return jax.vmap(lambda g: evaluate(problem, g))(pop)


@functools.partial(jax.jit, static_argnums=(0, 2))
def evaluate_flat_population(problem: Problem, z: jnp.ndarray,
                             fused: bool = False) -> jnp.ndarray:
    """Continuous-encoded population [P, D] -> [P, 2] (CMA-ES / SA path)."""
    if fused:
        bx, by = jax.vmap(
            lambda zz: G.decode(problem, G.from_flat(problem, zz)))(z)
        s, d = jnp.asarray(problem.net_src), jnp.asarray(problem.net_dst)
        w = jnp.asarray(problem.net_w)
        return ops.fused_eval(bx, by, s, d, w, unit_index(problem))
    return jax.vmap(lambda zz: evaluate(problem, G.from_flat(problem, zz)))(z)


def scalarize(objs: jnp.ndarray) -> jnp.ndarray:
    """Single-objective fitness for SA / GA.

    The paper's combined metric is wirelength^2 x max-bbox (Fig. 7a); its log
    is scale-balanced, so SA temperatures mean the same thing for both terms.
    """
    return jnp.log(objs[..., 0] + 1e-9) + jnp.log(objs[..., 1] + 1e-9)


def combined_metric(objs: jnp.ndarray) -> jnp.ndarray:
    """wirelength^2 x max bbox, as plotted in paper Fig. 7a."""
    return objs[..., 0] * objs[..., 1]


@functools.partial(jax.jit, static_argnums=0)
def net_lengths(problem: Problem, g: G.Genotype) -> jnp.ndarray:
    """Per-net Manhattan lengths (post-placement pipelining input)."""
    bx, by = G.decode(problem, g)
    s, d = jnp.asarray(problem.net_src), jnp.asarray(problem.net_dst)
    return ref.net_lengths_ref(bx[s], by[s], bx[d], by[d])


# ------------------------------------------------------------- validation

def validate_placement(problem: Problem, g: G.Genotype) -> Dict[str, bool]:
    """Independent numpy re-check of every constraint (property tests).

    Returns a dict of named boolean checks; all must be True for a legal
    placement.  Deliberately *not* written against the decoder internals:
    it re-derives occupancy from decoded coordinates.
    """
    out: Dict[str, bool] = {}
    for t in G.TYPES:
        geom = problem.geom[t]
        x, y = G._decode_type(geom, g["dist"][t], g["loc"][t])
        x, y = np.asarray(x), np.asarray(y)
        # every block must sit on a column of its type; BRAM parity
        # sub-columns share x, so disambiguate via the row parity
        col_x = np.asarray(geom.col_x)
        col_par = np.asarray(geom.col_parity)
        row = np.round(y / geom.row_pitch).astype(np.int64)
        blk_par = row[:, 0] % geom.site_step
        dist = np.abs(x[:, 0, None] - col_x[None, :])
        dist += 1e9 * (col_par[None, :] != blk_par[:, None])
        col_of = np.argmin(dist, axis=-1)
        out[f"on_column_{t}"] = bool(
            np.allclose(x[:, 0], col_x[col_of], atol=1e-4))
        # cascade adjacency (Eq. 5): successive members step by
        # site_step * row_pitch in RPM rows, same column
        dy = np.diff(y, axis=1)
        step = geom.site_step * geom.row_pitch
        out[f"cascade_{t}"] = bool(np.allclose(dy, step, atol=1e-4))
        out[f"same_col_{t}"] = bool(np.all(np.diff(x, axis=1) == 0.0))
        # exclusivity (Eq. 4): no two chains overlap a site.  Reconstruct
        # integer site indices per (sub)column (parity-aware).
        parity = col_par[col_of]
        site = (row - parity[:, None]) // geom.site_step
        occ = set()
        ok = True
        for c in range(x.shape[0]):
            for s in site[c]:
                key = (int(col_of[c]), int(s))
                if key in occ:
                    ok = False
                occ.add(key)
        out[f"exclusive_{t}"] = ok
        # region (Eq. 3)
        cap = np.asarray(geom.col_cap_chains)[col_of]
        out[f"region_{t}"] = bool(
            np.all(site >= 0)
            and np.all(site < (cap * geom.chain_len)[:, None]))
        # mapping is a permutation
        perm = np.asarray(g["perm"][t])
        out[f"perm_{t}"] = bool(
            np.array_equal(np.sort(perm), np.arange(geom.n_chains)))
    return out


def assert_valid(problem: Problem, g: G.Genotype) -> None:
    checks = validate_placement(problem, g)
    bad = [k for k, v in checks.items() if not v]
    assert not bad, f"illegal placement: {bad}"
