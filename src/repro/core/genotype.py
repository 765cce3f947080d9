"""Three-tier genotype (paper Fig. 2) and its fixed-shape JAX decoder.

A candidate placement is encoded as, per hard-block type t in {URAM,DSP,BRAM}:

  distribution  dist_t  f32[C_t]   how many cascade *chains* land in each
                                   (sub)column (softmax share of N_t chains,
                                   capacity-clipped exactly),
  location      loc_t   f32[N_t]   relative position of each chain within its
                                   column, in [0,1),
  mapping       perm_t  i32[N_t]   permutation: logical chain role -> physical
                                   chain (which placed chains form which conv
                                   unit).

Cascade constraints (Eq. 5) are *encoded*, not legalised after the fact: the
decoder only ever emits chains as contiguous cascade-legal site runs
(BRAM parity handled by sub-columns), so every genotype decodes to a legal
placement -- the paper's key search-space reduction (SS III-A.3).

The decoder is pure JAX with static shapes: a whole population decodes with
one `vmap`, and whole populations of populations (islands) with `shard_map`.

Two encodings are supported:
  * structured (dict of per-type arrays)   -- NSGA-II / GA operators,
  * flat continuous vector z in R^D        -- CMA-ES / SA; permutations via
    random keys (argsort), the classic continuous relaxation the paper's
    CMA-ES needs ("crossover and mutation become adding Gaussian noise").

`decode_reduced` implements the paper SS IV-B2 reduced genotype: mapping only,
blocks uniformly distributed and stacked bottom-up.
"""
from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.fpga.device import BRAM, DSP, URAM
from repro.fpga.netlist import Problem, TypeGeom

Genotype = Dict[str, Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]
TYPES = (URAM, DSP, BRAM)


# ---------------------------------------------------------------- utilities

def _seg_cummax(vals: jnp.ndarray, segs: jnp.ndarray) -> jnp.ndarray:
    """Segment-wise running max (segments = contiguous equal ids)."""

    def comb(a, b):
        sa, va = a
        sb, vb = b
        return sb, jnp.where(sa == sb, jnp.maximum(va, vb), vb)

    _, out = lax.associative_scan(comb, (segs, vals))
    return out


def allocate_counts(genes: jnp.ndarray, caps: jnp.ndarray,
                    total: int) -> jnp.ndarray:
    """Exact capacity-respecting proportional allocation.

    softmax share -> floor -> leftover water-filled by fractional priority.
    Always sums to `total` when sum(caps) >= total, never exceeds caps.
    """
    p = jax.nn.softmax(genes.astype(jnp.float32))
    desired = p * total
    base = jnp.minimum(jnp.floor(desired), caps.astype(jnp.float32))
    base = base.astype(jnp.int32)
    rem = total - jnp.sum(base)
    room = caps.astype(jnp.int32) - base
    prio = desired - base.astype(jnp.float32)          # in [0,1); 0 if capped
    prio = jnp.where(room > 0, prio, -1.0)
    order = jnp.argsort(-prio)
    room_s = room[order]
    cum_before = jnp.cumsum(room_s) - room_s
    give_s = jnp.clip(rem - cum_before, 0, room_s)
    give = jnp.zeros_like(base).at[order].set(give_s.astype(jnp.int32))
    return base + give


def _col_lookup(table, col: jnp.ndarray) -> jnp.ndarray:
    """`table[col]` for a short per-column table, as a one-hot select summed
    over the C columns in the table's own dtype: no gather, and exact, since
    each row keeps one entry. (A matmul would round through bfloat16.)"""
    table = jnp.asarray(table)
    hit = col[:, None] == jnp.arange(table.shape[0])[None, :]
    return jnp.sum(jnp.where(hit, table[None, :], 0), axis=-1,
                   dtype=table.dtype)


def _decode_type(geom: TypeGeom, dist: jnp.ndarray, loc: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Decode one hard-block type to physical chain-member coordinates.

    Returns (x, y) each of shape [N_chains, chain_len] in RPM units.
    """
    N, L = geom.n_chains, geom.chain_len
    caps = jnp.asarray(geom.col_cap_chains)
    counts = allocate_counts(dist, caps, N)

    bounds = jnp.cumsum(counts)                       # exclusive upper bounds
    chain_idx = jnp.arange(N)
    # one dense comparison over the C (<= 50) columns: the default method's
    # loop of per-row gathers is the slowest part of a decode on the TPU
    col = jnp.searchsorted(bounds, chain_idx, side="right",
                           method="compare_all").astype(jnp.int32)
    col = jnp.clip(col, 0, geom.n_cols - 1)

    # within-column order by location gene: single global sort on (col, loc)
    locc = jnp.clip(loc, 0.0, 1.0 - 1e-6)
    key = col.astype(jnp.float32) * 2.0 + locc
    order = jnp.argsort(key)
    col_s = col[order]
    loc_s = locc[order]
    col_start = _col_lookup(bounds - counts, col_s)
    rank_s = jnp.arange(N) - col_start                # rank within column

    # spread slack slots according to location genes, monotone within column
    slack_sites = _col_lookup((caps - counts) * L, col_s).astype(jnp.float32)
    off = jnp.floor(loc_s * (slack_sites + 1.0))
    off = jnp.minimum(off, slack_sites)
    off = _seg_cummax(off, col_s)                     # keep packing legal
    ystart_s = rank_s * L + off.astype(jnp.int32)

    ystart = jnp.zeros(N, jnp.int32).at[order].set(ystart_s)

    member = jnp.arange(L)[None, :]
    site = ystart[:, None] + member                   # sub-column site index
    parity = _col_lookup(geom.col_parity, col)[:, None]
    phys_row = site * geom.site_step + parity
    y = phys_row.astype(jnp.float32) * geom.row_pitch
    x = _col_lookup(geom.col_x, col)[:, None] * jnp.ones((1, L), jnp.float32)
    return x, y


# ------------------------------------------------------------------ decode

@functools.partial(jax.jit, static_argnums=0)
def decode(problem: Problem, g: Genotype) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Genotype -> logical-block coordinates (x[G], y[G]) in RPM units.

    Logical gid order is unit-major (see netlist._ROLE_LAYOUT); the mapping
    permutation routes logical chain roles onto physical chains.
    """
    xs, ys = [], []
    for t in TYPES:
        x, y = _decode_type(problem.geom[t], g["dist"][t], g["loc"][t])
        perm = g["perm"][t]
        xs.append(x[perm].reshape(-1))
        ys.append(y[perm].reshape(-1))
    xcat = jnp.concatenate(xs)
    ycat = jnp.concatenate(ys)
    pos = jnp.asarray(problem.blk_flatpos)
    return xcat[pos], ycat[pos]


def reduced_to_full(problem: Problem, perms: Tuple[jnp.ndarray, ...]
                    ) -> Genotype:
    """Lift a mapping-only genotype to the full composite encoding:
    distribution proportional to column capacity, location packed bottom-up.
    """
    return {
        "dist": tuple(jnp.log(jnp.asarray(
            problem.geom[t].col_cap_chains, jnp.float32) + 1e-3)
            for t in TYPES),
        "loc": tuple(jnp.zeros(problem.geom[t].n_chains) for t in TYPES),
        "perm": tuple(perms),
    }


@functools.partial(jax.jit, static_argnums=0)
def decode_reduced(problem: Problem, perms: Tuple[jnp.ndarray, ...]
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Paper SS IV-B2: mapping-only genotype.

    ~1.8x less decode work, larger bounding boxes.
    """
    return decode(problem, reduced_to_full(problem, perms))


# ----------------------------------------------------- encodings / sampling

def random_genotype(key: jax.Array, problem: Problem) -> Genotype:
    ks = jax.random.split(key, 9)
    dist, loc, perm = [], [], []
    for i, t in enumerate(TYPES):
        geom = problem.geom[t]
        dist.append(jax.random.normal(ks[i], (geom.n_cols,)) * 0.5)
        loc.append(jax.random.uniform(ks[3 + i], (geom.n_chains,)))
        perm.append(jax.random.permutation(ks[6 + i], geom.n_chains)
                    .astype(jnp.int32))
    return {"dist": tuple(dist), "loc": tuple(loc), "perm": tuple(perm)}


def flat_dim(problem: Problem) -> int:
    return problem.continuous_dim


def flat_split(problem: Problem):
    """Static slices of the flat continuous vector."""
    sizes = []
    for part in ("dist", "loc", "map"):
        for t in TYPES:
            g = problem.geom[t]
            sizes.append(g.n_cols if part == "dist" else g.n_chains)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    return [(int(offs[i]), int(offs[i + 1])) for i in range(len(sizes))]


@functools.partial(jax.jit, static_argnums=0)
def from_flat(problem: Problem, z: jnp.ndarray) -> Genotype:
    """Continuous vector -> structured genotype (perm via argsort keys)."""
    sl = flat_split(problem)
    dist = tuple(z[a:b] for (a, b) in sl[0:3])
    loc = tuple(jax.nn.sigmoid(z[a:b]) for (a, b) in sl[3:6])
    perm = tuple(jnp.argsort(z[a:b]).astype(jnp.int32) for (a, b) in sl[6:9])
    return {"dist": dist, "loc": loc, "perm": perm}


def to_flat(problem: Problem, g: Genotype) -> jnp.ndarray:
    """Structured -> flat continuous (inverse up to argsort equivalence).

    Used to seed CMA-ES / SA from a structured genotype (transfer learning).
    """
    parts = []
    for t in TYPES:
        parts.append(g["dist"][t])
    for t in TYPES:
        x = jnp.clip(g["loc"][t], 1e-4, 1 - 1e-4)
        parts.append(jnp.log(x) - jnp.log1p(-x))      # logit
    for t in TYPES:
        n = problem.geom[t].n_chains
        # keys whose argsort reproduces the permutation
        ranks = jnp.zeros(n).at[g["perm"][t]].set(jnp.arange(n, dtype=jnp.float32))
        parts.append(ranks / jnp.maximum(n - 1, 1) * 2.0 - 1.0)
    return jnp.concatenate(parts)
