"""NSGA-II for hard-block placement -- fully vectorized, fixed-shape JAX.

Implements Deb et al.'s elitist multi-objective GA with:
  * fast non-dominated sorting from the P x P domination matrix
    (Pallas kernel on TPU, `kernels.domination`),
  * crowding distance with exact per-front normalisation,
  * crowded binary tournament selection,
  * SBX crossover + polynomial mutation on the real genotype tiers
    (distribution, location),
  * fixed-shape order crossover (OX) + swap mutation on the mapping
    permutations -- the paper's composite-genotype operators (SS III-A.1),
  * the SS IV-B2 *reduced genotype* variant (mapping only).

All operators are jit/vmap-safe; one generation is a single XLA program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core import genotype as G
from repro.core import objectives as O
from repro.fpga.netlist import Problem
from repro.kernels import ops

INF = jnp.float32(1e9)


@dataclasses.dataclass(frozen=True)
class NSGA2Config:
    pop_size: int = 64
    crossover_prob: float = 0.9
    sbx_eta: float = 15.0
    mut_eta: float = 20.0
    real_mut_prob: float = 0.1     # per-gene polynomial mutation prob
    perm_swaps: int = 2            # swap mutations per child permutation
    perm_swap_prob: float = 0.6
    reduced: bool = False          # SS IV-B2 mapping-only genotype
    fused: bool = False            # route evaluation through ops.fused_eval


# ------------------------------------------------- non-dominated sorting

def nondominated_rank(objs: jnp.ndarray, fused: bool = False) -> jnp.ndarray:
    """[P, M] objectives -> [P] int32 Pareto front index (0 = best).

    `fused=True` takes the matrix and its column counts from one kernel
    launch (`ops.fused_domination_counts`); the default branch is the
    original two-step computation, untouched.
    """
    p = objs.shape[0]
    if fused:
        dom_b, ndom = ops.fused_domination_counts(objs)
        dom = dom_b.astype(jnp.int32)                        # dom[i,j]: i>j
    else:
        dom = ops.domination_matrix(objs).astype(jnp.int32)  # dom[i,j]: i>j
        ndom = jnp.sum(dom, axis=0)                          # dominated-by ct

    def body(r, carry):
        rank, nd = carry
        front = (nd == 0) & (rank == p)
        rank = jnp.where(front, r, rank)
        release = jnp.sum(dom * front[:, None].astype(jnp.int32), axis=0)
        nd = jnp.where(front, -1, nd - release)
        return rank, nd

    rank, _ = jax.lax.fori_loop(0, p, body, (jnp.full(p, p, jnp.int32), ndom))
    return rank


def crowding_distance(objs: jnp.ndarray, rank: jnp.ndarray) -> jnp.ndarray:
    """Crowding distance within each front (boundaries get INF)."""
    p, m = objs.shape
    crowd = jnp.zeros(p, jnp.float32)
    # exact per-front objective ranges via scatter-min/max into rank buckets
    for mm in range(m):
        f = objs[:, mm].astype(jnp.float32)
        fmax = jnp.full(p, -jnp.inf).at[rank].max(f)[rank]
        fmin = jnp.full(p, jnp.inf).at[rank].min(f)[rank]
        rng = jnp.maximum(fmax - fmin, 1e-12)
        # exact lexicographic (rank, f) sort: two stable argsorts
        o1 = jnp.argsort(f, stable=True)
        order = o1[jnp.argsort(rank[o1], stable=True)]
        fs = f[order]
        rs = rank[order]
        prev = jnp.concatenate([fs[:1], fs[:-1]])
        nxt = jnp.concatenate([fs[1:], fs[-1:]])
        same_prev = jnp.concatenate(
            [jnp.array([False]), rs[1:] == rs[:-1]])
        same_next = jnp.concatenate(
            [rs[:-1] == rs[1:], jnp.array([False])])
        d = jnp.where(same_prev & same_next,
                      (nxt - prev) / rng[order], INF)
        crowd = crowd + jnp.zeros(p).at[order].set(d)
    return crowd


# ------------------------------------------------------------- operators

def _sbx(key, a: jnp.ndarray, b: jnp.ndarray, eta: float,
         prob: float) -> jnp.ndarray:
    k1, k2, k3 = jax.random.split(key, 3)
    u = jax.random.uniform(k1, a.shape)
    beta = jnp.where(u <= 0.5,
                     (2.0 * u) ** (1.0 / (eta + 1.0)),
                     (1.0 / (2.0 * (1.0 - u) + 1e-12)) ** (1.0 / (eta + 1.0)))
    sign = jnp.where(jax.random.bernoulli(k2, 0.5, a.shape), 1.0, -1.0)
    child = 0.5 * ((a + b) + sign * beta * (a - b))
    do = jax.random.bernoulli(k3, prob, a.shape)
    return jnp.where(do, child, a)


def _poly_mut(key, x: jnp.ndarray, eta: float, prob: float,
              scale: float = 1.0) -> jnp.ndarray:
    k1, k2 = jax.random.split(key)
    u = jax.random.uniform(k1, x.shape)
    d = jnp.where(u < 0.5,
                  (2.0 * u) ** (1.0 / (eta + 1.0)) - 1.0,
                  1.0 - (2.0 * (1.0 - u)) ** (1.0 / (eta + 1.0)))
    do = jax.random.bernoulli(k2, prob, x.shape)
    return x + jnp.where(do, d * scale, 0.0)


def _ox(key, p1: jnp.ndarray, p2: jnp.ndarray) -> jnp.ndarray:
    """Fixed-shape order crossover: child keeps p1's segment [a, b), fills
    the remaining slots left-to-right with p2's values in p2 order."""
    n = p1.shape[0]
    k1, k2 = jax.random.split(key)
    cuts = jnp.sort(jax.random.randint(k1, (2,), 0, n + 1))
    a, b = cuts[0], cuts[1]
    pos = jnp.arange(n)
    seg = (pos >= a) & (pos < b)
    taken = jnp.zeros(n + 1, bool).at[jnp.where(seg, p1, n)].set(True)[:n]
    # order positions: non-segment slots first (stable), then segment slots
    pos_order = jnp.argsort(seg, stable=True)
    # order values: untaken values in p2 order first, then the taken ones
    val_order = jnp.argsort(taken[p2], stable=True)
    n_free = n - (b - a)
    fill = jnp.where(jnp.arange(n) < n_free, p2[val_order], p1[pos_order])
    return jnp.zeros(n, p1.dtype).at[pos_order].set(fill)


def _swap_mut(key, perm: jnp.ndarray, n_swaps: int, prob: float
              ) -> jnp.ndarray:
    n = perm.shape[0]

    def one(carry, k):
        p = carry
        ki, kj, kd = jax.random.split(k, 3)
        i = jax.random.randint(ki, (), 0, n)
        j = jax.random.randint(kj, (), 0, n)
        do = jax.random.bernoulli(kd, prob)
        pi, pj = p[i], p[j]
        p = p.at[i].set(jnp.where(do, pj, pi)).at[j].set(
            jnp.where(do, pi, pj))
        return p, None

    perm, _ = jax.lax.scan(one, perm, jax.random.split(key, n_swaps))
    return perm


def _vary_one(key, g1: G.Genotype, g2: G.Genotype,
              cfg: NSGA2Config) -> G.Genotype:
    """Produce one child from two parents (full composite genotype)."""
    keys = jax.random.split(key, 12)
    dist, loc, perm = [], [], []
    for t in range(3):
        d = _sbx(keys[t], g1["dist"][t], g2["dist"][t],
                 cfg.sbx_eta, cfg.crossover_prob)
        d = _poly_mut(keys[3 + t], d, cfg.mut_eta, cfg.real_mut_prob, 1.0)
        dist.append(d)
        l = _sbx(keys[6 + t], g1["loc"][t], g2["loc"][t],
                 cfg.sbx_eta, cfg.crossover_prob)
        l = _poly_mut(keys[9 + t], l, cfg.mut_eta, cfg.real_mut_prob, 0.25)
        loc.append(jnp.clip(l, 0.0, 1.0))
    pkeys = jax.random.split(keys[11], 6)
    for t in range(3):
        c = _ox(pkeys[t], g1["perm"][t], g2["perm"][t])
        c = _swap_mut(pkeys[3 + t], c, cfg.perm_swaps, cfg.perm_swap_prob)
        perm.append(c)
    return {"dist": tuple(dist), "loc": tuple(loc), "perm": tuple(perm)}


def _vary_one_reduced(key, g1, g2, cfg: NSGA2Config):
    pkeys = jax.random.split(key, 6)
    perm = []
    for t in range(3):
        c = _ox(pkeys[t], g1[t], g2[t])
        c = _swap_mut(pkeys[3 + t], c, cfg.perm_swaps, cfg.perm_swap_prob)
        perm.append(c)
    return tuple(perm)


# ------------------------------------------------------------- algorithm

def _tournament(key, rank, crowd, n: int) -> jnp.ndarray:
    p = rank.shape[0]
    ka, kb = jax.random.split(key)
    ia = jax.random.randint(ka, (n,), 0, p)
    ib = jax.random.randint(kb, (n,), 0, p)
    better = (rank[ia] < rank[ib]) | (
        (rank[ia] == rank[ib]) & (crowd[ia] > crowd[ib]))
    return jnp.where(better, ia, ib)


def _lexsort_rank_crowd(rank, crowd):
    order1 = jnp.argsort(-crowd, stable=True)
    order2 = jnp.argsort(rank[order1], stable=True)
    return order1[order2]


def init_state(problem: Problem, key: jax.Array, cfg: NSGA2Config
               ) -> Dict[str, jnp.ndarray]:
    keys = jax.random.split(key, cfg.pop_size)
    if cfg.reduced:
        pop = jax.vmap(
            lambda k: tuple(G.random_genotype(k, problem)["perm"]))(keys)
        objs = _eval_reduced(problem, pop, cfg.fused)
    else:
        pop = jax.vmap(lambda k: G.random_genotype(k, problem))(keys)
        objs = O.evaluate_population(problem, pop, cfg.fused)
    return {"pop": pop, "objs": objs}


@functools.partial(jax.jit, static_argnums=(0, 2))
def _eval_reduced(problem: Problem, perms, fused: bool = False
                  ) -> jnp.ndarray:
    if fused:
        with jax.named_scope("decode"):
            bx, by = jax.vmap(
                lambda ps: G.decode_reduced(problem, ps))(perms)
        with jax.named_scope("evaluate"):
            s = jnp.asarray(problem.net_src)
            d = jnp.asarray(problem.net_dst)
            w = jnp.asarray(problem.net_w)
            return ops.fused_eval(bx, by, s, d, w, O.unit_index(problem))

    def one(ps):
        with jax.named_scope("decode"):
            bx, by = G.decode_reduced(problem, ps)
        wl2, bb = O.objectives_from_coords(problem, bx, by)
        return jnp.stack([wl2, bb])

    return jax.vmap(one)(perms)


def step_impl(problem: Problem, cfg: NSGA2Config, state, key):
    """One NSGA-II generation: P children, (mu+lambda) truncation.

    Unjitted body: float config fields may be JAX tracers (portfolio
    batching); only `pop_size`, `perm_swaps`, `reduced` must be concrete.

    Each phase runs under a `jax.named_scope` -- `rank`, `select`, `vary`,
    and `decode` / `evaluate` inside the evaluation -- so every operation's
    `op_name` says which phase it belongs to; a profile charges device
    time to the phases by it.  Scopes are metadata: no operation changes.
    """
    pop, objs = state["pop"], state["objs"]
    p = cfg.pop_size
    with jax.named_scope("rank"):
        rank = nondominated_rank(objs, cfg.fused)
        crowd = crowding_distance(objs, rank)
    with jax.named_scope("select"):
        k1, k2, k3 = jax.random.split(key, 3)
        pa = _tournament(k1, rank, crowd, p)
        pb = _tournament(k2, rank, crowd, p)

        def take(idx):
            return jax.tree.map(lambda a: a[idx], pop)

        parents_a, parents_b = take(pa), take(pb)
    vary = _vary_one_reduced if cfg.reduced else _vary_one
    with jax.named_scope("vary"):
        children = jax.vmap(lambda k, g1, g2: vary(k, g1, g2, cfg))(
            jax.random.split(k3, p), parents_a, parents_b)
    # decode and evaluate scope themselves inside the evaluation
    cobjs = (_eval_reduced(problem, children, cfg.fused) if cfg.reduced
             else O.evaluate_population(problem, children, cfg.fused))

    # (mu + lambda) environmental selection on the combined population
    with jax.named_scope("select"):
        allpop = jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                              pop, children)
        allobjs = jnp.concatenate([objs, cobjs])
    with jax.named_scope("rank"):
        arank = nondominated_rank(allobjs, cfg.fused)
        acrowd = crowding_distance(allobjs, arank)
        order = _lexsort_rank_crowd(arank, acrowd)[:p]
    with jax.named_scope("select"):
        return {"pop": jax.tree.map(lambda a: a[order], allpop),
                "objs": allobjs[order]}


step = functools.partial(jax.jit, static_argnums=(0, 1))(step_impl)


def best(state) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(best combined-metric objectives, index)."""
    c = O.combined_metric(state["objs"])
    i = jnp.argmin(c)
    return state["objs"][i], i
