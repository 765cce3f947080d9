"""Island-model evolution: P sub-populations, ring migration, one program.

The single-population algorithms (`nsga2`, `ga`, `cmaes`, `sa`) cap
quality-per-wallclock at their pop_size: one more generation is one serial
step, however many devices sit idle.  The island model is the classic EA
answer -- P independent sub-populations ("islands") evolve in parallel and
exchange their champions every `migrate_every` generations over a ring --
and on accelerators it is almost free: the island axis is just one more
batch axis.

This module reuses the algorithms' unjitted ``step_impl``s through
`core.hyper`'s static/traced split (exactly like `core.portfolio`), so ONE
jitted program advances every island of a run:

  * `IslandConfig`      -- (n_islands, migrate_every); a frozen hashable
    dataclass, so it rides `jit` static arguments and pool signatures.
  * `member_init` / `member_round` / `member_warm_init` -- the slot-level
    programs mirroring `core.portfolio` / `core.warmstart`, but over
    island-stacked states ``[P, ...]``.  `serve.placement_service` vmaps
    them over its slot axis: an islands pool is just a pool whose static
    signature includes the island config.  Warm seeds land on island 0
    and diffuse to the others via migration.
  * `run` -- the full-run entry (`evolve.run(islands=...)` dispatches
    here).  With more than one visible device and ``P % device_count ==
    0`` the island axis is sharded via `shard_map` (routed through
    `runtime.jaxcompat`), and ring migration crosses shard boundaries
    with a single `ppermute` -- no host round-trip, ever.

Migration is a pure function of the stacked states: island ``i`` adopts
the champion of island ``(i - 1) % P`` (one `jnp.roll` on the stacked
champions, or local roll + boundary `ppermute` when sharded).  Population
states replace their worst member; point states (CMA-ES, SA) adopt the
incoming champion only when it beats their own best, restarting the
mean/chain there.

Determinism: results are a pure function of (config, seed/key, budget,
init_state, island config).  Island keys come from `island_keys`, which
gives island 0 the caller's key *unchanged* when ``P == 1`` -- so
``islands(P=1)`` is bitwise identical to the single-population path, the
degeneracy check CI enforces (`benchmarks.check_bench`:
`islands_match_single_pop`).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import evolve, hyper, portfolio, warmstart
from repro.core import genotype as G
from repro.core import objectives as O
from repro.fpga.netlist import Problem
from repro.runtime import jaxcompat as jc

AXIS = "islands"                   # mesh axis name for the sharded path


@dataclasses.dataclass(frozen=True)
class IslandConfig:
    """Static island topology: baked into compiled programs (and pool
    signatures) exactly like pop_size.  `migrate_every == 0` never
    migrates; `n_islands == 1` is the single-population degeneracy."""
    n_islands: int = 1
    migrate_every: int = 0         # generations between ring migrations

    def __post_init__(self):
        if self.n_islands < 1:
            raise ValueError(f"n_islands must be >= 1, got {self.n_islands}")
        if self.migrate_every < 0:
            raise ValueError(
                f"migrate_every must be >= 0, got {self.migrate_every}")

    @property
    def active(self) -> bool:
        """True when this config actually changes the computation."""
        return self.n_islands > 1


def island_keys(key: jax.Array, n: int) -> jax.Array:
    """[n] per-island PRNG keys.  `n == 1` returns the caller's key
    unchanged (stacked): the P=1 island run consumes the *same* key
    stream as the single-population path -- the bitwise-identity
    contract."""
    if n == 1:
        return key[None]
    return jax.random.split(key, n)


# ----------------------------------------------------------- migration

def champion(state: Dict) -> Tuple[Dict, jnp.ndarray]:
    """(champion payload, its objectives [2]) of ONE island's state.

    Population states ship their best full genotype; point states
    (CMA-ES / SA) ship their flat `best_z`.  The payload pytree is
    identical across islands of a pool, so it rolls/ppermutes as one.
    """
    if "best_z" in state:
        return state["best_z"], state["best_objs"]
    c = O.combined_metric(state["objs"])
    b = jnp.argmin(c)
    return jax.tree.map(lambda a: a[b], state["pop"]), state["objs"][b]


def adopt(state: Dict, champ, champ_objs: jnp.ndarray) -> Dict:
    """One island adopts an incoming champion.

    Population states replace their worst member unconditionally (elitist
    truncation culls it anyway if the local pool is stronger).  Point
    states adopt only on strict improvement, restarting the CMA-ES mean /
    SA chain at the migrant so the search continues from it.
    """
    st = dict(state)
    if "best_z" in state:
        better = (O.combined_metric(champ_objs)
                  < O.combined_metric(state["best_objs"]))
        st["best_z"] = jnp.where(better, champ, state["best_z"])
        st["best_objs"] = jnp.where(better, champ_objs, state["best_objs"])
        if "mean" in state:                                   # cmaes
            st["mean"] = jnp.where(better, champ, state["mean"])
        if "z" in state:                                      # sa
            st["z"] = jnp.where(better, champ, state["z"])
            st["objs"] = jnp.where(better, champ_objs, state["objs"])
            st["fit"] = jnp.where(better, O.scalarize(champ_objs),
                                  state["fit"])
        return st
    w = jnp.argmax(O.combined_metric(state["objs"]))
    st["pop"] = jax.tree.map(lambda a, b: a.at[w].set(b),
                             state["pop"], champ)
    st["objs"] = state["objs"].at[w].set(champ_objs)
    return st


def migrate_ring(state: Dict, axis: Optional[str] = None) -> Dict:
    """Ring migration over island-stacked states ``[L, ...]``: island i
    adopts the champion of island i-1 (mod P, globally).

    Unsharded (`axis=None`): one `jnp.roll` of the stacked champions.
    Inside `shard_map`: local roll + ONE `ppermute` carrying each shard's
    last champion to the next shard's island 0 -- the whole exchange is
    device-to-device, no host round-trip.
    """
    champs, cobjs = jax.vmap(champion)(state)
    if axis is None:
        inc = jax.tree.map(lambda a: jnp.roll(a, 1, axis=0), champs)
        inc_objs = jnp.roll(cobjs, 1, axis=0)
    else:
        n_shards = jc.axis_size(axis)
        perm = jc.ring_perm(n_shards)
        # my last island's champion -> next shard's boundary slot
        bound = jc.ppermute(jax.tree.map(lambda a: a[-1], champs),
                            axis, perm)
        bound_objs = jc.ppermute(cobjs[-1], axis, perm)
        inc = jax.tree.map(
            lambda b, a: jnp.concatenate([b[None], a[:-1]], axis=0),
            bound, champs)
        inc_objs = jnp.concatenate([bound_objs[None], cobjs[:-1]], axis=0)
    return jax.vmap(adopt)(state, inc, inc_objs)


# ------------------------------------------------------ generation loop

def round_impl(problem: Problem, algo: str, icfg: IslandConfig, cfg,
               state: Dict, gen_keys: jax.Array, g0,
               axis: Optional[str] = None) -> Tuple[Dict, jnp.ndarray]:
    """Advance island-stacked states by `len(gen_keys)` generations.

    `gen_keys` is ``[n_gens, L]`` per-island step keys, `g0` the global
    generation count already run (traced: service slots differ).  Ring
    migration fires after every generation g with ``g % migrate_every ==
    0`` -- counted globally, so a service pool stepping `gens_per_step`
    at a time migrates on exactly the same generations as a monolithic
    run.  Returns (state, per-island best objectives ``[n_gens, L, 2]``).
    """
    m = evolve.get_algo(algo)
    migrating = icfg.active and icfg.migrate_every > 0

    def body(carry, ks):
        st, g = carry
        st = jax.vmap(lambda s, k: m.step_impl(problem, cfg, s, k))(st, ks)
        g = g + 1
        if migrating:
            mig = migrate_ring(st, axis)
            do = (g % icfg.migrate_every) == 0
            st = jax.tree.map(lambda a, b: jnp.where(do, b, a), st, mig)
        return (st, g), jax.vmap(evolve.state_best_objs)(st)

    (state, _), hist = jax.lax.scan(body, (state, jnp.int32(g0)), gen_keys)
    return state, hist


def best_over_islands(state: Dict) -> jnp.ndarray:
    """Best (wl^2, bbox) across an island-stacked state (traced-safe)."""
    best = jax.vmap(evolve.state_best_objs)(state)          # [P, 2]
    return best[jnp.argmin(O.combined_metric(best))]


# ------------------------------------------- slot-level member programs
#
# Mirrors of `portfolio.member_init/member_round` and
# `warmstart.member_warm_init` over the island axis: the placement
# service vmaps these over its slot axis, so an islands pool keeps the
# exact serving discipline (static shapes, one compiled step).

def member_init(problem: Problem, algo: str, static_key: hyper.StaticKey,
                icfg: IslandConfig, traced: Dict[str, jnp.ndarray],
                key: jax.Array) -> Dict:
    """Init one slot's island-stacked state ``[P, ...]``."""
    cfg = hyper.tracify(hyper.merge_config(static_key, traced))
    m = evolve.get_algo(algo)
    keys = island_keys(key, icfg.n_islands)
    return jax.vmap(lambda k: m.init_state(problem, k, cfg))(keys)


def member_round(problem: Problem, algo: str, static_key: hyper.StaticKey,
                 icfg: IslandConfig, n_gens: int,
                 traced: Dict[str, jnp.ndarray], state: Dict,
                 key: jax.Array, g0) -> Tuple[Dict, jnp.ndarray]:
    """Advance one slot's islands `n_gens` generations; returns
    (state, best objectives across all islands)."""
    cfg = hyper.tracify(hyper.merge_config(static_key, traced))
    keys = island_keys(key, icfg.n_islands)
    gen_keys = jnp.swapaxes(
        jax.vmap(lambda k: jax.random.split(k, n_gens))(keys), 0, 1)
    state, _ = round_impl(problem, algo, icfg, cfg, state, gen_keys, g0)
    return state, best_over_islands(state)


def member_warm_init(problem: Problem, algo: str,
                     static_key: hyper.StaticKey, icfg: IslandConfig,
                     traced: Dict[str, jnp.ndarray], pop: G.Genotype,
                     fresh: jnp.ndarray, jitter: jnp.ndarray,
                     sigma_shrink: jnp.ndarray, key: jax.Array) -> Dict:
    """Warm-start one slot's islands from a canonical seed block.

    The seed lands on **island 0** (`warmstart.warm_state`, same
    semantics as a non-islands pool); islands 1..P-1 start cold and pick
    the transferred champion up through ring migration -- transfer
    serving (paper SS IV-D) composes with islands for free.
    """
    cold = member_init(problem, algo, static_key, icfg, traced, key)
    cfg = hyper.tracify(hyper.merge_config(static_key, traced))
    keys = island_keys(key, icfg.n_islands)
    warm0 = warmstart.warm_state(problem, algo, cfg, pop, fresh, keys[0],
                                 jitter, sigma_shrink)
    return jax.tree.map(lambda c, w: c.at[0].set(w), cold, warm0)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _vinit(problem, algo, static_key, icfg, traced, keys):
    """[K] slots of island-stacked states in one program (pool fill)."""
    return jax.vmap(
        lambda tr, k: member_init(problem, algo, static_key, icfg, tr, k)
    )(traced, keys)


def best_genotype(problem: Problem, algo: str, state: Dict,
                  cfg=None) -> Tuple[G.Genotype, jnp.ndarray]:
    """Best full genotype + objectives across one slot's islands (host
    side, harvest time): pick the champion island, then delegate to
    `portfolio.best_genotype` on its unstacked state."""
    best = np.asarray(jax.vmap(evolve.state_best_objs)(state))
    i = int(np.argmin(np.asarray(O.combined_metric(jnp.asarray(best)))))
    return portfolio.best_genotype(
        problem, algo, jax.tree.map(lambda a: a[i], state), cfg)


# ------------------------------------------------------- full-run entry

@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 5, 6))
def _run(problem: Problem, algo: str, cfg, icfg: IslandConfig,
         key: jax.Array, n_gens: int,
         mesh) -> Tuple[Dict, jnp.ndarray]:
    """One jitted program: init + n_gens generations of P islands.

    Per-island key streams mirror `evolve._run_impl` exactly (split into
    init/run, run split per generation), so P=1 is bitwise the
    single-population run.  With a mesh, the island axis is sharded via
    `shard_map` and migration ppermutes across shard boundaries.
    """
    cfg = hyper.tracify(cfg)
    m = evolve.get_algo(algo)
    keys = island_keys(key, icfg.n_islands)
    halves = jax.vmap(jax.random.split)(keys)               # [P, 2, key]
    k_init, k_run = halves[:, 0], halves[:, 1]
    gen_keys = jnp.swapaxes(
        jax.vmap(lambda k: jax.random.split(k, n_gens))(k_run), 0, 1)

    def init_and_run(ki, gk, axis=None):
        states = jax.vmap(lambda k: m.init_state(problem, k, cfg))(ki)
        return round_impl(problem, algo, icfg, cfg, states, gk,
                          jnp.int32(0), axis=axis)

    if mesh is None:
        return init_and_run(k_init, gen_keys)

    # the init evaluates the population too, so it runs inside the
    # shard_map: the TPU's Pallas kernels cannot be auto-partitioned
    sharded = jc.shard_map(
        functools.partial(init_and_run, axis=AXIS), mesh=mesh,
        in_specs=(P(AXIS), P(None, AXIS)),
        out_specs=(P(AXIS), P(None, AXIS)))
    return sharded(k_init, gen_keys)


def run(problem: Problem, algo: str, cfg, key: jax.Array, n_gens: int,
        islands: IslandConfig = IslandConfig(), mesh=None,
        shard: str = "auto") -> Tuple[Dict, jnp.ndarray]:
    """P islands of a full optimization in one program.

    Returns (island-stacked states ``[P, ...]``, per-island history
    ``[n_gens, P, 2]``).  `shard="auto"` shards the island axis across
    all visible devices whenever ``P % device_count == 0`` (pass an
    explicit `mesh` with an ``"islands"`` axis, or ``shard=False``, to
    override); 1 device or an indivisible P falls back to a pure-vmap
    stack of islands -- the same program either way, only the mesh
    changes.
    """
    n = islands.n_islands
    if mesh is None and shard == "auto":
        ndev = jax.device_count()
        if ndev > 1 and n >= ndev and n % ndev == 0:
            mesh = jc.make_mesh((ndev,), (AXIS,))
    if mesh is not None:
        size = int(np.prod([mesh.shape[a] for a in mesh.axis_names
                            if a == AXIS]))
        if AXIS not in mesh.axis_names or n % size != 0:
            raise ValueError(
                f"mesh must carry an {AXIS!r} axis dividing n_islands="
                f"{n}; got axes {mesh.axis_names} shape {dict(mesh.shape)}")
    return _run(problem, algo, cfg, islands, key, n_gens, mesh)
