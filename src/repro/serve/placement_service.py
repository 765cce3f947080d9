"""Placement-as-a-service: slot-based continuous batching of placement jobs.

Mirrors the serving discipline of `serve.engine.Engine` (fixed KV-cache
slot pool, masked batched decode) for evolutionary placement: a fixed pool
of `n_slots` *job slots* shares one compiled step program for a single
device/problem.

  submit()  -> pick a free slot, initialise the job's algorithm state into
               it (its own seed + float hyperparameters; one jitted init)
  step()    -> ONE batched jitted call advances every slot by
               `gens_per_step` generations (vmap over the slot axis;
               per-slot hyperparameters ride as traced f32 operands)
  finished  -> jobs whose generation budget is exhausted -- or whose
               combined metric hit their `target` -- are harvested (best
               genotype + objectives), the slot is freed

Jobs are reproducible: every step key derives from the *job's* seed and
its own generation counter (never a shared service stream), so a job's
result is a pure function of (config, seed, budget, gens_per_step) --
independent of co-tenant jobs and admission timing.

Shapes are static: jobs come and go by overwriting slot *contents* (state
arrays, hyperparameter rows, mask entries), never shapes, so `step()` never
recompiles -- the TPU-friendly serving discipline, now for placement
traffic.  Vacant slots keep evolving whatever state they hold; their work
is masked out of accounting and their results are never read.

Static config fields (pop_size, perm_swaps, reduced, fused, ...) are
fixed per pool at construction: they are baked into the compiled step.
Jobs whose config disagrees on those belong in a different pool --
`serve.scheduler.PlacementScheduler` routes mixed traffic across pools.
`fused=True` configs evaluate the pool's whole stacked (slots x islands x
pop) batch through the fused Pallas pipeline (`kernels.fused_eval`): one
kernel launch reduces both objectives over the stacked batch.

Warm starts: `submit(init_state=...)` seeds a job from a genotype (e.g.
`core.transfer.migrate`'s projection of a sibling-device champion) via a
per-pool jitted warm-init program (`core.warmstart`) -- the transfer
serving path of paper SS IV-D.

Islands: `PlacementService(..., islands=IslandConfig(P, migrate_every))`
makes every slot hold P island sub-populations (`core.islands`) instead of
one: slot states grow a leading island axis, the batched step vmaps the
islands round (P independent `step_impl`s + ring champion migration at
global-generation boundaries) over the slot axis, and harvest returns the
best genotype across a slot's islands.  The island config is static --
part of the pool's compiled-program signature, like pop_size -- so an
islands pool keeps the exact serving discipline above (one step compile,
jobs come and go by content).  Warm seeds land on island 0 and diffuse to
the other islands via migration.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import itertools
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import hyper, portfolio, warmstart
from repro.core import islands as islands_mod
from repro.core import objectives as O
from repro.core.islands import IslandConfig
from repro.fpga.netlist import Problem
from repro.runtime import compile_cache, telemetry
from repro.serve import api, tracing
from repro.serve.api import JobRequest, ServiceStats

# registry-global instruments (recording is host-side arithmetic, cheap
# next to a jitted step; exporters are what the config flags gate)
_REG = telemetry.registry()
_M_STEPS = _REG.counter(
    "repro_service_steps_total", "Batched service step() calls")
_M_GENS = _REG.counter(
    "repro_useful_gens_total", "Active-slot generations actually served")
_M_HARVESTED = _REG.counter(
    "repro_jobs_harvested_total", "Jobs harvested at budget/target")
_M_CANCELLED = _REG.counter(
    "repro_jobs_cancelled_total", "In-flight slots freed early by cancel()")
_M_STEP_MS = _REG.histogram(
    "repro_service_step_ms", "Wall ms per batched service step",
    buckets=telemetry.DEFAULT_LATENCY_BUCKETS_MS)
_M_BEST = _REG.gauge(
    "repro_pool_best_metric",
    "Best combined metric across a pool's active slots (live convergence)")

# per-job convergence ring depth: (gens, metric) pairs at step boundaries
CONVERGENCE_RING = 256
# tail length surfaced through ProgressUpdate / stats() (the full ring
# stays on the job and on JobHandle.trace())
CONVERGENCE_TAIL = 8

_POOL_COUNTER = itertools.count(1)


def make_job_specs(n: int, pop_size: int, budget: int, seed: int = 0,
                   eta_range=(5.0, 25.0), mut_range=(0.05, 0.3),
                   fused: bool = False) -> List[Dict]:
    """Synthetic placement workload: n NSGA-II jobs with jittered float
    hyperparameters (shared by the CLI demo, the example, and the bench,
    so they all exercise the same traffic shape).

    `fused=True` routes every job's evaluation through the fused Pallas
    pipeline (`kernels.fused_eval`); it is a static config field, so fused
    and unfused jobs belong to different pools."""
    from repro.core import nsga2
    rng = np.random.default_rng(seed)
    return [dict(seed=seed * 10_000 + i, budget=budget,
                 cfg=nsga2.NSGA2Config(
                     pop_size=pop_size,
                     sbx_eta=float(rng.uniform(*eta_range)),
                     real_mut_prob=float(rng.uniform(*mut_range)),
                     fused=fused))
            for i in range(n)]


@dataclasses.dataclass
class PlacementJob:
    jid: int
    cfg: Any                       # full config (floats may differ per job)
    seed: int
    budget: int                    # generation budget
    target: Optional[float]        # finish early if combined metric <= this
    slot: int = -1
    gens: int = 0                  # generations run so far
    warm: bool = False             # seeded via submit(init_state=...)
    done: bool = False
    cancelled: bool = False        # slot freed early by cancel()
    best_objs: Optional[np.ndarray] = None   # [2] = (wl^2, max bbox)
    metric: float = float("inf")             # combined metric of best_objs
    genotype: Any = None                     # best full genotype at harvest
    trace_id: Optional[str] = None           # observability only
    # per-step convergence ring: (gens, metric) recorded at every step
    # boundary the job was alive for -- the paper's Fig. 7 curve as a
    # live signal (bounded; never read by jitted code)
    history: Any = dataclasses.field(
        default_factory=lambda: collections.deque(maxlen=CONVERGENCE_RING))


class PlacementService:
    """Continuous-batching placement engine for one `Problem`."""

    def __init__(self, problem: Problem, base_cfg, algo: str = "nsga2",
                 n_slots: int = 8, gens_per_step: int = 4, seed: int = 0,
                 islands: Optional[IslandConfig] = None,
                 label: Optional[str] = None):
        self.problem, self.algo = problem, algo
        self.n_slots, self.gens_per_step = n_slots, gens_per_step
        # observability-only pool name (metric label / span attr); the
        # scheduler passes its pool-signature label, standalone pools get
        # a process-unique default
        self.label = label or f"pool{next(_POOL_COUNTER)}/{algo}"
        if tracing.enabled():
            tracing.tracer().begin("pool.build", pool=self.label,
                                   n_slots=n_slots, algo=algo)
        # island topology is static pool identity, exactly like pop_size:
        # P > 1 swaps the slot programs for their island-stacked mirrors
        # (`core.islands`); P == 1 keeps the original single-population
        # programs bit for bit
        self.islands = islands or IslandConfig()
        self.static_key, base_traced = hyper.split_config(base_cfg)
        self.base_cfg = base_cfg
        self._base_traced = dict(base_traced)   # grow() fills new slots
        self.size_history: List[int] = [n_slots]  # every slot count compiled
        # host mirror of the per-slot traced hyperparameters
        self.traced = {k: np.full(n_slots, v, np.float32)
                       for k, v in base_traced.items()}
        self.active = np.zeros(n_slots, bool)
        self.slot_job: List[Optional[PlacementJob]] = [None] * n_slots
        # per-slot (seed, generation counter): step keys derive from the
        # *job's* seed, never a shared stream, so a job's trajectory is a
        # pure function of (seed, budget, gens_per_step) -- identical on an
        # empty or a fully-loaded pool, reproducible across submissions
        self.slot_seed = np.zeros(n_slots, np.uint32)
        self.slot_gens = np.zeros(n_slots, np.int32)
        self.next_jid = 0
        self.key = jax.random.PRNGKey(seed)
        self.total_steps = 0
        self.useful_gens = 0       # active-slot generations actually served
        self.jobs_cancelled = 0    # slots freed early via cancel()
        # compile observability: the process meter separates *blocking*
        # compiles (on the thread calling submit/step/grow -- the stepping
        # loop's latency) from background prewarm compiles
        # (`prewarm_size`, typically run by `serve.prewarm.Prewarmer`)
        self._meter = compile_cache.meter().install()
        self.blocking_compiles = 0
        self.blocking_compile_secs = 0.0
        self.prewarm_compiles = 0
        self.prewarm_compile_secs = 0.0
        self._prewarmed_sizes: set = set()
        self._created_at = time.perf_counter()
        self._first_gen_ms: Optional[float] = None

        # per-pool jitted programs; problem/algo/static config (and the
        # island config) are closure constants, so each compiles exactly
        # once for the pool's shapes.  Step keys derive inside the program
        # from (slot seed, slot gens), so the host ships two small int
        # arrays, not key material.
        icfg = self.islands
        if icfg.active:
            self._init_fn = jax.jit(functools.partial(
                islands_mod.member_init, problem, algo, self.static_key,
                icfg))
            self._fill_fn = functools.partial(
                islands_mod._vinit, problem, algo, self.static_key, icfg)
        else:
            self._init_fn = jax.jit(functools.partial(
                portfolio.member_init, problem, algo, self.static_key))
            self._fill_fn = functools.partial(
                portfolio._vinit, problem, algo, self.static_key)
        # warm-start init: the seed block rides as a traced operand at the
        # pool's canonical shape (`warmstart.seed_rows`), so transfer-seeded
        # jobs share ONE compiled warm-init regardless of their hyperparams.
        # Islands pools seed island 0 and let migration spread it.
        self._seed_rows = warmstart.seed_rows(algo, self.static_key)
        if icfg.active:
            self._warm_init_fn = jax.jit(functools.partial(
                islands_mod.member_warm_init, problem, algo,
                self.static_key, icfg))
        else:
            self._warm_init_fn = jax.jit(functools.partial(
                warmstart.member_warm_init, problem, algo, self.static_key))

        def _step(traced, states, seeds, gens):
            def one(tr, st, s, g):
                key = jax.random.fold_in(jax.random.PRNGKey(s), g)
                if icfg.active:
                    # g doubles as the migration phase: boundaries are
                    # counted in global generations, invariant to
                    # gens_per_step chunking and admission timing
                    return islands_mod.member_round(
                        problem, algo, self.static_key, icfg,
                        gens_per_step, tr, st, key, g)
                return portfolio.member_round(
                    problem, algo, self.static_key, gens_per_step,
                    tr, st, key)
            return jax.vmap(one)(traced, states, seeds, gens)

        self._step_fn = jax.jit(_step)

        # fill the pool with throwaway states so step() shapes exist from
        # the first call (vacant slots evolve garbage; it is never read)
        # per-pool step-latency histogram (the registry-global one
        # aggregates across pools; this instance feeds stats())
        self._step_hist = telemetry.Histogram(
            "step_ms", buckets=telemetry.DEFAULT_LATENCY_BUCKETS_MS)

        k_fill = jax.random.fold_in(self.key, 0x5eed)
        with self._blocking():
            self.states = self._fill_fn(self._traced_dev(),
                                        jax.random.split(k_fill, n_slots))
        if tracing.enabled():
            tracing.tracer().end("pool.build", pool=self.label,
                                 n_slots=n_slots, algo=algo)

    @contextlib.contextmanager
    def _blocking(self):
        """Attribute compiles on the calling thread to this pool's
        blocking counters (the stepping loop's compile latency)."""
        with self._meter.measure() as m:
            yield
        self.blocking_compiles += m.compiles
        self.blocking_compile_secs += m.secs

    # ------------------------------------------------------------- admit

    def submit(self, cfg=None, seed: Optional[int] = None, budget: int = 64,
               target: Optional[float] = None, init_state=None,
               jitter: float = 0.15,
               sigma_shrink: float = 0.25) -> Optional[int]:
        """Admit one job; returns its jid, or None if the pool is full.

        The canonical form is `submit(request)` with a
        `serve.api.JobRequest` as the only argument; the kwarg form
        survives as a deprecated shim that builds the same request
        (results are bitwise identical -- the shim only repackages
        arguments).

        Budgets are quantized UP to the pool's `gens_per_step` granularity
        (the batched step advances whole steps only); `job.budget` records
        the quantized value, which the job then runs exactly.

        `init_state` warm-starts the job from a seed genotype (or stacked
        population / reduced perm tuple) on *this* pool's problem --
        typically `transfer.migrate(base, target, champion)`.  The seed is
        padded/truncated to the pool's static shape on the host and turned
        into an algorithm state by one per-pool jitted warm-init program
        (`core.warmstart`): NSGA-II/GA populations keep the seed at row 0
        and fill the rest with `jitter`-scaled copies, CMA-ES starts its
        mean at the seed with `sigma0 * sigma_shrink`, SA starts its chain
        there.  Warm jobs stay reproducible: the result is a pure function
        of (config, seed, budget, init_state, jitter, sigma_shrink).
        """
        if isinstance(cfg, JobRequest):
            request = cfg
        else:
            request = api.deprecated_kwargs_request(
                "PlacementService", cfg=cfg, seed=seed, budget=budget,
                target=target, init_state=init_state, jitter=jitter,
                sigma_shrink=sigma_shrink, algo=self.algo)
        return self.submit_request(request)

    def submit_request(self, request: JobRequest) -> Optional[int]:
        """`submit()` on the unified request type (no shim, no warning):
        admit one job described by a `serve.api.JobRequest`; returns its
        jid, or None when the pool is full.

        Routing fields are validated, never silently re-routed: a request
        whose `algo` or `islands` disagrees with this pool raises (the
        scheduler is the layer that routes mixed traffic)."""
        if request.algo is not None and request.algo != self.algo:
            raise ValueError(
                f"request.algo={request.algo!r} does not match this "
                f"pool's algo={self.algo!r}; route via PlacementScheduler")
        if (request.islands is not None
                and request.islands != self.islands):
            raise ValueError(
                f"request.islands={request.islands} does not match this "
                f"pool's islands={self.islands}; route via "
                "PlacementScheduler")
        if (request.gens_per_step is not None
                and request.gens_per_step != self.gens_per_step):
            raise ValueError(
                f"request.gens_per_step={request.gens_per_step} does not "
                f"match this pool's gens_per_step={self.gens_per_step}")
        cfg = request.resolved_cfg(self.base_cfg)
        seed, target = request.seed, request.target
        init_state = request.init_state
        jitter, sigma_shrink = request.jitter, request.sigma_shrink
        budget = -(-request.budget // self.gens_per_step) \
            * self.gens_per_step
        static_key, traced = hyper.split_config(cfg)
        if static_key != self.static_key:
            raise ValueError(
                "job config disagrees with the pool's static fields "
                f"({static_key[1]} vs {self.static_key[1]}); "
                "open a separate pool for it")
        free = np.where(~self.active)[0]
        if len(free) == 0:
            return None
        slot = int(free[0])
        seed = self.next_jid if seed is None else seed
        trace_id = request.trace_id
        if tracing.enabled() and trace_id is None:
            # direct pool submission (no scheduler/front-end above us):
            # this layer is the outermost, so it mints and announces
            trace_id = tracing.new_trace_id()
            tracing.tracer().instant("job.submit", trace_id,
                                     algo=self.algo, budget=budget)
        job = PlacementJob(self.next_jid, cfg, seed, budget, target,
                           slot=slot, warm=init_state is not None,
                           trace_id=trace_id)
        self.next_jid += 1
        if tracing.enabled():
            tracing.tracer().instant("job.admitted", trace_id,
                                     slot=slot, pool=self.label,
                                     warm=job.warm)
        traced_dev = {k: jnp.float32(v) for k, v in traced.items()}
        with self._blocking():
            if init_state is None:
                state1 = self._init_fn(traced_dev, jax.random.PRNGKey(seed))
            else:
                pop, fresh = warmstart.canonicalize(
                    self.problem, init_state, self._seed_rows)
                state1 = self._warm_init_fn(
                    traced_dev, jax.tree.map(jnp.asarray, pop),
                    jnp.asarray(fresh), jnp.float32(jitter),
                    jnp.float32(sigma_shrink), jax.random.PRNGKey(seed))
        # splice the single job state into the pool at `slot`
        self.states = jax.tree.map(
            lambda pool, one: pool.at[slot].set(one), self.states, state1)
        for k, v in traced.items():
            self.traced[k][slot] = v
        self._traced_cache = None          # hyperparameter row changed
        self.slot_seed[slot] = np.uint32(seed)
        self.slot_gens[slot] = 0
        self.active[slot] = True
        self.slot_job[slot] = job
        return job.jid

    # ------------------------------------------------------------- cancel

    def cancel(self, jid: int) -> bool:
        """Cancel an in-flight job: its slot is freed immediately (the
        vacant slot keeps evolving garbage that is never read, exactly
        like a harvested one) and is reusable by the next `submit()`.

        Call between `step()`s -- the step boundary.  The async front-end
        (`serve.frontend`) guarantees this by executing cancels on the
        stepping thread; direct callers own the discipline themselves.
        Returns False when the jid is not currently in flight (already
        harvested, cancelled, or never admitted).  Cancellation cannot
        perturb co-tenant jobs: their trajectories depend only on their
        own (seed, gens), never on slot occupancy."""
        for slot in np.where(self.active)[0]:
            job = self.slot_job[slot]
            if job is not None and job.jid == jid:
                job.cancelled = True
                self.active[slot] = False
                self.slot_job[slot] = None
                self.jobs_cancelled += 1
                _M_CANCELLED.inc()
                if tracing.enabled():
                    tracing.tracer().instant(
                        "job.cancelled", job.trace_id,
                        slot=int(slot), gens=job.gens)
                return True
        return False

    def job(self, jid: int) -> Optional[PlacementJob]:
        """The in-flight job with this jid (None once harvested/cancelled
        -- finished jobs are returned by `step()`, not looked up here)."""
        for slot in np.where(self.active)[0]:
            job = self.slot_job[slot]
            if job is not None and job.jid == jid:
                return job
        return None

    def inflight(self) -> List[PlacementJob]:
        """Snapshot of the jobs currently occupying slots (progress
        streaming reads `gens`/`metric`/`best_objs` off these between
        steps)."""
        return [self.slot_job[slot] for slot in np.where(self.active)[0]
                if self.slot_job[slot] is not None]

    # -------------------------------------------------------------- grow

    def grow(self, n_slots: int) -> None:
        """Rebuild the pool at a larger static slot count, carrying every
        live slot's state over on the host.

        The slot axis is a static shape, so the batched step compiles once
        per *size* -- which is why callers (the scheduler's autoscaler)
        restrict sizes to a small geometric ladder rather than growing by
        one.  In-flight jobs are untouched: their states, hyperparameter
        rows, seeds and generation counters keep their slot index, and a
        job's trajectory depends only on (seed, gens) -- never the batch
        width -- so results stay identical to a never-grown pool.  New
        slots arrive vacant, filled with throwaway states (same discipline
        as construction).
        """
        if n_slots <= self.n_slots:
            raise ValueError(
                f"grow() only grows: {n_slots} <= current {self.n_slots}")
        if tracing.enabled():
            tracing.tracer().begin("pool.grow", pool=self.label,
                                   from_slots=self.n_slots,
                                   to_slots=n_slots)
        extra = n_slots - self.n_slots
        k_fill = jax.random.fold_in(self.key, 0x5eed + n_slots)
        fill_traced = {k: jnp.full((extra,), v, jnp.float32)
                       for k, v in self._base_traced.items()}
        with self._blocking():
            fill = self._fill_fn(fill_traced,
                                 jax.random.split(k_fill, extra))
            self.states = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0),
                self.states, fill)
        self.traced = {
            k: np.concatenate(
                [v, np.full(extra, self._base_traced[k], np.float32)])
            for k, v in self.traced.items()}
        self._traced_cache = None
        self.active = np.concatenate([self.active, np.zeros(extra, bool)])
        self.slot_job.extend([None] * extra)
        self.slot_seed = np.concatenate(
            [self.slot_seed, np.zeros(extra, np.uint32)])
        self.slot_gens = np.concatenate(
            [self.slot_gens, np.zeros(extra, np.int32)])
        self.n_slots = n_slots
        self.size_history.append(n_slots)
        if tracing.enabled():
            tracing.tracer().end("pool.grow", pool=self.label,
                                 to_slots=n_slots)

    # ----------------------------------------------------------- prewarm

    def prewarm_size(self, n_slots: int) -> bool:
        """Ahead-of-time compile the programs a future `grow(n_slots)`
        needs: the fill at the extra-slot width and the batched step (and
        its combined-metric epilogue) at the full `n_slots` width.

        Runs the pool's OWN jitted callables on throwaway inputs of the
        target shapes, so the later `grow()` + `step()` hit the in-memory
        jit caches and perform zero blocking compiles -- the grow becomes
        pure host-side state surgery.  Compiles land in the prewarm
        counters, not the blocking ones; designed to run on a background
        thread (`serve.prewarm.Prewarmer`) while the pool keeps stepping
        at its current size (only array *shapes* matter here, so racing a
        concurrent step is benign).  Returns True when work was done,
        False for an already-prewarmed or non-growing size.
        """
        base, states = self.n_slots, self.states   # snapshot
        if n_slots <= base or n_slots in self._prewarmed_sizes:
            return False
        if tracing.enabled():
            tracing.tracer().begin("pool.prewarm_size", pool=self.label,
                                   n_slots=n_slots)
        extra = n_slots - base
        with self._meter.measure() as m:
            k_fill = jax.random.fold_in(self.key, 0x9ae + n_slots)
            fill_traced = {k: jnp.full((extra,), v, jnp.float32)
                           for k, v in self._base_traced.items()}
            fill = self._fill_fn(fill_traced,
                                 jax.random.split(k_fill, extra))
            probe = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b], axis=0), states, fill)
            # operands built exactly as step() builds them (jnp.array
            # copies of numpy mirrors): the per-(dtype, width) host-copy
            # programs compile here too, not in the stepping loop
            traced = {k: jnp.array(np.full(n_slots, v, np.float32))
                      for k, v in self._base_traced.items()}
            _, best = self._step_fn(traced, probe,
                                    jnp.array(np.zeros(n_slots, np.uint32)),
                                    jnp.array(np.zeros(n_slots, np.int32)))
            # step()'s epilogue ops compile per slot-count too
            jax.block_until_ready(O.combined_metric(best))
        self._prewarmed_sizes.add(n_slots)
        self.prewarm_compiles += m.compiles
        self.prewarm_compile_secs += m.secs
        if tracing.enabled():
            tracing.tracer().end("pool.prewarm_size", pool=self.label,
                                 n_slots=n_slots, compiles=m.compiles)
        return True

    # -------------------------------------------------------------- step

    _traced_cache: Optional[Dict[str, jnp.ndarray]] = None

    def _traced_dev(self) -> Dict[str, jnp.ndarray]:
        """Device copy of the per-slot hyperparameters, re-uploaded only
        when submit() changed a row (the step loop reuses the cache).

        jnp.array (copy=True), NOT asarray: CPU jax may zero-copy a numpy
        buffer, and submit() mutates these mirrors in place -- an aliased
        buffer would let a later submit corrupt an in-flight step."""
        if self._traced_cache is None:
            self._traced_cache = {k: jnp.array(v)
                                  for k, v in self.traced.items()}
        return self._traced_cache

    def step(self) -> List[PlacementJob]:
        """Advance every slot `gens_per_step` generations in one jitted
        call; harvest and return newly finished jobs."""
        if not self.active.any():
            return []
        n_active = int(self.active.sum())
        traced_on = tracing.enabled()
        if traced_on:
            tracing.tracer().begin("pool.step", pool=self.label,
                                   active=n_active)
        t_step = time.perf_counter()
        # jnp.array copies: the numpy mirrors are mutated in place below
        # and by submit(), and CPU jax may otherwise alias their buffers
        # while the dispatched step is still consuming them
        with self._blocking():
            self.states, best = self._step_fn(
                self._traced_dev(), self.states,
                jnp.array(self.slot_seed), jnp.array(self.slot_gens))
        self.total_steps += 1
        self.useful_gens += int(self.active.sum()) * self.gens_per_step
        self.slot_gens += self.gens_per_step
        best = np.asarray(best)
        metric = np.asarray(O.combined_metric(best))
        if self._first_gen_ms is None:
            # first generations actually served: the pool's cold-start
            # latency (construction + first submit + first step, compiles
            # included) -- the number the compile bench/CI budget watches
            self._first_gen_ms = (time.perf_counter()
                                  - self._created_at) * 1e3
        finished = []
        best_active = float("inf")
        for slot in np.where(self.active)[0]:
            job = self.slot_job[slot]
            job.gens += self.gens_per_step
            job.best_objs = best[slot]
            job.metric = float(metric[slot])
            # live convergence: one (gens, metric) point per step boundary
            job.history.append((job.gens, job.metric))
            best_active = min(best_active, job.metric)
            hit_target = job.target is not None and job.metric <= job.target
            if job.gens >= job.budget or hit_target:
                self._harvest(slot, job)
                finished.append(job)
                self.active[slot] = False
                self.slot_job[slot] = None
                _M_HARVESTED.inc()
                if traced_on:
                    tracing.tracer().instant(
                        "job.harvested", job.trace_id, slot=int(slot),
                        gens=job.gens, metric=job.metric,
                        hit_target=hit_target)
        step_ms = (time.perf_counter() - t_step) * 1e3
        self._step_hist.observe(step_ms)
        _M_STEP_MS.observe(step_ms)
        _M_STEPS.inc()
        _M_GENS.inc(int(self.active.sum() + len(finished))
                    * self.gens_per_step)
        if best_active != float("inf"):
            _M_BEST.set(best_active, pool=self.label)
        if traced_on:
            tracing.tracer().end("pool.step", pool=self.label,
                                 harvested=len(finished))
        return finished

    def _harvest(self, slot: int, job: PlacementJob) -> None:
        state = jax.tree.map(lambda a: a[slot], self.states)
        if self.islands.active:
            g, objs = islands_mod.best_genotype(self.problem, self.algo,
                                                state, job.cfg)
        else:
            g, objs = portfolio.best_genotype(self.problem, self.algo,
                                              state, job.cfg)
        job.genotype = jax.tree.map(np.asarray, g)
        job.best_objs = np.asarray(objs)
        job.metric = float(O.combined_metric(job.best_objs))
        job.done = True

    # ------------------------------------------------------- conveniences

    @property
    def step_compiles(self) -> int:
        """Distinct compilations of the batched step: must stay 1 for a
        fixed-size pool, and at most `len(size_history)` after `grow()`
        (one compile per slot-count ladder size, never per job).

        Reads jax's private jit-cache counter; returns -1 (unknown) if a
        jax upgrade removes it, rather than breaking the service."""
        try:
            return self._step_fn._cache_size()
        except AttributeError:
            return -1

    def lowered_step(self):
        """The batched step lowered at the pool's current shapes, for
        reading the program the pool runs (`.compile().as_text()`,
        `.compile().memory_analysis()`)."""
        return self._step_fn.lower(
            self._traced_dev(), self.states,
            jnp.array(self.slot_seed), jnp.array(self.slot_gens))

    def run_jobs(self, specs: List[Dict]) -> List[PlacementJob]:
        """Rolling admission: submit specs as slots free up, step until
        every job finishes.  Each spec is a `serve.api.JobRequest` or a
        dict of its fields (the `make_job_specs` shape)."""
        queue = [s if isinstance(s, JobRequest)
                 else JobRequest(algo=self.algo, **s) for s in specs]
        done: List[PlacementJob] = []
        while queue or self.active.any():
            while queue:
                if self.submit_request(queue[0]) is None:
                    break
                queue.pop(0)
            done.extend(self.step())
        return done

    def stats(self) -> ServiceStats:
        return api.stats_payload(
            n_slots=self.n_slots,
            gens_per_step=self.gens_per_step,
            steps=self.total_steps,
            useful_gens=self.useful_gens,
            step_compiles=self.step_compiles,
            sizes=list(self.size_history),
            n_islands=self.islands.n_islands,
            migrate_every=self.islands.migrate_every,
            jobs_cancelled=self.jobs_cancelled,
            # compile observability (process meter + this pool's split of
            # blocking vs prewarmed compiles; see runtime.compile_cache)
            blocking_compiles=self.blocking_compiles,
            blocking_compile_secs=round(self.blocking_compile_secs, 3),
            prewarm_compiles=self.prewarm_compiles,
            prewarm_compile_secs=round(self.prewarm_compile_secs, 3),
            prewarmed_sizes=sorted(self._prewarmed_sizes),
            time_to_first_gen_ms=(
                None if self._first_gen_ms is None
                else round(self._first_gen_ms, 1)),
            compiles_total=self._meter.compiles,
            recompiles_total=self._meter.recompiles,
            compile_secs_total=round(self._meter.compile_secs, 3),
            persistent_cache_dir=compile_cache.enabled_dir(),
            # --- appended under schema_version 2 (observability) ---
            step_ms_hist=self._step_hist.to_dict(),
            convergence={
                job.jid: list(job.history)[-CONVERGENCE_TAIL:]
                for job in self.inflight()},
            tracing_enabled=tracing.enabled(),
        )
