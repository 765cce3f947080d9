"""Placement service + portfolio + transfer + scheduler -> BENCH_placement.json.

    PYTHONPATH=src python -m benchmarks.bench_service [--smoke|--full] [--out P]
    PYTHONPATH=src python -m benchmarks.compile_probe --pair --merge-into P

The serving-perf trajectory, one JSON per run.  Four measurements:

  * **service**: the continuous-batching placement engine runs >= 8
    concurrent jobs batched into one compiled step; reports jobs/sec,
    generations/sec (active-slot generations actually served) and
    candidate evaluations/sec (gens x pop), all measured after the single
    step compile.
  * **portfolio**: >= 4 hyperparameter configs run as ONE vmapped jitted
    program (`core.portfolio.run_portfolio`); verifies the champion and
    every per-member best match equivalent independent `evolve.run` calls,
    and reports the batched-vs-sequential speedup (both post-compile).
  * **transfer**: warm vs cold gens-to-target on a sibling-device pair
    (paper Table II direction).  A champion converged on the base device
    is migrated (`core.transfer`) and submitted via
    `PlacementService.submit(init_state=...)`; both jobs chase the
    migrated champion's own metric.  `warm_beats_cold` must stay true.
  * **scheduler**: a heterogeneous job stream (mixed pop sizes, algorithms
    and devices) served by `serve.scheduler.PlacementScheduler`; reports
    jobs/sec, the pool count, and compiles-per-pool (each pool's batched
    step must compile exactly once -- `all_single_compile`).
  * **cache**: the champion store (`serve.champion_store`) end to end on
    the sibling pair: a cold run populates the store, an exact-signature
    resubmission is served in O(ms) with ZERO generations and no slot
    (`cache_hit_exact_correct` must stay true), and a sibling-device job
    is warm-started by signature discovery, reaching the migrated
    champion's metric in <= 1/4 the cold generations
    (`sibling_within_quarter`).
  * **policy**: completion-order contract of the stepping policies: an
    urgent (tight-deadline) job submitted after bulk work finishes FIRST
    under `deadline` and does NOT under `round_robin`
    (`policy_deadline_meets_order` must stay true); the `priority` rank
    is reported alongside.
  * **autoscale**: a 1-slot pool absorbing a burst grows along the
    geometric slot ladder; compiles stay bounded by the number of ladder
    sizes (`compiles_within_ladder`) and every job's objectives match a
    standalone never-grown service (`jobs_match_standalone`).
  * **islands**: within-job scaling (`core.islands`): P island
    sub-populations per slot with ring champion migration reach a
    single-population run's combined-metric target in measurably fewer
    wallclock steps at an equal total evaluation budget
    (`speedup_steps`, `islands_fewer_steps`); an islands pool still
    compiles its batched step exactly once (`islands_single_compile`)
    and `islands(P=1)` is bitwise identical to the single-population
    `evolve.run` (`islands_match_single_pop`) -- both hard CI gates.

  * **frontend**: the asyncio job front-end (`serve.frontend`) under 32
    concurrent clients: p50/p99 submit->champion latency and jobs/sec
    (post-compile), with `max_queue < n_clients` so part of the load
    experiences real backpressure.  `concurrent_match_sequential` (every
    client's best objectives bitwise-match the same requests hand-pumped
    through a sequential scheduler) is a hard CI gate: the stepping
    thread changes latency only, never results.

  * **telemetry**: observability overhead contract (`runtime.telemetry` /
    `serve.tracing`).  The same front-end workload runs with tracing OFF
    (the default serving configuration) and ON (in-memory span ring, no
    sink), interleaved best-of-k.  `jobs_per_sec_off` is the number the
    disabled-overhead gate rides on: `check_bench --baseline` HARD-FAILS
    when it regresses more than 2% at an identical workload shape --
    instrumented-but-disabled serving must cost nothing.  The enabled-path
    overhead (`enabled_overhead_pct`) is warn-only trend data.
    `trace_events_complete` (every traced run reconciled exactly: one
    `job.submit` and one terminal event per job) is a hard CI gate.

  * **compile**: cold-start latency vs the persistent compilation cache
    (`runtime.compile_cache`).  This process holds the device, so it
    cannot start the probe processes that measure a cold start: the
    section comes from its own command, `benchmarks.compile_probe --pair
    --merge-into P`, run after this one.  `recompiles_warm_zero` (the
    warm probe performed ZERO real XLA compiles) and `warm_ttfg_5x` (warm
    time-to-first-generation is >= 5x faster than cold) are hard CI
    gates; the raw cold/warm ttfg and compile counts are trend keys.

  * **kernels**: the fused Pallas evaluation pipeline
    (`kernels.fused_eval`) vs the unfused two-op dispatch at EQUAL
    workload shape: candidate evaluations/sec for both paths (best-of-k
    jitted steady state), the fused/unfused speedup, and two differential
    correctness gates -- the tiled kernel (compiled on a TPU, interpret
    mode elsewhere) matching
    `ref.fused_eval_ref` on the real problem extents (`fused_match_ref`)
    and the fused domination counts matching the domination matrix
    (`dom_counts_match_ref`).  Both booleans are hard CI gates; the
    throughputs are warn-only trend keys.

JSON contract (consumed by `benchmarks.check_bench` and future trend
tooling -- keys are append-only):
  bench, created_unix, mode, device, jax_version, backend,
  service.{n_slots,n_jobs,pop_size,budget_gens,gens_per_step,wall_s,
           jobs_per_sec,gens_per_sec,evals_per_sec,step_compiles},
  portfolio.{n_configs,n_gens,pop_size,wall_s_batched,wall_s_independent,
             speedup,champion_matches,members_match},
  transfer.{base_device,device,base_gens,base_pop,pop_size,budget_gens,
            gens_per_step,target_metric,cold_gens,warm_gens,speedup,
            warm_beats_cold},
  scheduler.{n_jobs,n_pools,budget_gens,gens_per_step,n_slots,wall_s,
             jobs_per_sec,all_single_compile,pools},
  cache.{base_device,device,pop_size,budget_gens,gens_per_step,cold_gens,
         exact_hit_gens,exact_hit_wall_ms,sibling_warm_gens,
         sibling_speedup,sibling_within_quarter,cache_hit_exact_correct},
  policy.{device,budget_gens,gens_per_step,n_bulk,rr_urgent_rank,
          edf_urgent_rank,priority_urgent_rank,policy_deadline_meets_order},
  autoscale.{n_jobs,n_slots_initial,max_slots,pop_size,sizes,
             step_compiles,budget_gens,gens_per_step,wall_s,jobs_per_sec,
             compiles_within_ladder,jobs_match_standalone},
  islands.{n_islands,migrate_every,pop_size,budget_gens,gens_per_step,
           target_metric,single_gens_to_target,islands_gens_to_target,
           single_hit_target,islands_hit_target,wall_s_islands,
           speedup_steps,islands_fewer_steps,islands_single_compile,
           islands_match_single_pop},
  kernels.{pop_size,n_nets,n_units,n_gids,reps,evals_per_sec_fused,
           evals_per_sec_unfused,fused_speedup,fused_match_ref,
           dom_counts_match_ref},
  frontend.{n_clients,n_slots,max_queue,pop_size,budget_gens,
            gens_per_step,wall_s,jobs_per_sec,submit_to_champion_p50_ms,
            submit_to_champion_p99_ms,backpressure_waits,step_compiles,
            concurrent_match_sequential},
  telemetry.{n_clients,n_slots,max_queue,pop_size,budget_gens,
             gens_per_step,rounds,jobs_per_sec_off,jobs_per_sec_on,
             enabled_overhead_pct,trace_events_complete},
  compile.{pop_size,n_slots,gens_per_step,budget_gens,grow_to,backend,
           cache_salt,
           ttfg_cold_ms,ttfg_warm_ms,ttfg_speedup,compiles_cold,
           recompiles_cold,compile_secs_cold,compiles_warm,
           recompiles_warm,cache_hits_warm,compile_secs_warm,
           recompiles_warm_zero,warm_ttfg_5x}
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from benchmarks import common
from repro.core import evolve, nsga2, cmaes, transfer, portfolio
from repro.core import objectives as O
from repro.core.islands import IslandConfig
from repro.runtime import compile_cache
from repro.serve.api import JobRequest
from repro.serve.champion_store import ChampionStore
from repro.serve.placement_service import PlacementService, make_job_specs
from repro.serve.scheduler import PlacementScheduler


def bench_service(prob, n_jobs: int, n_slots: int, pop: int, budget: int,
                  gens_per_step: int) -> dict:
    base = nsga2.NSGA2Config(pop_size=pop)
    svc = PlacementService(prob, base, n_slots=n_slots,
                           gens_per_step=gens_per_step)

    # warmup: compiles the init + step programs (one job is enough)
    svc.run_jobs(make_job_specs(1, pop, budget, seed=99))
    svc.useful_gens, svc.total_steps = 0, 0

    t0 = time.perf_counter()
    done = svc.run_jobs(make_job_specs(n_jobs, pop, budget))
    wall = time.perf_counter() - t0
    assert len(done) == n_jobs and all(j.done for j in done)
    s = svc.stats()
    return {
        "n_slots": n_slots, "n_jobs": n_jobs, "pop_size": pop,
        "budget_gens": budget, "gens_per_step": gens_per_step,
        "wall_s": round(wall, 4),
        "jobs_per_sec": round(n_jobs / wall, 3),
        "gens_per_sec": round(s["useful_gens"] / wall, 2),
        "evals_per_sec": round(s["useful_gens"] * pop / wall, 1),
        "step_compiles": s["step_compiles"],
    }


def bench_portfolio(prob, n_cfgs: int, pop: int, n_gens: int) -> dict:
    etas = np.linspace(5.0, 25.0, n_cfgs)
    muts = np.linspace(0.05, 0.3, n_cfgs)
    cfgs = [nsga2.NSGA2Config(pop_size=pop, sbx_eta=float(e),
                              real_mut_prob=float(m))
            for e, m in zip(etas, muts)]
    keys = jax.random.split(jax.random.PRNGKey(7), n_cfgs)

    # batched: warmup compile, then timed steady-state call
    portfolio.run_portfolio(prob, "nsga2", cfgs, keys=keys, n_gens=n_gens)
    t0 = time.perf_counter()
    res = portfolio.run_portfolio(prob, "nsga2", cfgs, keys=keys,
                                  n_gens=n_gens)
    wall_batched = time.perf_counter() - t0

    # independent references (same keys): warmup each, then timed
    ind_best = []
    wall_ind = 0.0
    for cfg, k in zip(cfgs, keys):
        evolve.run(prob, "nsga2", cfg, k, n_gens)          # compile
        dt, (st, _) = common.timed(evolve.run, prob, "nsga2", cfg, k, n_gens)
        wall_ind += dt
        ind_best.append(np.asarray(evolve.state_best_objs(st)))
    ind_best = np.stack(ind_best)
    members_match = bool(np.allclose(res.best_objs, ind_best, rtol=1e-5))
    ind_champ = int(np.argmin(O.combined_metric(ind_best)))
    return {
        "n_configs": n_cfgs, "n_gens": n_gens, "pop_size": pop,
        "wall_s_batched": round(wall_batched, 4),
        "wall_s_independent": round(wall_ind, 4),
        "speedup": round(wall_ind / max(wall_batched, 1e-9), 2),
        "champion_matches": bool(res.champion == ind_champ),
        "members_match": members_match,
    }


def bench_transfer(base_dev: str, dst_dev: str, base_pop: int,
                   base_gens: int, pop: int, budget: int,
                   gens_per_step: int) -> dict:
    """Warm vs cold gens-to-target on a sibling pair (paper Table II).

    Target = the migrated champion's own combined metric: the warm job
    carries it from generation 0 (elitist seeding), the cold job has to
    re-discover it from random init.
    """
    base_prob = common.problem(base_dev)
    dst_prob = common.problem(dst_dev)
    champ = transfer.converge_champion(base_prob, jax.random.PRNGKey(0),
                                       base_pop, base_gens)
    g_mig = transfer.migrate(base_prob, dst_prob, champ)
    target = float(O.combined_metric(O.evaluate(dst_prob, g_mig)))

    svc = PlacementService(dst_prob, nsga2.NSGA2Config(pop_size=pop),
                           n_slots=2, gens_per_step=gens_per_step)
    svc.submit_request(JobRequest(seed=0, budget=budget, target=target))
    svc.submit_request(JobRequest(seed=0, budget=budget, target=target,
                                  init_state=g_mig))
    done = []
    while svc.active.any():
        done.extend(svc.step())
    cold = next(j for j in done if not j.warm)
    warm = next(j for j in done if j.warm)
    return {
        "base_device": base_dev, "device": dst_dev,
        "base_pop": base_pop, "base_gens": base_gens, "pop_size": pop,
        "budget_gens": budget, "gens_per_step": gens_per_step,
        "target_metric": target,
        "cold_gens": cold.gens, "warm_gens": warm.gens,
        "speedup": round(cold.gens / max(warm.gens, 1), 2),
        "warm_beats_cold": bool(warm.gens < cold.gens),
    }


def bench_scheduler(devices, pops, jobs_per_pool: int, budget: int,
                    n_slots: int, gens_per_step: int) -> dict:
    """Heterogeneous stream: mixed pop sizes x algos x devices, one
    process.  Pools compile lazily in a warmup wave; the timed wave then
    measures steady-state fleet throughput."""
    sch = PlacementScheduler(n_slots=n_slots, gens_per_step=gens_per_step)

    def combos():
        for dev in devices:
            for p in pops:
                yield dev, "nsga2", nsga2.NSGA2Config(pop_size=p)
            yield dev, "cmaes", cmaes.CMAESConfig(pop_size=pops[0])

    # warmup wave: every pool compiles its init + step once
    for dev, algo, cfg in combos():
        sch.submit_request(JobRequest(device=dev, cfg=cfg, algo=algo,
                                      seed=999, budget=gens_per_step))
    sch.run_all()

    n_jobs = 0
    t0 = time.perf_counter()
    for dev, algo, cfg in combos():
        for s in range(jobs_per_pool):
            sch.submit_request(JobRequest(device=dev, cfg=cfg,
                                          algo=algo, seed=s, budget=budget))
            n_jobs += 1
    done = sch.run_all()
    wall = time.perf_counter() - t0
    assert len(done) == n_jobs and all(j.done for j in done)
    stats = sch.stats()
    pools = {label: {"step_compiles": ps["step_compiles"],
                     "useful_gens": ps["useful_gens"]}
             for label, ps in stats["pools"].items()}
    return {
        "n_jobs": n_jobs, "n_pools": stats["n_pools"],
        "budget_gens": budget, "gens_per_step": gens_per_step,
        "n_slots": n_slots,
        "wall_s": round(wall, 4),
        "jobs_per_sec": round(n_jobs / wall, 3),
        "all_single_compile": all(
            p["step_compiles"] in (1, -1) for p in pools.values()),
        "pools": pools,
    }


def bench_cache(base_dev: str, sib_dev: str, pop: int, budget: int,
                gens_per_step: int) -> dict:
    """Champion store end to end: cold run -> exact hit -> sibling warm.

    The exact hit must serve with zero generations and no pool (O(ms));
    the sibling warm start must reach the migrated champion's metric in
    <= 1/4 of the cold gens-to-target (paper Table II direction, now
    decided inside the serving layer by content signatures).
    """
    store = ChampionStore()
    sch = PlacementScheduler(n_slots=2, gens_per_step=gens_per_step,
                             store=store)
    cfg = nsga2.NSGA2Config(pop_size=pop)
    jid_cold = sch.submit_request(JobRequest(device=base_dev, cfg=cfg,
                                             seed=0, budget=budget))
    done = {j.jid: j for j in sch.run_all()}
    champion_metric = done[jid_cold].result.metric

    # exact hit: same signature, reachable target -> instant finished job
    pools_before = sch.stats()["n_pools"]
    target = champion_metric * 1.001
    t0 = time.perf_counter()
    jid_hit = sch.submit_request(JobRequest(device=base_dev, cfg=cfg,
                                            seed=1, budget=budget,
                                            target=target))
    done_hit = {j.jid: j for j in sch.run_all()}
    wall_hit = time.perf_counter() - t0
    hit = done_hit[jid_hit]
    cache_hit_exact_correct = bool(
        hit.cached and hit.result.gens == 0
        and hit.result.metric <= target
        and sch.stats()["n_pools"] == pools_before)

    # sibling warm hit vs a cold control, both chasing the migrated
    # champion's own metric (the store discovers the donor by sibling_key)
    sib_prob = sch.problem(sib_dev)
    entry, kind = store.lookup(sib_prob)
    assert kind == "sibling", kind
    g_mig = store.seed_for(sib_prob, entry)
    sib_target = float(O.combined_metric(O.evaluate(sib_prob, g_mig)))
    cold_sch = PlacementScheduler(n_slots=2, gens_per_step=gens_per_step)
    jid = cold_sch.submit_request(JobRequest(device=sib_dev, cfg=cfg,
                                             seed=0, budget=budget,
                                             target=sib_target))
    cold_gens = {j.jid: j for j in cold_sch.run_all()}[jid].result.gens
    jid = sch.submit_request(JobRequest(device=sib_dev, cfg=cfg,
                                        seed=0, budget=budget,
                                        target=sib_target))
    warm_job = {j.jid: j for j in sch.run_all()}[jid]
    assert warm_job.warm_from_cache
    warm_gens = warm_job.result.gens
    return {
        "base_device": base_dev, "device": sib_dev, "pop_size": pop,
        "budget_gens": budget, "gens_per_step": gens_per_step,
        "cold_gens": cold_gens,
        "exact_hit_gens": hit.result.gens,
        "exact_hit_wall_ms": round(wall_hit * 1e3, 3),
        "sibling_warm_gens": warm_gens,
        "sibling_speedup": round(cold_gens / max(warm_gens, 1), 2),
        "sibling_within_quarter": bool(4 * warm_gens <= cold_gens),
        "cache_hit_exact_correct": cache_hit_exact_correct,
    }


def bench_policy(dev: str, budget: int, gens_per_step: int,
                 n_bulk: int = 2) -> dict:
    """Completion-order contract: an urgent job submitted AFTER bulk work
    finishes first under `deadline` (EDF) and not under `round_robin`."""
    bulk_cfg = nsga2.NSGA2Config(pop_size=16)
    urgent_cfg = nsga2.NSGA2Config(pop_size=8)

    def urgent_rank(policy) -> int:
        sch = PlacementScheduler(n_slots=1, gens_per_step=gens_per_step,
                                 policy=policy)
        for s in range(n_bulk):
            sch.submit_request(JobRequest(device=dev, cfg=bulk_cfg, seed=s,
                                          budget=budget, deadline=1e9,
                                          priority=0.0))
        urgent = sch.submit_request(JobRequest(device=dev, cfg=urgent_cfg,
                                               seed=0, budget=budget,
                                               deadline=1.0, priority=10.0))
        order = [j.jid for j in sch.run_all()]
        return order.index(urgent)

    rr = urgent_rank("round_robin")
    edf = urgent_rank("deadline")
    prio = urgent_rank("priority")
    return {
        "device": dev, "budget_gens": budget,
        "gens_per_step": gens_per_step, "n_bulk": n_bulk,
        "rr_urgent_rank": rr, "edf_urgent_rank": edf,
        "priority_urgent_rank": prio,
        "policy_deadline_meets_order": bool(edf == 0 and rr > 0),
    }


def bench_autoscale(dev: str, n_jobs: int, pop: int, budget: int,
                    gens_per_step: int, max_slots: int = 4) -> dict:
    """Queue-depth autoscaling: a 1-slot pool absorbs a burst by growing
    along the geometric slot ladder.  Compiles stay O(#sizes) and every
    job's result must match a standalone never-grown service."""
    prob = common.problem(dev)
    cfg = nsga2.NSGA2Config(pop_size=pop)
    sch = PlacementScheduler(n_slots=1, gens_per_step=gens_per_step,
                             autoscale=True, autoscale_threshold=2,
                             max_slots=max_slots)
    t0 = time.perf_counter()
    jids = [sch.submit_request(JobRequest(device=dev, cfg=cfg, seed=i,
                                          budget=budget))
            for i in range(n_jobs)]
    done = {j.jid: j for j in sch.run_all()}
    wall = time.perf_counter() - t0
    assert sorted(done) == jids
    (pool_stats,) = sch.stats()["pools"].values()
    sizes = pool_stats["sizes"]
    compiles = pool_stats["step_compiles"]

    ref = PlacementService(prob, cfg, n_slots=1,
                           gens_per_step=gens_per_step)
    ref_objs = {j.seed: j.best_objs for j in ref.run_jobs(
        [dict(seed=i, budget=budget) for i in range(n_jobs)])}
    jobs_match = all(
        np.allclose(done[j].result.best_objs,
                    ref_objs[done[j].result.seed], rtol=1e-5)
        for j in jids)
    return {
        "n_jobs": n_jobs, "n_slots_initial": 1, "max_slots": max_slots,
        "pop_size": pop, "sizes": sizes, "step_compiles": compiles,
        "budget_gens": budget, "gens_per_step": gens_per_step,
        "wall_s": round(wall, 4),
        "jobs_per_sec": round(n_jobs / wall, 3),
        "compiles_within_ladder": bool(compiles == -1
                                       or compiles <= len(sizes)),
        "jobs_match_standalone": bool(jobs_match),
    }


def _islands_match_single_pop(prob, pop: int, n_gens: int = 6) -> bool:
    """Degeneracy gate: `islands(P=1)` is bitwise the single-population
    `evolve.run` -- history AND every final state leaf."""
    cfg = nsga2.NSGA2Config(pop_size=pop)
    key = jax.random.PRNGKey(3)
    st_s, h_s = evolve.run(prob, "nsga2", cfg, key, n_gens)
    st_i, h_i = evolve.run(prob, "nsga2", cfg, key, n_gens,
                           islands=IslandConfig(1, 0))
    ok = np.array_equal(np.asarray(h_s), np.asarray(h_i)[:, 0])
    for a, b in zip(jax.tree.leaves(st_s), jax.tree.leaves(st_i)):
        ok = ok and np.array_equal(np.asarray(a), np.asarray(b)[0])
    return bool(ok)


def _gens_to_target(prob, cfg, islands, seed: int, budget: int,
                    target, gens_per_step: int):
    svc = PlacementService(prob, cfg, n_slots=1,
                           gens_per_step=gens_per_step, islands=islands)
    svc.submit_request(JobRequest(seed=seed, budget=budget,
                                  target=target))
    done = []
    while svc.active.any():
        done.extend(svc.step())
    return done[0]


def bench_islands(prob, pop: int, n_islands: int, migrate_every: int,
                  budget: int, gens_per_step: int) -> dict:
    """Within-job scaling: P islands per slot vs a single population.

    Both contestants chase the same combined-metric target (where a
    single population lands with ~2/3 of the budget) under EQUAL total
    evaluation budgets: the single-population job may run `budget` gens
    at pop evals each, the islands job `budget / P` gens at P x pop
    evals each.  Islands burn their evals in parallel -- P x the
    candidates per service step -- so they reach the target in fewer
    wallclock steps (`speedup_steps`).  `islands_single_compile` (an
    islands pool still compiles its batched step exactly once across
    rolling admission) and `islands_match_single_pop` (P=1 is bitwise
    the single-population run) are hard CI gates.
    """
    cfg = nsga2.NSGA2Config(pop_size=pop)
    match = _islands_match_single_pop(prob, pop)

    probe = _gens_to_target(prob, cfg, None, seed=123,
                            budget=(2 * budget) // 3, target=None,
                            gens_per_step=gens_per_step)
    target = float(probe.metric)
    single = _gens_to_target(prob, cfg, None, 0, budget, target,
                             gens_per_step)
    icfg = IslandConfig(n_islands, migrate_every)
    t0 = time.perf_counter()
    isl = _gens_to_target(prob, cfg, icfg, 0, max(budget // n_islands,
                                                  gens_per_step),
                          target, gens_per_step)
    wall_islands = time.perf_counter() - t0
    # a gens-to-target only counts if the target was actually reached
    # inside the budget -- exhausting the budget is not "reaching"
    single_hit = bool(single.metric <= target)
    islands_hit = bool(isl.metric <= target)

    # single-compile under rolling admission: more jobs than slots, each
    # with its own float hyperparameters, one islands pool
    svc = PlacementService(prob, cfg, n_slots=2,
                           gens_per_step=gens_per_step, islands=icfg)
    done = svc.run_jobs(make_job_specs(3, pop, 2 * gens_per_step, seed=55))
    single_compile = (len(done) == 3 and all(j.done for j in done)
                      and svc.step_compiles in (1, -1))
    return {
        "n_islands": n_islands, "migrate_every": migrate_every,
        "pop_size": pop, "budget_gens": budget,
        "gens_per_step": gens_per_step,
        "target_metric": target,
        "single_gens_to_target": single.gens,
        "islands_gens_to_target": isl.gens,
        "single_hit_target": single_hit,
        "islands_hit_target": islands_hit,
        "wall_s_islands": round(wall_islands, 4),
        "speedup_steps": round(single.gens / max(isl.gens, 1), 2),
        "islands_fewer_steps": bool(islands_hit and
                                    isl.gens < single.gens),
        "islands_single_compile": bool(single_compile),
        "islands_match_single_pop": match,
    }


def bench_kernels(prob, pop: int, reps: int = 40, timed_rounds: int = 12
                  ) -> dict:
    """Fused vs unfused evaluation at equal workload shape + differential
    correctness of the tiled kernel bodies on the problem's real extents.

    Throughput is the best of `timed_rounds` interleaved samples of `reps`
    jitted `evaluate_population` calls (post-compile, block_until_ready),
    reported at 3 significant figures -- run-to-run noise on a shared CI
    machine is well above 0.1%, so finer digits are spurious precision.
    On CPU both paths dispatch to the same ref-oracle composition and
    lower to the same XLA program (verified: identical fusion/while
    counts), so a best-sample gap below the ~3% measurement resolution is
    a tie and reports the pooled best for both paths instead of
    coin-flipping the ordering.
    Correctness checks the Pallas kernels against the `ref.py` oracles on
    the real decode extents: compiled on a TPU, in interpret mode
    elsewhere (the same differential contract as
    `tests/test_fused_eval.py`).
    """
    import jax.numpy as jnp

    from repro.core import genotype as G
    from repro.kernels import fused_eval as FE
    from repro.kernels import ref

    keys = jax.random.split(jax.random.PRNGKey(11), pop)
    popn = jax.vmap(lambda k: G.random_genotype(k, prob))(keys)

    # differential gates on the real extents: the compiled kernels on a
    # TPU, their bodies in interpret mode elsewhere
    interpret = jax.default_backend() != "tpu"
    bx, by = jax.vmap(lambda g: G.decode(prob, g))(popn)
    s, d = jnp.asarray(prob.net_src), jnp.asarray(prob.net_dst)
    w = jnp.asarray(prob.net_w)
    uidx = O.unit_index(prob)
    got = np.asarray(FE.fused_eval_pallas(bx, by, s, d, w, uidx,
                                          interpret=interpret))
    want = np.asarray(ref.fused_eval_ref(bx, by, s, d, w, uidx))
    fused_match_ref = bool(np.allclose(got, want, rtol=1e-5, atol=1e-6))
    objs = jnp.asarray(want)
    dom, cnt = FE.domination_counts_pallas(objs, interpret=interpret)
    dref = np.asarray(ref.domination_ref(objs))
    dom_counts_match_ref = bool(
        np.array_equal(np.asarray(dom.astype(bool)), dref)
        and np.array_equal(np.asarray(cnt), dref.astype(np.int32).sum(0)))

    def sample(fused: bool) -> float:
        t0 = time.perf_counter()
        for _ in range(reps):
            out = O.evaluate_population(prob, popn, fused)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    # warm both compiles, then interleave the timed rounds so clock/cache
    # drift on a busy CI machine cannot systematically favour whichever
    # path happens to be measured first
    jax.block_until_ready(O.evaluate_population(prob, popn, False))
    jax.block_until_ready(O.evaluate_population(prob, popn, True))
    best = {False: float("inf"), True: float("inf")}
    for _ in range(timed_rounds):
        for fused in (False, True):
            best[fused] = min(best[fused], sample(fused))
    pooled = min(best[False], best[True])
    if abs(best[True] - best[False]) / pooled < 0.03:
        best = {False: pooled, True: pooled}      # tie below resolution
    eps_unfused = float(f"{reps * pop / best[False]:.3g}")
    eps_fused = float(f"{reps * pop / best[True]:.3g}")
    return {
        "pop_size": pop,
        "n_nets": int(np.asarray(prob.net_src).shape[0]),
        "n_units": int(prob.n_units),
        "n_gids": int(bx.shape[-1]),
        "reps": reps,
        "evals_per_sec_fused": eps_fused,
        "evals_per_sec_unfused": eps_unfused,
        "fused_speedup": round(eps_fused / max(eps_unfused, 1e-9), 3),
        "fused_match_ref": fused_match_ref,
        "dom_counts_match_ref": dom_counts_match_ref,
    }


def bench_frontend(dev: str, n_clients: int, n_slots: int, pop: int,
                   budget: int, gens_per_step: int, max_queue: int) -> dict:
    """The asyncio front-end under concurrent load (`serve.frontend`).

    `n_clients` concurrent client coroutines each submit a
    `serve.api.JobRequest` and await its champion; with
    `max_queue < n_clients` part of the load experiences real
    backpressure.  Reports p50/p99 submit->champion latency and jobs/sec
    (post-compile: a warmup job compiles the pool's programs before the
    timed wave), plus `concurrent_match_sequential` -- every client's
    best objectives bitwise-match the same request set hand-pumped
    through a sequential scheduler, the determinism hard gate: the
    stepping thread and any admission interleaving change latency only,
    never results.
    """
    import asyncio

    from repro.serve.api import JobRequest
    from repro.serve.frontend import PlacementFrontend

    specs = make_job_specs(n_clients, pop, budget)
    reqs = [JobRequest(device=dev, cfg=s["cfg"], seed=s["seed"],
                       budget=s["budget"]) for s in specs]

    # sequential reference: same requests, hand-pumped scheduler
    seq = PlacementScheduler(n_slots=n_slots, gens_per_step=gens_per_step)
    jids = [seq.submit_request(r) for r in reqs]
    by_jid = {j.jid: j for j in seq.run_all()}
    ref = {r.seed: np.asarray(by_jid[j].result.best_objs)
           for r, j in zip(reqs, jids)}

    async def run():
        sched = PlacementScheduler(n_slots=n_slots,
                                   gens_per_step=gens_per_step)
        lat: list = []

        async def client(req):
            t0 = time.perf_counter()
            handle = await fe.submit(req)
            pj = await handle.wait()
            lat.append(time.perf_counter() - t0)
            return req.seed, np.asarray(pj.best_objs)

        async with PlacementFrontend(sched, max_queue=max_queue) as fe:
            # warmup: the pool's init/step programs compile here, so the
            # timed wave measures serving latency, not XLA
            warm = await fe.submit(JobRequest(
                device=dev, cfg=specs[0]["cfg"], seed=10_000,
                budget=gens_per_step))
            await warm.wait()
            t0 = time.perf_counter()
            results = await asyncio.gather(*[client(r) for r in reqs])
            wall = time.perf_counter() - t0
            stats = fe.stats()
        return dict(results), lat, wall, stats

    got, lat, wall, stats = asyncio.run(run())
    match = all(np.array_equal(ref[s], got[s]) for s in ref)
    p50, p99 = np.percentile(np.array(lat) * 1e3, [50, 99])
    (pool_stats,) = stats["fleet"]["pools"].values()
    return {
        "n_clients": n_clients, "n_slots": n_slots,
        "max_queue": max_queue, "pop_size": pop, "budget_gens": budget,
        "gens_per_step": gens_per_step,
        "wall_s": round(wall, 4),
        "jobs_per_sec": round(n_clients / wall, 3),
        "submit_to_champion_p50_ms": round(float(p50), 2),
        "submit_to_champion_p99_ms": round(float(p99), 2),
        "backpressure_waits": stats["backpressure_waits"],
        "step_compiles": pool_stats["step_compiles"],
        "concurrent_match_sequential": bool(match),
    }


def bench_telemetry(dev: str, n_clients: int, n_slots: int, pop: int,
                    budget: int, gens_per_step: int, max_queue: int,
                    rounds: int = 3) -> dict:
    """Observability overhead: tracing OFF vs ON on the front-end path.

    Interleaved best-of-`rounds` waves (OFF, ON, OFF, ON, ...) so clock
    and cache drift cannot systematically favour one configuration.  The
    OFF waves run the default serving configuration -- instrumented
    modules, tracing disabled -- and produce `jobs_per_sec_off`, the
    number `check_bench` hard-gates at 2% against the committed baseline:
    a single mispredicted branch per event site is the entire budget.
    The ON waves record into the in-memory span ring (no sink -- disk
    flushing is an exporter cost, not an instrumentation cost) and must
    reconcile exactly: one `job.submit` and exactly one terminal event
    per job, every round (`trace_events_complete`).
    """
    import asyncio

    from repro.serve.frontend import PlacementFrontend
    from repro.serve import tracing

    specs = make_job_specs(n_clients, pop, budget)

    def wave() -> float:
        async def run():
            sched = PlacementScheduler(n_slots=n_slots,
                                       gens_per_step=gens_per_step)

            async def client(req):
                handle = await fe.submit(req)
                await handle.wait()

            async with PlacementFrontend(sched, max_queue=max_queue) as fe:
                # warmup inside the wave: the pool's programs land in the
                # in-memory jit cache before the timed gather
                warm = await fe.submit(JobRequest(
                    device=dev, cfg=specs[0]["cfg"], seed=10_000,
                    budget=gens_per_step))
                await warm.wait()
                reqs = [JobRequest(device=dev, cfg=s["cfg"], seed=s["seed"],
                                   budget=s["budget"]) for s in specs]
                t0 = time.perf_counter()
                await asyncio.gather(*[client(r) for r in reqs])
                return time.perf_counter() - t0
        return asyncio.run(run())

    was_enabled = tracing.enabled()
    best = {"off": float("inf"), "on": float("inf")}
    events_complete = True
    try:
        for _ in range(rounds):
            tracing.disable(close_sinks=False)
            best["off"] = min(best["off"], wave())
            tracing.enable()
            tracing.tracer().clear()
            best["on"] = min(best["on"], wave())
            evs = tracing.tracer().events()
            submits = sum(ev.name == "job.submit" for ev in evs)
            terminals = sum(ev.name in tracing.TERMINAL_EVENTS
                            for ev in evs)
            # + 1: the warmup job is traced too and must terminate
            events_complete = (events_complete
                               and submits == n_clients + 1
                               and terminals == submits)
    finally:
        tracing.tracer().clear()
        if not was_enabled:
            tracing.disable(close_sinks=False)
    return {
        "n_clients": n_clients, "n_slots": n_slots,
        "max_queue": max_queue, "pop_size": pop, "budget_gens": budget,
        "gens_per_step": gens_per_step, "rounds": rounds,
        "jobs_per_sec_off": round(n_clients / best["off"], 3),
        "jobs_per_sec_on": round(n_clients / best["on"], 3),
        "enabled_overhead_pct": round(
            (best["on"] / max(best["off"], 1e-9) - 1.0) * 100, 2),
        "trace_events_complete": bool(events_complete),
    }


def main(out: str = "BENCH_placement.json", mode: str = "quick") -> dict:
    """mode: smoke (CI PR gate) < quick (default) < full (paper-scale)."""
    compile_cache.enable()
    smoke, full = mode == "smoke", mode == "full"
    dev = "xcvu11p" if full else "xcvu_test"
    prob = common.problem(dev)
    service = bench_service(
        prob,
        n_jobs=8 if smoke else (16 if not full else 64),
        n_slots=8, pop=16 if not full else 64,
        budget=8 if smoke else (16 if not full else 96),
        gens_per_step=8)
    pf = bench_portfolio(prob, n_cfgs=4 if not full else 8,
                         pop=16 if not full else 64,
                         n_gens=8 if smoke else (16 if not full else 100))
    # base_gens does NOT shrink in smoke mode: the migrated champion must
    # be converged for warm_beats_cold to be a meaningful (and stable)
    # assertion -- an under-trained seed migrates worse than random init.
    tr = bench_transfer(
        base_dev="xcvu3p" if full else "xcvu_test",
        dst_dev="xcvu5p" if full else "xcvu_test2",
        base_pop=32, base_gens=120 if full else 100,
        pop=16, budget=80 if full else (40 if smoke else 60),
        gens_per_step=2)
    sched = bench_scheduler(
        devices=("xcvu3p", "xcvu5p") if full else ("xcvu_test",
                                                   "xcvu_test2"),
        pops=(8, 16), jobs_per_pool=2 if smoke else 4,
        budget=8 if smoke else 16, n_slots=2, gens_per_step=4)
    # cache budgets mirror `transfer` (same sibling-pair race, now driven
    # by the store): the cold leg must genuinely converge toward the
    # migrated champion for the 1/4-gens sibling assertion to be stable
    cache = bench_cache(
        base_dev="xcvu3p" if full else "xcvu_test",
        sib_dev="xcvu5p" if full else "xcvu_test2",
        pop=16, budget=80 if full else (40 if smoke else 60),
        gens_per_step=2)
    pol = bench_policy(dev, budget=8 if smoke else 16, gens_per_step=4)
    autoscale = bench_autoscale(
        dev, n_jobs=6 if not full else 12, pop=16 if not full else 64,
        budget=8 if smoke else 16, gens_per_step=4)
    # the islands budget does NOT shrink in smoke mode (same reasoning as
    # `transfer`): the single-population contestant must genuinely reach
    # the probe target inside its budget for gens-to-target to mean
    # anything -- 48 gens is the verified-convergent smoke/quick config
    isl = bench_islands(
        prob, pop=16 if not full else 32,
        n_islands=4 if not full else 8, migrate_every=2,
        budget=48 if not full else 96, gens_per_step=2)
    kern = bench_kernels(prob, pop=64 if not full else 256,
                         reps=40 if smoke else 60)
    # 32 concurrent clients in EVERY mode (the serving-contract load the
    # ROADMAP names); only budgets shrink in smoke
    fe = bench_frontend(
        dev, n_clients=32, n_slots=8, pop=16,
        budget=8 if smoke else (16 if not full else 64),
        gens_per_step=4, max_queue=16)
    # telemetry shape stays fixed across smoke/quick (only full widens the
    # budget): the 2% disabled-overhead gate only fires at an identical
    # workload shape, so a stable shape keeps the gate armed in CI
    te = bench_telemetry(
        dev, n_clients=16, n_slots=8, pop=16,
        budget=8 if not full else 16, gens_per_step=4, max_queue=16)
    report = {
        "bench": "placement_service",
        "created_unix": int(time.time()),
        "mode": mode,
        "device": dev,
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "service": service,
        "portfolio": pf,
        "transfer": tr,
        "scheduler": sched,
        "cache": cache,
        "policy": pol,
        "autoscale": autoscale,
        "islands": isl,
        "kernels": kern,
        "frontend": fe,
        "telemetry": te,
    }
    with open(out, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps(report, indent=2))
    print(f"\nwrote {out}; add the compile section with: python -m "
          f"benchmarks.compile_probe --pair --merge-into {out}")
    return report


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="smallest budgets (CI PR gate)")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--out", default="BENCH_placement.json")
    args = ap.parse_args()
    if args.smoke and args.full:
        ap.error("--smoke and --full are mutually exclusive")
    main(out=args.out,
         mode="smoke" if args.smoke else ("full" if args.full else "quick"))
