"""Bring-up smoke run of the placement service on a TPU.

    python3 chip_smoke.py                # one chip: the served path
    python3 chip_smoke.py --four-chips   # islands sharded over 4 chips

One chip (the default): builds the paper's VU11P rectangle problem (80 conv
units, 2,240 hard blocks, 1,999 nets, generated from its seed) and serves
8 NSGA-II jobs (population 64) plus one job on the fused evaluation kernel
through `PlacementFrontend` -> `PlacementScheduler` -> `PlacementService`,
the path `launch/serve.py --placement --frontend` takes.  It then checks:

  * every job is harvested and none failed;
  * every pool compiled its batched step exactly once;
  * every champion is a legal placement (`objectives.validate_placement`,
    the numpy re-check);
  * every champion's (wl^2, bbox) matches a float64 numpy evaluation of
    paper Eqs. 1-2 from its decoded coordinates, to 1e-5 relative;
  * every pool's compiled step holds a Pallas kernel (`tpu_custom_call`).

`--four-chips`: runs only the path a four-chip host gives `core.islands.run`
users -- NSGA-II on VU11P with 8 islands sharded over the 4 chips (ring
migration crosses chips with a collective-permute) -- and the same run
unsharded on one chip.  It prints where each shard lives and checks that
the two runs agree in history and champions, that every chip holds a
shard, and that the compiled program holds a collective-permute.

The persistent compilation cache is on (`runtime.compile_cache`): at
`$JAX_COMPILATION_CACHE_DIR` when set, else `<checkout>/.jax_cache`.  Every
check raises; nothing is caught.  The last line of standard output is one
JSON object, printed only when every check passed:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Where JAX finds no TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DEVICE = "xcvu11p"          # paper VU11P rectangle: 80 units, 2,240 blocks
POP = 64                    # NSGA2Config default population
N_JOBS, SLOTS = 8, 8
BUDGET, GENS_PER_STEP = 32, 4
ISLANDS, MIGRATE_EVERY, ISLAND_GENS = 8, 4, 16
RTOL = 1e-5


def check(ok: bool, what) -> None:
    """Raise when a check fails (unlike `assert`, kept under `-O`)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def require_tpu(count: int):
    """The JAX devices, or exit non-zero when they are not `count` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX sees "
                 f"{len(devs)} {devs[0].platform} device(s))")
    if len(devs) != count:
        sys.exit(f"chip_smoke: needs {count} TPU chip(s), found {len(devs)}")
    return devs


def host_objectives(problem, genotype):
    """(wl^2, max bbox) of paper Eqs. 1-2 in float64 numpy, from the
    genotype's decoded block coordinates."""
    import numpy as np

    from repro.core import genotype as G
    from repro.fpga.netlist import BLOCKS_PER_UNIT

    bx, by = (np.asarray(a, np.float64) for a in G.decode(problem, genotype))
    s, d = np.asarray(problem.net_src), np.asarray(problem.net_dst)
    w = np.asarray(problem.net_w, np.float64)
    wl2 = np.sum(((np.abs(bx[s] - bx[d]) + np.abs(by[s] - by[d])) * w) ** 2)
    ux = bx.reshape(problem.n_units, BLOCKS_PER_UNIT)
    uy = by.reshape(problem.n_units, BLOCKS_PER_UNIT)
    bbox = np.max(ux.max(1) - ux.min(1) + uy.max(1) - uy.min(1))
    return np.array([wl2, bbox])


def serve(device: str = DEVICE, n_jobs: int = N_JOBS, slots: int = SLOTS,
          pop: int = POP, budget: int = BUDGET,
          gens_per_step: int = GENS_PER_STEP):
    """Serve `n_jobs` unfused NSGA-II jobs and one fused job through the
    front-end; check harvest, compiles, legality and the host objectives.
    Returns (scheduler, front-end stats, results, wall seconds)."""
    import numpy as np

    from repro.core import nsga2
    from repro.core import objectives as O
    from repro.serve.api import JobRequest
    from repro.serve.frontend import PlacementFrontend
    from repro.serve.placement_service import make_job_specs
    from repro.serve.scheduler import PlacementScheduler

    sch = PlacementScheduler(n_slots=slots, gens_per_step=gens_per_step)
    reqs = [JobRequest(device=device, cfg=s["cfg"], seed=s["seed"],
                       budget=s["budget"])
            for s in make_job_specs(n_jobs, pop, budget)]
    reqs.append(JobRequest(device=device,
                           cfg=nsga2.NSGA2Config(pop_size=pop, fused=True),
                           seed=n_jobs, budget=budget))

    async def run():
        async with PlacementFrontend(sch, max_queue=len(reqs)) as fe:
            handles = [await fe.submit(r) for r in reqs]
            results = [await h.wait() for h in handles]
            return results, fe.stats()

    t0 = time.perf_counter()
    results, stats = asyncio.run(run())
    wall = time.perf_counter() - t0

    fleet = stats["fleet"]
    check(stats["completed"] == len(reqs) and stats["failed"] == 0
          and fleet["jobs_failed"] == 0 and fleet["jobs_done"] == len(reqs),
          f"jobs harvested: {stats}")
    compiles = {k: p["step_compiles"] for k, p in fleet["pools"].items()}
    check(len(compiles) == 2 and all(c == 1 for c in compiles.values()),
          f"one step compile per pool: {compiles}")
    problem = sch.problem(device)
    for r in results:
        check(r.done and r.gens >= budget, f"job {r.jid}: {r.gens} gens")
        bad = [k for k, v in
               O.validate_placement(problem, r.genotype).items() if not v]
        check(not bad, f"job {r.jid} illegal placement: {bad}")
        want = host_objectives(problem, r.genotype)
        np.testing.assert_allclose(np.asarray(r.best_objs, np.float64),
                                   want, rtol=RTOL)
    return sch, stats, results, wall


def check_step_kernels(sch) -> None:
    """Every pool's compiled batched step holds a Pallas TPU kernel."""
    for label, svc in sch.pools().items():
        text = svc.lowered_step().compile().as_text()
        check("tpu_custom_call" in text, f"a Pallas kernel in {label}")


def one_chip() -> dict:
    import jax

    from repro.runtime import compile_cache

    dev = require_tpu(1)[0]
    cache = compile_cache.enable()
    meter = compile_cache.meter()
    print(f"jax {jax.__version__}; device {dev.device_kind}; "
          f"compile cache {cache}", flush=True)
    sch, stats, results, wall = serve()
    gens = sum(r.gens for r in results)
    print(f"served {len(results)} jobs ({gens} generations) in {wall:.2f}s "
          f"wall, compiles included: {gens / wall:.2f} gens/s; "
          f"step compiles per pool: "
          f"{[p['step_compiles'] for p in stats['fleet']['pools'].values()]}",
          flush=True)
    for r in results:
        print(f"  job{r.jid}: wl2={r.best_objs[0]:.6e} "
              f"bbox={r.best_objs[1]:.6e} gens={r.gens}")
    m = meter.stats()
    print(f"compile meter: {m['compiles']} compiles, {m['recompiles']} "
          f"real, {m['cache_hits']} persistent-cache hits, "
          f"{m['compile_secs']}s compiling", flush=True)
    check_step_kernels(sch)
    return {"platform": dev.platform, "kind": dev.device_kind, "count": 1}


def four_chips(device: str = DEVICE, pop: int = POP,
               n_islands: int = ISLANDS, migrate_every: int = MIGRATE_EVERY,
               n_gens: int = ISLAND_GENS):
    """Islands sharded over every device vs the same run unsharded on one
    device.  Returns the compiled sharded program's text."""
    import jax
    import numpy as np

    from repro.core import evolve, nsga2
    from repro.core import islands as I
    from repro.core import objectives as O
    from repro.fpga import device as device_mod
    from repro.fpga import netlist
    from repro.runtime import jaxcompat

    problem = netlist.make_problem(device_mod.get_device(device))
    cfg = nsga2.NSGA2Config(pop_size=pop)
    icfg = I.IslandConfig(n_islands=n_islands, migrate_every=migrate_every)
    key = jax.random.PRNGKey(0)
    ndev = jax.device_count()

    t0 = time.perf_counter()
    st_s, h_s = I.run(problem, "nsga2", cfg, key, n_gens, islands=icfg)
    h_s = jax.block_until_ready(h_s)
    print(f"sharded run: {time.perf_counter() - t0:.2f}s, compile included",
          flush=True)
    shard_devs = set()
    for s in h_s.addressable_shards:
        shard_devs.add(s.device)
        print(f"  history shard {s.index} on {s.device}")
    check(len(shard_devs) == ndev, f"shards on {shard_devs} of {ndev}")

    t0 = time.perf_counter()
    st_v, h_v = I.run(problem, "nsga2", cfg, key, n_gens, islands=icfg,
                      shard=False)
    h_v = jax.block_until_ready(h_v)
    print(f"unsharded run on {sorted(h_v.devices(), key=str)}: "
          f"{time.perf_counter() - t0:.2f}s, compile included", flush=True)

    np.testing.assert_allclose(np.asarray(h_s), np.asarray(h_v), rtol=1e-6)
    best_s = np.asarray(jax.vmap(evolve.state_best_objs)(st_s))
    best_v = np.asarray(jax.vmap(evolve.state_best_objs)(st_v))
    np.testing.assert_allclose(best_s, best_v, rtol=1e-6)
    champ_s = int(np.argmin(np.asarray(O.combined_metric(best_s))))
    champ_v = int(np.argmin(np.asarray(O.combined_metric(best_v))))
    check(champ_s == champ_v, f"champion island {champ_s} vs {champ_v}")
    print(f"champion island {champ_s}: wl2={best_s[champ_s, 0]:.6e} "
          f"bbox={best_s[champ_s, 1]:.6e} (sharded == unsharded)")

    mesh = jaxcompat.make_mesh((ndev,), (I.AXIS,))
    text = I._run.lower(problem, "nsga2", cfg, icfg, key, n_gens,
                        mesh).compile().as_text()
    check("collective-permute" in text, "a collective-permute in program")
    return text


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="islands sharded over 4 chips vs one chip, only")
    args = ap.parse_args(argv)
    if args.four_chips:
        import jax

        from repro.runtime import compile_cache

        devs = require_tpu(4)
        compile_cache.enable()
        print(f"jax {jax.__version__}; devices {devs}", flush=True)
        four_chips()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
    else:
        device = one_chip()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
