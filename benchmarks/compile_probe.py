"""Cold-start measurement in a fresh process, and the cold/warm pair.

    PYTHONPATH=src python -m benchmarks.compile_probe [--out P]
    PYTHONPATH=src python -m benchmarks.compile_probe --pair \
        [--merge-into BENCH_placement.json]

One probe answers "how long does a fresh process take to serve its first
generation, and how many real XLA compiles did that cost?".  It turns on
the persistent compilation cache where `runtime.compile_cache.cache_dir()`
says (`$JAX_COMPILATION_CACHE_DIR`, else `<checkout>/.jax_cache`); cold
vs warm is decided entirely by what that directory holds when the probe
starts.  Running the probe as its own process is the point: jax's
in-memory jit caches die with the process, so only the persistent cache can
make a second run fast.

The probe builds a smoke-shaped `PlacementService`, runs one job through
its first batched step, then exercises one `grow()` ladder rung -- the
full set of programs a restarted serving process replays -- and prints a
single JSON object:

  {"ttfg_ms": ..., "wall_ms": ..., "compiles": ..., "recompiles": ...,
   "cache_hits": ..., "cache_misses": ..., "compile_secs": ...,
   "events_seen": ..., "pop_size": ..., "n_slots": ..., ...}

`recompiles` (real XLA compiles: requests the persistent cache did not
answer) is the number the CI budget pins at 0 for a warm start.

`--pair` is the bench's `compile` section.  It clears the fixed
subdirectory `<cache_dir()>/compile_probe`, then runs two probes against
it, cold then warm, each in its own process, and prints the section
(`--merge-into` also writes it into a bench report under "compile").  The
pair's parent never imports jax: a process that holds JAX holds the chip,
and a child that needs the chip would then fail or hang.  So run the pair
as its own command, never from a process that has touched JAX.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

PAIR_SUBDIR = "compile_probe"


def probe(pop: int, n_slots: int, gens_per_step: int, budget: int,
          device: str, grow_to: int) -> dict:
    from repro.runtime import compile_cache
    cache_dir = compile_cache.enable()
    m = compile_cache.meter().install()

    import jax
    from repro.core import nsga2
    from repro.fpga import device as device_mod
    from repro.fpga import netlist
    from repro.serve.placement_service import PlacementService

    t0 = time.perf_counter()
    prob = netlist.make_problem(device_mod.get_device(device))
    svc = PlacementService(prob, nsga2.NSGA2Config(pop_size=pop),
                           n_slots=n_slots, gens_per_step=gens_per_step)
    svc.submit(seed=0, budget=budget)
    while svc.active.any():
        svc.step()
    ttfg = svc.stats()["time_to_first_gen_ms"]
    if grow_to > n_slots:
        # one ladder rung: a restarted autoscaling process replays these
        # programs too, so the warm budget must cover them
        svc.grow(grow_to)
        svc.submit(seed=1, budget=budget)
        while svc.active.any():
            svc.step()
    wall_ms = (time.perf_counter() - t0) * 1e3
    return {
        "ttfg_ms": round(float(ttfg), 1),
        "wall_ms": round(wall_ms, 1),
        "pop_size": pop, "n_slots": n_slots,
        "gens_per_step": gens_per_step, "budget_gens": budget,
        "device": device, "grow_to": grow_to,
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "cache_salt": compile_cache.cache_salt(),
        "cache_dir": cache_dir,
        **{k: v for k, v in m.stats().items()
           if k != "persistent_cache_dir"},
    }


def pair(pop: int = 16, n_slots: int = 8, gens_per_step: int = 8,
         budget: int = 8, device: str = "xcvu_test",
         grow_to: int = 16) -> dict:
    """Cold vs cache-restored cold start: two probe processes sharing one
    freshly cleared cache directory.  Returns the bench `compile` section.

    Leg 1 (cold) fills the directory, leg 2 (warm) must answer every
    compile request from it.  Shapes do not scale with the bench mode: the
    compile bill depends on the program set, not the budgets."""
    if "jax" in sys.modules:
        raise RuntimeError("the probe pair's parent must not import jax: "
                           "its children need the device")
    from repro.runtime.compile_cache import cache_dir
    root = os.path.join(cache_dir(), PAIR_SUBDIR)
    shutil.rmtree(root, ignore_errors=True)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=root)

    def leg() -> dict:
        cmd = [sys.executable, "-m", "benchmarks.compile_probe",
               "--pop", str(pop), "--slots", str(n_slots),
               "--gps", str(gens_per_step), "--budget", str(budget),
               "--device", device, "--grow-to", str(grow_to)]
        out = subprocess.run(cmd, check=True, capture_output=True,
                             text=True, env=env)
        # last stdout line is the JSON object (jax may log above it)
        return json.loads(out.stdout.strip().splitlines()[-1])

    cold = leg()
    warm = leg()
    speedup = cold["ttfg_ms"] / max(warm["ttfg_ms"], 1e-9)
    return {
        "pop_size": pop, "n_slots": n_slots, "gens_per_step": gens_per_step,
        "budget_gens": budget, "grow_to": grow_to, "device": device,
        "backend": cold["backend"],
        "cache_salt": cold["cache_salt"],
        "ttfg_cold_ms": cold["ttfg_ms"],
        "ttfg_warm_ms": warm["ttfg_ms"],
        "ttfg_speedup": round(speedup, 2),
        "compiles_cold": cold["compiles"],
        "recompiles_cold": cold["recompiles"],
        "compile_secs_cold": cold["compile_secs"],
        "compiles_warm": warm["compiles"],
        "recompiles_warm": warm["recompiles"],
        "cache_hits_warm": warm["cache_hits"],
        "compile_secs_warm": warm["compile_secs"],
        "recompiles_warm_zero": bool(warm["recompiles"] == 0),
        "warm_ttfg_5x": bool(speedup >= 5.0),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pair", action="store_true",
                    help="run a cold and a warm probe in child processes "
                         "and print the bench `compile` section")
    ap.add_argument("--merge-into", default=None, metavar="REPORT",
                    help="with --pair, also write the section into this "
                         "bench JSON report under \"compile\"")
    ap.add_argument("--pop", type=int, default=16)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--gps", type=int, default=8)
    ap.add_argument("--budget", type=int, default=8)
    ap.add_argument("--device", default="xcvu_test")
    ap.add_argument("--grow-to", type=int, default=16,
                    help="grow the pool to this slot count after the first "
                         "job (0 disables the ladder rung)")
    ap.add_argument("--out", default=None,
                    help="also write the JSON object to this path")
    args = ap.parse_args()
    shape = (args.pop, args.slots, args.gps, args.budget, args.device,
             args.grow_to)
    if args.pair:
        out = pair(*shape)
        if args.merge_into:
            with open(args.merge_into) as f:
                report = json.load(f)
            report["compile"] = out
            with open(args.merge_into, "w") as f:
                json.dump(report, f, indent=2)
                f.write("\n")
    else:
        out = probe(*shape)
    line = json.dumps(out)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
