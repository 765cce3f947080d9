"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (`BENCHMARK.json` "workloads") names a configuration
(`bench/configs/<config>.json`) and a traffic mix (`bench/traffic/<mix>.json`);
the configuration names its search algorithm, whose module
`bench/algorithms/<algorithm>.py` builds each job's config and checks its
final state.  The run

  1. refuses to start unless JAX sees TPUs, as many as the cell asks for;
  2. sets up: keeps JAX's persistent compilation cache in `<checkout>/.jax_cache`,
     builds a `PlacementScheduler` behind a `PlacementFrontend`, and warms up
     every program of the window by serving one wave of short jobs that fills
     every slot of every pool (init, step and harvest at each slot);
  3. measures for `--seconds`: clients submit through
     `PlacementFrontend.submit`, the front-end's stepping thread drives
     `PlacementScheduler` -> `PlacementService.step`; with `--trace 1` the
     program's own span tracing is on for the window and the JAX profiler
     for its first `TRACE_S` seconds;
  4. drains every job that was due in the window, reads the chip's peak
     memory, frees the program's state, and checks the answers of a sample
     of those jobs (drawn from the seed) against `bench/reference.py`,
     through the algorithm's module;
  5. reads each metric of the cell with its reader `bench/metrics/<name>.py`
     (`--trace 0`: the end-to-end metrics, `--trace 1`: the per-layer ones).

Diagnostics go to earlier lines.  The compared numbers and their limits are
the last lines of standard error, and the last line of standard output is
one JSON object: correct, attempted, failed, metrics, device (and breakdown
when traced), then the compared numbers under "checks".
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import generator, reference  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / "bench_out" / "trace"
MAX_QUEUE = 1 << 20          # open loops never meet front-end backpressure
RAMP_S = 30.0                # closed loop: at most this long to fill slots
DRAIN_S = 90.0               # at most this long for the window's jobs
TRACE_S = 10.0               # profiled slice: one harvest wave or more
CHECK_SHARE = 0.25           # share of jobs whose final state is kept
CHECK_JOBS = 24              # jobs compared with the reference per run


@dataclasses.dataclass
class JobRecord:
    """One job as the client sees it (times on the host's perf clock)."""

    job: Dict
    request: Any
    due: Optional[float] = None        # open loop: when it fell due
    submitted: Optional[float] = None
    done: Optional[float] = None
    handle: Any = None
    result: Any = None
    error: Optional[str] = None
    window: bool = False               # due (or submitted) in the window
    check: bool = False                # final state kept for the check

    @property
    def ok(self) -> bool:
        return self.result is not None and self.error is None

    @property
    def latency(self) -> float:
        """Seconds from due (or submitted) to champion; inf if none came."""
        start = self.due if self.due is not None else self.submitted
        return self.done - start if self.ok else math.inf


@dataclasses.dataclass
class Run:
    """What one run measured: the input of every metric reader."""

    cell: Dict
    config: Dict
    mix: Dict
    seconds: float = 0.0               # measured window, host clock
    setup_s: float = 0.0
    jobs: List[JobRecord] = dataclasses.field(default_factory=list)
    pools: List[Dict] = dataclasses.field(default_factory=list)
    compile: Dict = dataclasses.field(default_factory=dict)
    events: List[Any] = dataclasses.field(default_factory=list)
    trace: Optional[Dict] = None       # bench/trace_reduce.py summary
    peaks: Optional[Dict] = None       # bench/peaks.json row of this chip
    notes: Dict = dataclasses.field(default_factory=dict)

    @property
    def window_jobs(self) -> List[JobRecord]:
        return [r for r in self.jobs if r.window]


# -------------------------------------------------------------- the cell

def load_cell(name: str, root: Path = ROOT):
    """(benchmark, cell, configuration, mix, algorithm module) for a
    workload name."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: unknown workload {name!r}; "
                         f"have {sorted(cells)}")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / entry["file"]).read_text())
    mix = generator.load(root / "bench" / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, config, mix, algorithm(config["algorithm"], root)


def cell_metrics(bench: Dict, cell: Dict, trace: bool) -> List[Dict]:
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])]


def _module(path: Path, kind: str):
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: Path = ROOT) -> Callable[[Run], Optional[float]]:
    """The `read(run)` function of `bench/metrics/<name>.py`."""
    return _module(root / "bench" / "metrics" / f"{name}.py", "metric").read


def algorithm(name: str, root: Path = ROOT):
    """The module `bench/algorithms/<name>.py` of a search algorithm.  It
    holds what the harness needs of that algorithm:

      ALGO                      the service's name for it (`JobRequest.algo`)
      job_config(algorithm, hyper)
                                the program's config object for one job, from
                                the configuration's `search.algorithm` and the
                                job's drawn hyperparameters
      evals_per_gen(cfg, prob)  placements one job evaluates per generation
                                (`prob`: the `bench/reference.py` Problem)
      host_copy(state, result)  what the check needs of one job, on the host,
                                from its harvested final state and its result
      candidates(copy)          the (genotype, reported objectives) pairs the
                                check compares with the reference
      check(prob, copy, control)
                                one job's numbers: at least objective_gap,
                                illegal_placements, members_checked and
                                members_ambiguous, and those of LIMITS
      merge(results)            those numbers over the sampled jobs
      LIMITS                    the limits of the algorithm's own numbers

    A configuration whose algorithm has no module exits here, before JAX
    is imported or a chip is asked for."""
    path = root / "bench" / "algorithms" / f"{name}.py"
    if not path.is_file():
        raise SystemExit(f"bench: algorithm {name!r} has no module "
                         f"{path.relative_to(root)}; nothing measured")
    return _module(path, "algorithm")


def limits(algo) -> Dict:
    """The limit of every number `correct` compares, in the order the
    result line gives them: the reference's, then the algorithm's own."""
    return {"objective_gap": reference.OBJECTIVE_GAP_LIMIT,
            "illegal_placements": 0, **algo.LIMITS, "unchecked_jobs": 0}


def require_chips(count: int):
    """The JAX devices; exits non-zero (no result) unless `count` TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX sees {len(devs)} "
                     f"{devs[0].platform} device(s)); nothing measured")
    if len(devs) < count:
        raise SystemExit(f"bench: the cell needs {count} TPU chip(s), "
                     f"JAX sees {len(devs)}; nothing measured")
    return devs


# ------------------------------------------------------------ final states

class Capture:
    """Keeps the final state of the jobs chosen for the check.

    The pool harvests a job by handing its final state to
    `core.portfolio.best_genotype`; the wrapper keeps a reference to that
    state (already on the device: no copy, no extra work) for the jobs
    whose config object it watches, so the algorithm's check can re-derive
    the champion from the whole state."""

    def __init__(self):
        self.watch: Dict[int, JobRecord] = {}
        self.states: Dict[int, Any] = {}
        self._undo = None

    def install(self) -> "Capture":
        from repro.core import portfolio
        harvest = portfolio.best_genotype

        def best_genotype(problem, algo, state, cfg=None):
            out = harvest(problem, algo, state, cfg)
            rec = self.watch.get(id(cfg))
            if rec is not None and rec.request.cfg is cfg:
                self.states[rec.job["index"]] = state
            return out

        portfolio.best_genotype = best_genotype
        self._undo = lambda: setattr(portfolio, "best_genotype", harvest)
        return self

    def close(self) -> None:
        if self._undo is not None:
            self._undo()
            self._undo = None
        self.watch.clear()
        self.states.clear()


class StepClock:
    """Where the host's time went when a run reads far off: every pool
    step's host seconds and the stepping thread's CPU seconds in it, and
    how late the event loop (clients and arrivals) woke from a short sleep,
    with the process's CPU seconds over that sleep.  A slow device shows as
    long steps with the loop on time; a process that made no progress (the
    host did not run it, or a call blocked holding the interpreter lock) as
    a long step and a late loop with little CPU in either; Python code
    holding the interpreter as a late loop with the CPU busy."""

    TICK_S = 0.02

    def __init__(self):
        self.steps: List[tuple] = []       # (start, end, thread CPU s)
        self.lags: List[tuple] = []        # (start, lateness s, process CPU s)
        self.watch: Optional[asyncio.Task] = None
        self._undo = None

    def install(self) -> "StepClock":
        from repro.serve.placement_service import PlacementService
        step, steps = PlacementService.step, self.steps

        def timed(svc):
            if not svc.active.any():
                return step(svc)
            t0, c0 = time.perf_counter(), time.thread_time()
            out = step(svc)
            steps.append((t0, time.perf_counter(), time.thread_time() - c0))
            return out

        PlacementService.step = timed
        self._undo = lambda: setattr(PlacementService, "step", step)
        return self

    def close(self) -> None:
        if self._undo is not None:
            self._undo()
            self._undo = None

    async def watch_loop(self) -> None:
        while True:
            t0, c0 = time.perf_counter(), time.process_time()
            await asyncio.sleep(self.TICK_S)
            self.lags.append((t0, time.perf_counter() - t0 - self.TICK_S,
                              time.process_time() - c0))

    def notes(self, t_open: float, t_close: float) -> Dict:
        steps = [s for s in self.steps if t_open <= s[0] < t_close]
        out: Dict[str, Any] = {}
        if steps:
            ms = sorted(1e3 * (e - s) for s, e, _ in steps)
            worst = max(steps, key=lambda s: s[1] - s[0])
            gaps = [b[0] - a[1] for a, b in zip(steps, steps[1:])]
            out.update(step_ms_p50=ms[len(ms) // 2], step_ms_max=ms[-1],
                       step_ms_max_at_s=worst[0] - t_open,
                       step_ms_max_cpu_ms=1e3 * worst[2],
                       steps_over_1p5x=sum(m > 1.5 * ms[len(ms) // 2]
                                           for m in ms),
                       step_gap_ms_max=1e3 * max(gaps, default=0.0))
        if self.lags:
            worst = max(self.lags, key=lambda t: t[1])
            out.update(loop_lag_ms_max=1e3 * worst[1],
                       loop_lag_max_at_s=worst[0] - t_open,
                       loop_lag_max_cpu_ms=1e3 * worst[2])
        return out


def _checked(seed: int, index: int, share: float) -> bool:
    """Every 1/share-th job, from an offset drawn from the seed."""
    stride = max(1, round(1 / share))
    return (index + seed) % stride == 0


# ------------------------------------------------------------------ driving

class Harness:
    """Set-up, window and drain of one run, on one asyncio loop."""

    def __init__(self, run: Run, algo, prob: reference.Problem, seed: int,
                 seconds: float, trace: bool, check_share: float = CHECK_SHARE):
        from repro.serve.scheduler import PlacementScheduler
        self.run, self.algo, self.seed, self.seconds = run, algo, seed, seconds
        self.trace, self.check_share = trace, check_share
        search = run.config["search"]
        self.evals_per_gen = algo.evals_per_gen(
            algo.job_config(search["algorithm"], {}), prob)
        self.device = run.config["device"]["name"]
        self.sch = PlacementScheduler(n_slots=search["n_slots"],
                                      gens_per_step=search["gens_per_step"])
        self.capture = Capture().install()
        self.tasks: List[asyncio.Task] = []
        self.t_open = self.t_close = None
        self.trace_stop = None
        self.clock = StepClock().install()

    def record(self, job: Dict, due: Optional[float] = None,
               check: bool = False) -> JobRecord:
        from repro.serve.api import JobRequest
        cfg = self.algo.job_config(self.run.config["search"]["algorithm"],
                                   job["hyper"])
        rec = JobRecord(job=job, due=due, check=check, request=JobRequest(
            device=self.device, cfg=cfg, algo=self.algo.ALGO,
            seed=job["seed"], budget=job["budget"]))
        if check:
            self.capture.watch[id(cfg)] = rec
        self.run.jobs.append(rec)
        return rec

    async def serve(self, fe, rec: JobRecord) -> None:
        rec.submitted = time.perf_counter()
        rec.handle = await fe.submit(rec.request)
        try:
            rec.result = await rec.handle.wait()
        except Exception as e:  # noqa: BLE001 -- a failed job is data
            rec.error = f"{type(e).__name__}: {e}"
        rec.done = time.perf_counter()

    def pool_counts(self) -> List[Dict]:
        return [{"label": label, "steps": svc.total_steps,
                 "useful_gens": svc.useful_gens,
                 "evals_per_gen": self.evals_per_gen,
                 "active": int(svc.active.sum()), "slots": svc.n_slots}
                for label, svc in self.sch.pools().items()]

    def in_system(self) -> int:
        return sum(r.submitted is not None and r.done is None
                   for r in self.run.jobs)

    async def main(self) -> None:
        from repro.runtime import compile_cache
        from repro.serve.frontend import PlacementFrontend
        meter = compile_cache.meter()
        fe = PlacementFrontend(self.sch, max_queue=MAX_QUEUE)
        fe.start()
        try:
            self.run.notes["setup_to_warm_up_s"] = time.perf_counter() - T_START
            await self.warm_up(fe)
            self.run.notes["warm_up_s"] = (time.perf_counter() - T_START
                                           - self.run.notes["setup_to_warm_up_s"])
            if self.run.mix["loop"] == "closed":
                await self.closed_loop(fe, meter)
            else:
                await self.open_loop(fe, meter)
        finally:
            await fe.aclose()

    async def warm_up(self, fe) -> None:
        """One wave of short jobs that fills every slot: compiles (or loads
        from the cache) the fill, init, step and harvest programs, and the
        per-slot state splices, before the window opens."""
        search = self.run.config["search"]
        warm = [self.record({"index": -1 - k, "due_s": None, "seed": k,
                             "budget": search["gens_per_step"], "hyper": {}})
                for k in range(search["n_slots"])]
        await asyncio.gather(*(self.serve(fe, r) for r in warm))
        self.run.jobs = [r for r in self.run.jobs if r not in warm]
        bad = [r.error for r in warm if not r.ok]
        if bad:
            raise RuntimeError(f"bench: warm-up jobs failed: {bad}")

    def open_window(self, meter) -> None:
        if self.trace:
            import jax
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # keep host timings honest
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        self.t_open = time.perf_counter()
        self.run.setup_s = self.t_open - T_START
        if self.trace:
            self.trace_stop = asyncio.get_running_loop().create_task(
                self.stop_trace_after(min(TRACE_S, self.seconds)))
        self.clock.watch = asyncio.get_running_loop().create_task(
            self.clock.watch_loop())
        self.run.compile["setup_secs"] = meter.compile_secs
        self.run.compile["setup_compiles"] = meter.compiles
        self.pools_open = self.pool_counts()
        self.run.notes["queue_at_open"] = self.queued()

    async def close_window(self, meter) -> None:
        self.t_close = time.perf_counter()
        self.run.seconds = self.t_close - self.t_open
        self.clock.watch.cancel()
        self.run.notes.update(self.clock.notes(self.t_open, self.t_close))
        self.run.compile["window_compiles"] = (
            meter.compiles - self.run.compile["setup_compiles"])
        pools_close = self.pool_counts()
        self.run.pools = [dict(c, steps=c["steps"] - o["steps"],
                               useful_gens=c["useful_gens"]
                               - o["useful_gens"])
                          for o, c in zip(self.pools_open, pools_close)]
        self.run.notes["queue_at_close"] = self.queued()
        if self.trace:
            await self.trace_stop

    async def stop_trace_after(self, seconds: float) -> None:
        """Stop the profiler `seconds` into the window, off the loop."""
        import jax
        await asyncio.sleep(seconds)
        await asyncio.get_running_loop().run_in_executor(
            None, jax.profiler.stop_trace)

    def queued(self) -> int:
        """Jobs in the system that hold no slot."""
        return self.in_system() - sum(p["active"] for p in self.pool_counts())

    async def closed_loop(self, fe, meter) -> None:
        stream = generator.closed(self.run.mix, self.seed)
        closing = False

        async def client():
            while not closing:
                job = next(stream)
                rec = self.record(job, check=_checked(
                    self.seed, job["index"], self.check_share))
                rec.window = (self.t_open is not None
                              and time.perf_counter() >= self.t_open)
                await self.serve(fe, rec)

        clients = [asyncio.create_task(client())
                   for _ in range(self.run.mix["clients"])]
        deadline = time.perf_counter() + RAMP_S
        while (not all(p["active"] == p["slots"] for p in self.pool_counts())
               and time.perf_counter() < deadline):
            await asyncio.sleep(0.002)
        self.open_window(meter)
        await asyncio.sleep(self.seconds)
        closing = True
        await self.close_window(meter)
        t0 = time.perf_counter()
        _, pending = await asyncio.wait(clients, timeout=DRAIN_S)
        self.run.notes["drain_s"] = time.perf_counter() - t0
        for r in self.run.jobs:
            if r.window and r.submitted >= self.t_close:
                r.window = False          # submitted after the close
        for r in self.run.jobs:
            if r.done is None and r.handle is not None:
                r.handle.cancel()
        await asyncio.gather(*pending, return_exceptions=True)

    async def open_loop(self, fe, meter) -> None:
        mix, seed, seconds = self.run.mix, self.seed, self.seconds
        preroll = mix.get("preroll_s", 0.0)
        t_zero = time.perf_counter() + preroll
        stop = asyncio.Event()

        def blocks():
            if preroll > 0:
                yield from generator.open_block(mix, seed, -1, preroll)
            b = 0
            while True:
                yield from generator.open_block(mix, seed, b, seconds)
                b += 1

        async def arrivals():
            for job in blocks():
                due = t_zero + job["due_s"]
                wait = due - time.perf_counter()
                if wait > 0:
                    await asyncio.sleep(wait)
                if stop.is_set():
                    return
                window = 0.0 <= job["due_s"] < seconds
                rec = self.record(job, due=due, check=window and _checked(
                    seed, job["index"], self.check_share))
                rec.window = window
                self.tasks.append(asyncio.create_task(self.serve(fe, rec)))

        feeder = asyncio.create_task(arrivals())
        await asyncio.sleep(max(0.0, t_zero - time.perf_counter()))
        self.open_window(meter)
        await asyncio.sleep(max(0.0, t_zero + seconds - time.perf_counter()))
        await self.close_window(meter)
        t0 = time.perf_counter()
        while not all(r.done is not None for r in self.run.window_jobs):
            if time.perf_counter() - t0 > DRAIN_S:
                break
            await asyncio.sleep(0.01)
        self.run.notes["drain_s"] = time.perf_counter() - t0
        stop.set()
        await feeder
        for r in self.run.jobs:
            if r.done is None and r.handle is not None:
                r.handle.cancel()
        await asyncio.gather(*self.tasks, return_exceptions=True)


# --------------------------------------------------------------- the check

def sample(run: Run, seed: int, n: int = CHECK_JOBS) -> List[JobRecord]:
    """Up to `n` window jobs with a kept final state, drawn from the seed,
    always with the largest budget among them."""
    import numpy as np
    pool = [r for r in run.window_jobs if r.check and r.ok]
    if len(pool) <= n:
        return pool
    longest = max(pool, key=lambda r: r.job["budget"])
    rest = [r for r in pool if r is not longest]
    rng = np.random.default_rng(
        np.random.SeedSequence([abs(seed), int(seed < 0), 11]))
    pick = rng.choice(len(rest), n - 1, replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def host_copies(algo, capture: Capture, recs: List[JobRecord]
                ) -> List[Optional[Dict]]:
    """The algorithm's host copy of each sampled job; None for a job whose
    final state was not captured."""
    out = []
    for r in recs:
        state = capture.states.get(r.job["index"])
        out.append(None if state is None else algo.host_copy(state, r.result))
    return out


def compare(algo, prob: reference.Problem, jobs: List[Optional[Dict]],
            control: bool = False) -> Dict:
    """The numbers that decide `correct`, over the sampled jobs."""
    results = [algo.check(prob, j, control=control)
               for j in jobs if j is not None]
    out = algo.merge(results)
    out["unchecked_jobs"] = sum(j is None for j in jobs) + (
        0 if results and out["members_checked"] else 1)
    out["jobs_checked"] = len(results)
    return out


# ------------------------------------------------------------------ a run

def run_cell(cell: Dict, config: Dict, mix: Dict, algo, seed: int,
             seconds: float, trace: bool, metrics: List[Dict],
             check_share: float = CHECK_SHARE,
             check_jobs: int = CHECK_JOBS) -> Dict:
    """Set up, measure, drain and check one run; returns the result line."""
    import jax

    from repro.serve import tracing
    run = Run(cell=cell, config=config, mix=mix)
    prob = reference.Problem(config["device"])
    if trace:
        tracing.tracer().clear()
        tracing.enable()
    harness = Harness(run, algo, prob, seed, seconds, trace, check_share)
    try:
        asyncio.run(harness.main())
        if trace:
            run.events = tracing.tracer().events()
        dev = jax.devices()[0]
        mem = dev.memory_stats() or {}
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": jax.device_count(),
                  "memory_peak_bytes": mem.get("peak_bytes_in_use")}
        copies = host_copies(algo, harness.capture,
                             sample(run, seed, check_jobs))
    finally:
        harness.capture.close()
        harness.clock.close()
        if trace:
            tracing.disable()
    del harness
    gc.collect()

    numbers = compare(algo, prob, copies)
    attempted = len(run.window_jobs)
    failed = sum(not r.ok for r in run.window_jobs)
    if trace:
        from bench import trace_reduce
        run.trace = trace_reduce.reduce(TRACE_DIR)
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        run.peaks = peaks_for(dev.device_kind)
    values = {}
    for m in metrics:
        v = reader(m["name"])(run)
        if v is not None:
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    checks = {k: {"value": numbers[k], "limit": lim}
              for k, lim in limits(algo).items()}
    line = {"correct": failed == 0 and all(
                c["value"] <= c["limit"] for c in checks.values()),
            "attempted": attempted, "failed": failed, "metrics": values,
            "device": device}
    if trace:
        line["breakdown"] = run.trace["breakdown"]
    line["checks"] = checks
    notes = dict(run.notes, window_s=run.seconds,
                 window_compiles=run.compile.get("window_compiles"),
                 setup_compile_s=run.compile.get("setup_secs"),
                 jobs_checked=numbers["jobs_checked"],
                 members_checked=numbers["members_checked"],
                 members_ambiguous=numbers["members_ambiguous"],
                 steps=sum(p["steps"] for p in run.pools))
    late = [r.submitted - r.due for r in run.window_jobs
            if r.due is not None and r.submitted is not None]
    if late:
        notes["late_ms_median"] = 1e3 * sorted(late)[len(late) // 2]
        notes["late_ms_max"] = 1e3 * max(late)
    return {"line": line, "notes": notes, "run": run, "copies": copies,
            "numbers": numbers}


def peaks_for(kind: str) -> Dict:
    table = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table["devices"][kind]


def emit(out: Dict) -> None:
    """Diagnostics, then the compared numbers (stderr), then the line."""
    for k, v in out["notes"].items():
        print(f"note {k} {v}", flush=True)
    for k, c in out["line"]["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out["line"]), flush=True)


def start_chip(cell: Dict) -> None:
    """Refuse without the cell's chips, then turn on the compile cache.

    The cache sits at the fixed `<checkout>/.jax_cache` whatever the
    environment says: a directory inside the checkout is never shared with
    another checkout measured on the same machine, and its fixed path keeps
    the cache's keys stable from run to run."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path.insert(0, str(ROOT / "src"))
    require_chips(cell["chips"])
    from repro.runtime import compile_cache
    compile_cache.enable()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench, cell, config, mix, algo = load_cell(args.workload)
    start_chip(cell)
    out = run_cell(cell, config, mix, algo, args.seed, args.seconds,
                   bool(args.trace), cell_metrics(bench, cell, args.trace))
    emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
