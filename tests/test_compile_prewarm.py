"""Compile-latency control: persistent cache, compile meter, AOT prewarm.

Covers the PR's acceptance contracts:
  * `runtime.compile_cache`: the meter counts real backend compiles and
    scopes them to the opening thread (`measure()`), `enable()` persists
    every compiled program to the cache directory, and a cleared
    in-memory jit cache re-loads from disk (cache hit, zero recompiles)
    -- the in-process version of the cross-process CI budget,
  * prewarm correctness: a scheduler pool adopted from the background
    prewarmer produces bitwise-identical job results to a cold-built
    pool, and a prewarm failure falls back to the synchronous build
    (latency, never jobs),
  * `grow()` on a prewarmed ladder size performs ZERO blocking compiles
    in the stepping loop (the same grow without prewarm must block on at
    least one),
  * champion-store traffic round-trip: `note_traffic` rows survive
    save/load and a FRESH store's `predicted_keys` drive
    `prewarm_predicted` end to end (restart -> prewarm -> adopt),
  * `PlacementScheduler._admit` resilience: an admission failure
    re-queues the job with an error note (transient failures recover,
    persistent ones surface as `failed` after bounded retries) and never
    wedges co-queued jobs or `run_all()`.
"""
import json
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import nsga2
from repro.fpga import device, netlist
from repro.runtime import compile_cache
from repro.serve.champion_store import ChampionStore
from repro.serve.placement_service import PlacementService
from repro.serve.prewarm import Prewarmer
from repro.serve.scheduler import PlacementScheduler

BASE = netlist.make_problem(device.get_device("xcvu_test"))
CFG = nsga2.NSGA2Config(pop_size=8)


def _drain(svc):
    done = []
    while svc.active.any():
        done.extend(svc.step())
    return done


# ---------------------------------------------------------- compile meter

def test_meter_counts_and_thread_scopes():
    m = compile_cache.meter().install()
    c = float(np.random.default_rng(0).standard_normal())  # unique consts

    with m.measure() as scope:
        jax.block_until_ready(jax.jit(lambda x: x * 2 + c)(jnp.ones(7)))
    assert scope.compiles >= 1
    assert scope.secs > 0

    # a compile on ANOTHER thread must not land in this thread's scope
    def other():
        jax.block_until_ready(jax.jit(lambda x: x * 3 + c)(jnp.ones(7)))

    with m.measure() as scope:
        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert scope.compiles == 0
    assert m.compiles >= 2                    # but the global total saw it
    assert m.recompiles <= m.compiles


def test_persistent_cache_round_trip(tmp_path, monkeypatch):
    """enable() -> compile -> clear in-memory caches -> reload from disk.

    `jax.clear_caches()` drops the in-process executable caches, so the
    second call can only avoid a real recompile by deserializing from the
    persistent directory -- the in-process mirror of the cross-process CI
    compile budget."""
    m = compile_cache.meter().install()
    d = str(tmp_path / "xc")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    try:
        assert compile_cache.enable() == d
        assert compile_cache.enabled_dir() == d

        c = float(np.random.default_rng(1).standard_normal())
        fn = jax.jit(lambda x: jnp.sin(x) * c)
        misses0, hits0 = m.cache_misses, m.cache_hits
        jax.block_until_ready(fn(jnp.ones(11)))
        assert m.cache_misses > misses0       # first compile: miss + write
        files = list(tmp_path.joinpath("xc").iterdir())
        assert files, "no entries persisted to the cache directory"

        jax.clear_caches()
        c0, r0, h0 = m.compiles, m.recompiles, m.cache_hits
        jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) * c)(jnp.ones(11)))
        assert m.cache_hits > h0              # answered from disk...
        # ...so strictly fewer REAL compiles than compile requests (only
        # programs first compiled before enable() may recompile here)
        assert m.recompiles - r0 < m.compiles - c0
    finally:
        compile_cache.disable()
        assert compile_cache.enabled_dir() is None


def test_cache_dir_defaults_to_checkout(monkeypatch):
    """With $JAX_COMPILATION_CACHE_DIR unset the cache has one fixed home
    inside the checkout, so a later run finds it again."""
    checkout = Path(__file__).resolve().parents[1]
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert compile_cache.cache_dir() == str(checkout / ".jax_cache")


def test_maybe_enable_from_env(tmp_path, monkeypatch):
    """Entry points place the cache from outside: with
    $JAX_COMPILATION_CACHE_DIR set, `enable()` roots the cache there."""
    d = str(tmp_path / "envxc")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", d)
    assert compile_cache.cache_dir() == d
    try:
        assert compile_cache.enable() == d
        assert compile_cache.enabled_dir() == d
        assert jax.config.jax_compilation_cache_dir == d
    finally:
        compile_cache.disable()


# ------------------------------------------------------- prewarm bitwise

def test_prewarmed_pool_results_bitwise_match_cold():
    spec = dict(seed=5, budget=4)
    warm_sch = PlacementScheduler(n_slots=2, gens_per_step=2, prewarm=True)
    warm_sch.prewarm("xcvu_test", CFG)
    assert warm_sch.prewarmer.wait_idle(timeout=300)
    assert warm_sch.prewarmer.builds_done == 1
    jid_w = warm_sch.submit("xcvu_test", CFG, **spec)
    warm = {j.jid: j for j in warm_sch.run_all()}[jid_w]
    assert warm_sch.prewarmer.adopted == 1    # took the background build

    cold_sch = PlacementScheduler(n_slots=2, gens_per_step=2)
    jid_c = cold_sch.submit("xcvu_test", CFG, **spec)
    cold = {j.jid: j for j in cold_sch.run_all()}[jid_c]

    assert warm.result.metric == cold.result.metric
    assert np.array_equal(warm.result.best_objs, cold.result.best_objs)
    for a, b in zip(jax.tree.leaves(warm.result.genotype),
                    jax.tree.leaves(cold.result.genotype)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_prewarm_failure_falls_back_to_synchronous_build():
    sch = PlacementScheduler(n_slots=2, gens_per_step=2, prewarm=True)
    key = sch.pool_key("xcvu_test", "nsga2", CFG)
    sch.prewarmer.prewarm_pool(key, lambda: 1 / 0)   # doomed build
    assert sch.prewarmer.wait_idle(timeout=60)
    assert sch.prewarmer.failures == 1
    assert sch.prewarmer.take(key) is None
    jid = sch.submit("xcvu_test", CFG, seed=0, budget=2)   # sync fallback
    done = {j.jid: j for j in sch.run_all()}
    assert done[jid].result is not None and done[jid].done
    assert "ZeroDivisionError" in json.dumps(sch.prewarmer.stats()["errors"])


def test_prewarmer_dedups_and_reports():
    pw = Prewarmer()
    built = []
    assert pw.prewarm_pool("k1", lambda: built.append(1) or "pool")
    assert not pw.prewarm_pool("k1", lambda: built.append(2) or "dup")
    assert pw.wait_idle(timeout=60)
    assert built == [1]
    assert pw.take("k1") == "pool"
    assert pw.take("k1") is None              # consumed
    s = pw.stats()
    assert s["builds_done"] == 1 and s["adopted"] == 1
    pw.stop()


# -------------------------------------------------- grow compile budget

def test_grow_on_prewarmed_size_zero_blocking_compiles():
    svc = PlacementService(BASE, CFG, n_slots=2, gens_per_step=2)
    svc.submit(seed=0, budget=64)
    svc.step()                                # all cold compiles done
    assert svc.blocking_compiles > 0

    assert svc.prewarm_size(4)
    assert not svc.prewarm_size(4)            # dedup
    assert not svc.prewarm_size(2)            # not a growth
    assert svc.prewarm_compiles > 0           # the ladder rung compiled...
    b0 = svc.blocking_compiles
    svc.grow(4)
    svc.step()
    svc.step()
    assert svc.blocking_compiles == b0        # ...so the loop never blocked
    assert 4 in svc.stats()["prewarmed_sizes"]

    # control: the same grow WITHOUT prewarm_size blocks on >= 1 compile
    ref = PlacementService(BASE, CFG, n_slots=2, gens_per_step=2)
    ref.submit(seed=0, budget=64)
    ref.step()
    b0 = ref.blocking_compiles
    ref.grow(4)
    ref.step()
    assert ref.blocking_compiles > b0


def test_grow_results_unchanged_by_prewarm():
    """prewarm_size moves compilation, never results: a grown pool's jobs
    match a pool that grew cold."""
    def run(prewarm: bool):
        svc = PlacementService(BASE, CFG, n_slots=1, gens_per_step=2)
        svc.submit(seed=7, budget=8)
        if prewarm:
            svc.prewarm_size(2)
        svc.grow(2)
        svc.submit(seed=8, budget=8)
        return {j.seed: j for j in _drain(svc)}

    a, b = run(True), run(False)
    assert a.keys() == b.keys()
    for seed in a:
        assert a[seed].metric == b[seed].metric
        assert np.array_equal(a[seed].best_objs, b[seed].best_objs)


# ------------------------------------------- store traffic -> prediction

def test_traffic_round_trip_drives_prewarm_predicted(tmp_path):
    store = ChampionStore()
    sch = PlacementScheduler(n_slots=2, gens_per_step=2, store=store)
    for s in range(2):                        # hottest signature: 2 hits
        sch.submit("xcvu_test", CFG, seed=s, budget=2)
    sch.submit("xcvu_test2", CFG, seed=0, budget=2)
    sch.run_all()

    path = str(tmp_path / "store.json")
    store.save(path)

    fresh = ChampionStore(path=path)          # the "restarted process"
    preds = fresh.predicted_keys()
    assert [p.count for p in preds] == [2, 1]
    assert preds[0].device_name == "xcvu_test"
    assert preds[0].algo == "nsga2" and preds[0].pop_size == 8

    sch2 = PlacementScheduler(n_slots=2, gens_per_step=2, store=fresh,
                              prewarm=True)
    keys = sch2.prewarm_predicted(top_k=1)
    assert len(keys) == 1
    assert sch2.prewarmer.wait_idle(timeout=300)
    assert sch2.prewarmer.builds_done == 1
    # traffic matching the prediction adopts the prewarmed pool -- note
    # the different float hyperparameters: only static fields route
    jid = sch2.submit("xcvu_test",
                      nsga2.NSGA2Config(pop_size=8, sbx_eta=19.0),
                      seed=3, budget=2)
    done = {j.jid: j for j in sch2.run_all()}
    assert done[jid].done
    assert sch2.prewarmer.adopted == 1


def test_traffic_counts_merge_on_load(tmp_path):
    a, b = ChampionStore(), ChampionStore()
    for store, n in ((a, 3), (b, 2)):
        for _ in range(n):
            store.note_traffic(BASE, algo="nsga2", pop_size=8)
    pa = str(tmp_path / "a.json")
    a.save(pa)
    b.load(pa)
    (pred,) = b.predicted_keys()
    assert pred.count == 5                    # 3 (loaded) + 2 (local)
    # old snapshots without a traffic key still load fine
    doc = json.loads(open(pa).read())
    del doc["traffic"]
    legacy = str(tmp_path / "legacy.json")
    with open(legacy, "w") as f:
        json.dump(doc, f)
    c = ChampionStore()
    c.load(legacy)
    assert c.predicted_keys() == []


# ------------------------------------------------------ admit resilience

def _patched_sched():
    """A scheduler whose (pre-created) pool we can sabotage before any
    job is submitted (submit() admits eagerly)."""
    sch = PlacementScheduler(n_slots=1, gens_per_step=2)
    key = sch.pool_key("xcvu_test", "nsga2", CFG)
    pool = sch._pool(key, CFG)
    return sch, pool


def test_admit_failure_requeues_with_error_note():
    sch, pool = _patched_sched()
    orig, calls = pool.submit_request, {"n": 0}

    def flaky(req):
        calls["n"] += 1
        if calls["n"] <= 2:                   # fail twice, then recover
            raise RuntimeError("slot allocator hiccup")
        return orig(req)

    pool.submit_request = flaky
    jid = sch.submit("xcvu_test", CFG, seed=0, budget=2)
    job = sch.jobs[jid]
    assert job.attempts == 1                  # first try failed at submit
    assert "slot allocator hiccup" in job.error
    assert not job.failed                     # re-queued, not given up
    done = {j.jid: j for j in sch.run_all()}
    assert done[jid].result is not None and done[jid].done
    assert done[jid].attempts == 2            # recovered on the third try


def test_admit_permanent_failure_surfaces_without_wedging():
    sch, pool = _patched_sched()
    orig = pool.submit_request

    def poison(req):
        if req.seed == 1:
            raise RuntimeError("poisoned job")
        return orig(req)

    pool.submit_request = poison
    bad = sch.submit("xcvu_test", CFG, seed=1, budget=2)
    good = sch.submit("xcvu_test", CFG, seed=2, budget=2)
    done = {j.jid: j for j in sch.run_all()}  # must terminate
    assert done.keys() == {bad, good}
    assert done[good].done and not done[good].failed
    assert done[bad].failed and done[bad].result is None
    assert done[bad].attempts == PlacementScheduler.ADMIT_RETRIES
    assert "poisoned job" in done[bad].error
    assert sch.stats()["jobs_failed"] == 1
    assert not sch.busy


def test_service_stats_report_compile_observability():
    svc = PlacementService(BASE, CFG, n_slots=1, gens_per_step=2)
    svc.submit(seed=0, budget=2)
    _drain(svc)
    s = svc.stats()
    for key in ("blocking_compiles", "blocking_compile_secs",
                "prewarm_compiles", "prewarm_compile_secs",
                "prewarmed_sizes", "time_to_first_gen_ms",
                "compiles_total", "recompiles_total", "compile_secs_total",
                "persistent_cache_dir"):
        assert key in s, key
    assert s["time_to_first_gen_ms"] > 0
    assert s["compiles_total"] >= s["blocking_compiles"]
