"""`bench/algorithms/nsga2.py` against the program, at `xcvu_test` size on
the CPU: the same ranking and selection rules as `core/nsga2.py`, a sound
run that is correct where the control is not, and faults planted under a
run's timed path that come out as not correct.  The runs drive the whole
harness (set-up, window, drain, the check), skipping only the look for a
chip."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import reference as R  # noqa: E402
from bench import run as B  # noqa: E402
from repro.core import evolve, nsga2, portfolio  # noqa: E402
from repro.kernels import ops  # noqa: E402
from test_bench_reference import host, pair  # noqa: E402,F401
from test_bench_run import CLOSED, NSGA2, OPEN, TINY, tiny_run  # noqa: E402


def job(st, champ, champ_objs, objs=None):
    """The host copy the check reads, from a state and a champion."""
    return {"pop": host(st["pop"]),
            "objs": np.asarray(st["objs"]) if objs is None else objs,
            "champion": host(champ), "champion_objs": np.asarray(champ_objs)}


def test_ranking_and_selection_match_nsga2(pair):
    prog, ref = pair
    cfg = nsga2.NSGA2Config(pop_size=16)
    st = nsga2.init_state(prog, jax.random.PRNGKey(1), cfg)
    for k in range(5):
        st = nsga2.step(prog, cfg, st, jax.random.PRNGKey(10 + k))
    objs = np.asarray(st["objs"])
    want = np.asarray(nsga2.nondominated_rank(st["objs"]))
    assert np.array_equal(NSGA2.nondominated_ranks(objs), want)
    assert NSGA2.rank_inversions(objs) == 0
    shuffled = objs[np.random.default_rng(0).permutation(len(objs))]
    assert NSGA2.rank_inversions(shuffled) > 0 or \
        len(set(NSGA2.nondominated_ranks(objs))) == 1
    champ, champ_objs = portfolio.best_genotype(prog, "nsga2", st, cfg)
    out = NSGA2.check(ref, job(st, champ, champ_objs))
    assert out["selection_misses"] == 0 and out["rank_inversions"] == 0
    assert out["illegal_placements"] == 0
    assert out["objective_gap"] < R.OBJECTIVE_GAP_LIMIT
    # a champion that is not the least wl^2 x bbox member is a miss
    other = NSGA2.member(host(st["pop"]), len(objs) - 1)
    out = NSGA2.check(ref, job(st, other, objs[-1]))
    assert out["selection_misses"] == 1


def test_a_nan_objective_is_an_infinite_gap(pair):
    prog, ref = pair
    cfg = nsga2.NSGA2Config(pop_size=8)
    st = nsga2.init_state(prog, jax.random.PRNGKey(2), cfg)
    objs = np.array(st["objs"])
    objs[3, 0] = np.nan
    champ, champ_objs = portfolio.best_genotype(prog, "nsga2", st, cfg)
    out = NSGA2.check(ref, job(st, champ, champ_objs, objs))
    assert out["objective_gap"] == np.inf


@pytest.mark.parametrize("mix,metrics", [
    (CLOSED, ("evals_per_s", "setup_s", "step_ms.backlog", "compile_s")),
    (OPEN, ("job_p50_s", "setup_s"))])
def test_a_sound_run_is_correct(mix, metrics):
    out = tiny_run(mix, metrics=metrics)
    line = out["line"]
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == set(metrics)
    assert out["notes"]["window_compiles"] == 0
    assert out["notes"]["jobs_checked"] > 0
    assert list(line)[-1] == "checks"
    # the control, bfloat16 Eqs. 1-2 in the program's place, must fail
    control = B.compare(NSGA2, R.Problem(TINY["device"]), out["copies"],
                        control=True)
    assert control["objective_gap"] > 3 * R.OBJECTIVE_GAP_LIMIT
    assert control["objective_gap"] > 30 * out["numbers"]["objective_gap"]


@pytest.fixture
def fresh_traces():
    """Faults are planted in functions that jitted programs trace: start
    and end with no cached trace, so neither side sees the other's."""
    jax.clear_caches()
    yield
    jax.clear_caches()


def step_unchanged(monkeypatch):
    def member_round(problem, algo, static_key, n_gens, traced, state, key):
        return state, evolve.state_best_objs(state)
    monkeypatch.setattr(portfolio, "member_round", member_round)


def half_the_nets(monkeypatch):
    wl = ops.wirelength2

    def wirelength2(x1, y1, x2, y2, w):
        h = x1.shape[-1] // 2
        return 2.0 * wl(*(a[..., :h] for a in (x1, y1, x2, y2, w)))
    monkeypatch.setattr(ops, "wirelength2", wirelength2)


def answer_altered(monkeypatch):
    best = portfolio.best_genotype

    def best_genotype(problem, algo, state, cfg=None):
        g, objs = best(problem, algo, state, cfg)
        return g, objs * np.float32(1.01)
    monkeypatch.setattr(portfolio, "best_genotype", best_genotype)


@pytest.mark.parametrize("plant,fails", [
    (step_unchanged, "rank_inversions"),
    (half_the_nets, "objective_gap"),
    (answer_altered, "selection_misses")])
def test_a_planted_fault_is_not_correct(plant, fails, monkeypatch,
                                        fresh_traces):
    plant(monkeypatch)
    line = tiny_run(CLOSED, seed=97)["line"]
    assert not line["correct"]
    c = line["checks"][fails]
    assert c["value"] > c["limit"], line["checks"]
