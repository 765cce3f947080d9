"""Faults planted under a run's timed path must come out as not correct.

Each drives the whole harness at `xcvu_test` size on the CPU (set-up,
window, drain, the reference check), skipping only the look for a chip."""
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from bench import run as B  # noqa: E402
from repro.core import evolve, portfolio  # noqa: E402
from repro.kernels import ops  # noqa: E402
from test_bench_run import CLOSED, tiny_run  # noqa: E402


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
