"""The benchmark's traffic generator: determinism and the mix parameters."""
import itertools
import json
import math
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import generator  # noqa: E402

OPEN = {"loop": "open", "rate_jobs_per_s": 9.0, "preroll_s": 3.0,
        "budget": [16, 512], "budget_multiple": 4,
        "hyper": {"sbx_eta": [5.0, 25.0], "real_mut_prob": [0.05, 0.3]}}
CLOSED = {"loop": "closed", "clients": 16, "budget": [128, 128],
          "budget_multiple": 4,
          "hyper": {"sbx_eta": [5.0, 25.0], "real_mut_prob": [0.05, 0.3]}}
BIG_SEED = 2 ** 31 + 12_345


def first(stream, n):
    return list(itertools.islice(stream, n))


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_same_seed_same_schedule(seed):
    assert generator.open_block(OPEN, seed, 0, 30.0) == \
        generator.open_block(OPEN, seed, 0, 30.0)
    assert first(generator.closed(CLOSED, seed), 50) == \
        first(generator.closed(CLOSED, seed), 50)


def test_different_seeds_different_schedules():
    a = generator.open_block(OPEN, 1, 0, 30.0)
    b = generator.open_block(OPEN, 2, 0, 30.0)
    assert [j["due_s"] for j in a] != [j["due_s"] for j in b]
    assert [j["seed"] for j in a] != [j["seed"] for j in b]
    ca = first(generator.closed(CLOSED, 1), 20)
    cb = first(generator.closed(CLOSED, 2), 20)
    assert [j["hyper"] for j in ca] != [j["hyper"] for j in cb]


def test_open_block_matches_rate_and_budget_law():
    seconds = 30.0
    jobs = generator.open_block(OPEN, BIG_SEED, 0, seconds)
    assert len(jobs) == round(OPEN["rate_jobs_per_s"] * seconds)
    due = np.array([j["due_s"] for j in jobs])
    assert np.all(np.diff(due) > 0) and due[0] == 0.0 and due[-1] < seconds
    budgets = np.array([j["budget"] for j in jobs])
    assert budgets.min() >= 16 and budgets.max() <= 512
    assert np.all(budgets % 4 == 0)
    # log-uniform over [16, 512]: median ~ sqrt(16 * 512)
    assert abs(np.median(budgets) - math.sqrt(16 * 512)) < 8
    for j in jobs:
        assert 5.0 <= j["hyper"]["sbx_eta"] <= 25.0
        assert 0.05 <= j["hyper"]["real_mut_prob"] <= 0.3
        assert 0 <= j["seed"] < 2 ** generator.JOB_SEED_BITS


def test_every_seed_gets_the_same_work_in_another_order():
    a = generator.open_block(OPEN, 3, 0, 30.0)
    b = generator.open_block(OPEN, 4, 0, 30.0)
    assert sorted(j["budget"] for j in a) == sorted(j["budget"] for j in b)
    gaps = [np.sort(np.diff([j["due_s"] for j in x])) for x in (a, b)]
    assert np.allclose(gaps[0].sum(), gaps[1].sum(), rtol=0.05)


def test_blocks_tile_time_and_do_not_repeat_jobs():
    pre = generator.open_block(OPEN, 5, -1, 3.0)
    win = generator.open_block(OPEN, 5, 0, 30.0)
    after = generator.open_block(OPEN, 5, 1, 30.0)
    assert all(-3.0 <= j["due_s"] < 0.0 for j in pre)
    assert all(30.0 <= j["due_s"] < 60.0 for j in after)
    idx = [j["index"] for j in pre + win + after]
    assert len(set(idx)) == len(idx)


def test_closed_stream_budgets_and_the_committed_mixes():
    jobs = first(generator.closed(CLOSED, 11), 100)
    assert {j["budget"] for j in jobs} == {128}
    assert [j["index"] for j in jobs] == list(range(100))
    assert all(j["due_s"] is None for j in jobs)
    for path in (ROOT / "bench" / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        assert mix["loop"] in ("open", "closed"), path
        lo, hi = mix["budget"]
        assert 0 < lo <= hi and lo % mix.get("budget_multiple", 1) == 0
