"""The one traffic generator: a mix file's parameters and a seed -> jobs.

A mix (`bench/traffic/<mix>.json`) is data.  Keys:

  loop             "closed" (clients resubmit when their job returns) or
                   "open" (jobs fall due on a schedule, whatever happens)
  clients          closed loop: number of clients
  rate_jobs_per_s  open loop: mean arrival rate
  preroll_s        open loop: seconds of arrivals before the window opens,
                   so the window starts in steady state
  budget           [low, high] generations; equal ends give one budget
  budget_multiple  budgets are rounded to a multiple of this
  hyper            {field: [low, high]}: float hyperparameters of the
                   configuration's algorithm, drawn uniformly per job

Open-loop schedules are stratified: every seed gets the same set of
inter-arrival gaps (exponential quantiles) and the same set of budgets
(log-uniform quantiles) in another order, so seeds change the order of the
work and not its amount.  A job is a plain dict:
{"index", "due_s" (None in a closed loop), "seed", "budget", "hyper"}.
"""
from __future__ import annotations

import json
import math
from typing import Dict, Iterator, List

import numpy as np

JOB_SEED_BITS = 31


def load(path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _rng(seed: int, *words: int) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([abs(int(seed)), int(seed < 0), *words]))


def _job(mix: Dict, seed: int, index: int, budget: int, due_s) -> Dict:
    rng = _rng(seed, 1, index)
    return {"index": index, "due_s": due_s,
            "seed": int(rng.integers(0, 2 ** JOB_SEED_BITS)),
            "budget": int(budget),
            "hyper": {k: float(rng.uniform(lo, hi))
                      for k, (lo, hi) in sorted(mix.get("hyper", {}).items())}}


def _budget_quantiles(mix: Dict, n: int) -> np.ndarray:
    """n budgets at the mid-quantiles of a log-uniform law over
    mix["budget"], rounded to mix["budget_multiple"]."""
    lo, hi = mix["budget"]
    m = mix.get("budget_multiple", 1)
    q = (np.arange(n) + 0.5) / n
    b = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    return np.clip(np.round(b / m) * m, lo, hi).astype(int)


def closed(mix: Dict, seed: int) -> Iterator[Dict]:
    """Endless job stream for the clients of a closed loop, in order of
    request.  Budgets are drawn from the same law, one job at a time."""
    index = 0
    while True:
        rng = _rng(seed, 2, index)
        lo, hi = mix["budget"]
        m = mix.get("budget_multiple", 1)
        b = math.exp(rng.uniform(math.log(lo), math.log(hi)))
        budget = int(min(max(round(b / m) * m, lo), hi))
        yield _job(mix, seed, index, budget, None)
        index += 1


def open_block(mix: Dict, seed: int, block: int, seconds: float
               ) -> List[Dict]:
    """Open-loop jobs due in [block * seconds, (block + 1) * seconds):
    block 0 is the measured window, block -1 the pre-roll, blocks >= 1
    background load while the window's jobs drain."""
    rate = mix["rate_jobs_per_s"]
    n = max(1, int(round(rate * seconds)))
    rng = _rng(seed, 3, block + 1_000)
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    budgets = rng.permutation(_budget_quantiles(mix, n))
    due = np.cumsum(gaps) - gaps[0]
    due = due * (seconds / max(due[-1] + gaps[0], 1e-9))
    first = (block + 1_000) * 1_000_000
    return [_job(mix, seed, first + k, budgets[k], block * seconds + due[k])
            for k in range(n)]
