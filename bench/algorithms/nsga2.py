"""NSGA-II as the placement service serves it (`core/nsga2.py`): what the
harness needs of this algorithm, loaded by `bench/run.py` for a
configuration whose "algorithm" is "nsga2".

A job's final state holds its population (`pop`, the genotypes, and
`objs`, their float32 (wl^2, bbox)), which the (mu + lambda) truncation
leaves ordered by non-dominated rank; its champion is the member with the
least combined metric wl^2 x bbox.  The check re-derives both by brute
force and compares every member's and the champion's objectives with the
reference (`bench/reference.py`).  Everything but `job_config` is numpy
only and imports nothing from `src/repro`.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench import reference

ALGO = "nsga2"                     # the service's name (`JobRequest.algo`)
LIMITS = {"selection_misses": 0, "rank_inversions": 0}
PARTS = ("dist", "loc", "perm")


def job_config(algorithm: Dict, hyper: Dict):
    """The program's config object for one job: the configuration's
    `search.algorithm` with the job's drawn hyperparameters."""
    from repro.core.nsga2 import NSGA2Config
    return NSGA2Config(**algorithm, **hyper)


def evals_per_gen(cfg, prob: reference.Problem) -> int:
    """Placements one job evaluates per generation: its offspring, as many
    as the population."""
    return cfg.pop_size


def host_copy(state: Dict, result) -> Dict:
    """What the check needs of one job, on the host: its final population
    and objectives from the harvested state, and the champion the service
    reported."""
    return {"pop": {p: tuple(np.asarray(a) for a in state["pop"][p])
                    for p in PARTS},
            "objs": np.asarray(state["objs"]),
            "champion": result.genotype,
            "champion_objs": np.asarray(result.best_objs)}


# --------------------------------------------------------------- selection

def domination(objs: np.ndarray) -> np.ndarray:
    """dom[i, j]: member i dominates member j (minimisation)."""
    a, b = objs[:, None, :], objs[None, :, :]
    return np.all(a <= b, axis=-1) & np.any(a < b, axis=-1)


def nondominated_ranks(objs: np.ndarray) -> np.ndarray:
    """Pareto front index of every member (0 = best), by peeling fronts."""
    dom = domination(objs)
    rank = np.full(len(objs), -1)
    r = 0
    while np.any(rank < 0):
        left = rank < 0
        front = left & ~np.any(dom & left[:, None], axis=0)
        rank[front] = r
        r += 1
    return rank


def rank_inversions(objs: np.ndarray) -> int:
    """Pairs i < j in which member j dominates member i: an NSGA-II
    population leaves its (mu + lambda) truncation sorted by rank, so a
    correct final population has none."""
    return int(np.triu(domination(objs).T, k=1).sum())


def champion_index(objs: np.ndarray) -> int:
    """The member with the least combined metric wl^2 x bbox, taken in the
    population's own float32 objectives (first on ties)."""
    o = np.asarray(objs, np.float32)
    return int(np.argmin(o[:, 0] * o[:, 1]))


# --------------------------------------------------------------- the check

def member(pop: Dict, k: int) -> Dict:
    """Member k of a population genotype (leading population axis)."""
    return {part: tuple(np.asarray(a)[k] for a in pop[part])
            for part in PARTS}


def candidates(copy: Dict) -> List[Tuple[Dict, np.ndarray]]:
    """Every genotype the check compares with the reference, with the
    objectives the program gave it: each member, then the champion."""
    objs = np.asarray(copy["objs"], np.float32)
    out = [(member(copy["pop"], k), objs[k]) for k in range(len(objs))]
    out.append((copy["champion"],
                np.asarray(copy["champion_objs"], np.float32)))
    return out


def check(prob: reference.Problem, copy: Dict, control: bool = False
          ) -> Dict:
    """Compare one job's final population and reported champion with the
    reference.  `control=True` puts the bfloat16 reference in the place of
    the program's objectives.  Returns the numbers that `correct` weighs."""
    objs = np.asarray(copy["objs"], np.float32)
    champion = copy["champion"]
    champion_objs = np.asarray(copy["champion_objs"], np.float32)
    out = merge([])
    sizes = prob.sizes()
    shaped = all(np.shape(champion[part][t]) == (sizes[part][t],)
                 for part in sizes for t in reference.TYPES)
    if not shaped or objs.ndim != 2 or objs.shape[1] != 2:
        out["illegal_placements"] = 1
        out["selection_misses"] = 1
        return out
    for g, reported in candidates(copy):
        bad, gap = reference.genotype_gap(prob, g, reported, control)
        out["illegal_placements"] += bad
        if gap is None:
            out["members_ambiguous"] += 1
            continue
        out["objective_gap"] = max(out["objective_gap"], gap)
        out["members_checked"] += 1
    best = member(copy["pop"], champion_index(objs))
    same = all(np.array_equal(np.asarray(best[p][t]),
                              np.asarray(champion[p][t]))
               for p in PARTS for t in reference.TYPES)
    if not (same and np.array_equal(champion_objs,
                                    objs[champion_index(objs)])):
        out["selection_misses"] = 1
    out["rank_inversions"] = rank_inversions(objs)
    return out


def merge(results: Sequence[Dict]) -> Dict:
    """Fold per-job numbers: gaps by their maximum, counts by their sum."""
    out = {"objective_gap": 0.0, "illegal_placements": 0,
           "selection_misses": 0, "rank_inversions": 0,
           "members_checked": 0, "members_ambiguous": 0}
    for r in results:
        for k, v in r.items():
            out[k] = max(out[k], v) if k == "objective_gap" else out[k] + v
    return out
