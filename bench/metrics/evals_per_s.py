"""evals_per_s: candidate placements evaluated for real jobs per second.

Useful generations served in the window (active slots only, counted by each
pool as `useful_gens`) times the pool's population, over the window's
seconds on the host clock.
"""


def read(run):
    if run.seconds <= 0 or not run.pools:
        return None
    return sum(p["useful_gens"] * p["pop"] for p in run.pools) / run.seconds
