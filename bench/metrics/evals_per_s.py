"""evals_per_s: candidate placements evaluated for real jobs per second.

Useful generations served in the window (active slots only, counted by each
pool as `useful_gens`) times the placements a job evaluates per generation
(the configuration's algorithm module, `bench/algorithms/<algorithm>.py`,
`evals_per_gen`), over the window's seconds on the host clock.
"""


def read(run):
    if run.seconds <= 0 or not run.pools:
        return None
    return sum(p["useful_gens"] * p["evals_per_gen"]
               for p in run.pools) / run.seconds
