"""step_ms.backlog: host milliseconds per pool step -- the window's seconds
over the batched steps the pools took in it (`PlacementService.total_steps`).
"""


def read(run):
    steps = sum(p["steps"] for p in run.pools)
    return 1e3 * run.seconds / steps if steps else None
