"""job_p50_s: median due -> champion seconds over every job due in the
window.  A job that failed or never finished counts as infinitely late."""
import math


def read(run):
    lat = sorted(r.latency for r in run.window_jobs)
    if not lat:
        return None
    v = lat[math.ceil(0.50 * len(lat)) - 1]          # nearest rank
    return v if math.isfinite(v) else None
