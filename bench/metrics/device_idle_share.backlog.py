"""device_idle_share.backlog: share of the traced window in which no
operation ran on the chip, from the profiler trace (`bench/trace_reduce.py`):
100 x (1 - busy / window)."""


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
