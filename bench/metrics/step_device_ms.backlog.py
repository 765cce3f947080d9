"""step_device_ms.backlog: device milliseconds per execution of the pool's
batched step program (`jit__step`, built in `serve/placement_service.py`),
from the profiler trace."""

STEP = "jit__step"


def read(run):
    if run.trace is None or STEP not in run.trace["modules"]:
        return None
    secs, count = run.trace["modules"][STEP]
    return 1e3 * secs / count
