"""job_init_ms.open: mean milliseconds of a `job.init` span -- a job's
admission into a slot: its initial population and the splice into the
pool's state (`serve/placement_service.py`) -- over the jobs due in the
window, from the program's spans (traced runs; `bench/spans.py`)."""
from bench import spans as S


def read(run):
    return S.mean_ms(run, "job.init")
