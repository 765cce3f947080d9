"""harvest_ms.open: mean milliseconds of a `pool.harvest` span -- the
champion's extraction and host copies when a job finishes
(`serve/placement_service.py`) -- over the jobs due in the window, from
the program's spans (traced runs; `bench/spans.py`)."""
from bench import spans as S


def read(run):
    return S.mean_ms(run, "pool.harvest")
