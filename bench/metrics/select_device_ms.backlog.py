"""select_device_ms.backlog: device milliseconds per execution of the pool's
step program (`jit__step`) in the `select` phase of NSGA-II -- tournament
selection, the parent gathers and the (mu + lambda) truncation gathers --
from the profiler trace, each operation charged to the phase its
`jax.named_scope` names (`bench/scope_reduce.py`)."""
from bench import scope_reduce


def read(run):
    ms = scope_reduce.phase_ms(run)
    return None if ms is None else ms["select"]
