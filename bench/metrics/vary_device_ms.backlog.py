"""vary_device_ms.backlog: device milliseconds per execution of the pool's step
program (`jit__step`) in the `vary` phase of NSGA-II -- SBX, polynomial
mutation, order crossover and swap mutation of the children -- from the
profiler trace, each operation charged to the phase its `jax.named_scope`
names (`bench/scope_reduce.py`)."""
from bench import scope_reduce


def read(run):
    ms = scope_reduce.phase_ms(run)
    return None if ms is None else ms["vary"]
