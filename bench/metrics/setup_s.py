"""setup_s: process start to window open on the host clock -- JAX and the
chip coming up, the problem build, pool construction and the warm-up wave,
compiles (or compile-cache loads) included."""


def read(run):
    return run.setup_s
