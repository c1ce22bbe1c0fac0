"""Set-up: process start to the first timed session (JAX start, cluster
build, native build wait, warm-up sessions and, on a first run, compiles)."""


def read(run):
    return run.setup_s
