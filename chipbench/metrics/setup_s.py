"""Seconds from the process's start to the first request: JAX's start,
the backend's compiles and warm-up, the weights, the engine."""


def read(run):
    return run.setup_s
