"""Seconds XLA spent compiling (or fetching from the persistent cache)
during set-up, from JAX's compile-duration events. Moves setup_s."""


def read(run):
    return run.get("compile_s")
