"""Seconds per round that JAX spent tracing, lowering and compiling (or
loading from the compile cache) inside the window, from
jax.monitoring.  Reported, not excluded: it should be 0."""


def read(run):
    if not run.traced:
        return None
    return run.compile_s / run.rounds
