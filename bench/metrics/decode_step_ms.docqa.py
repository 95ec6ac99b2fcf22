"""Device time of one call of the decode program (``serve_decode``),
averaged over its calls in the traced stretch."""
from bench.metrics._serve import per_call


def read(run):
    pc = per_call(run, "serve_decode")
    return None if pc is None else pc[0] * 1e3
