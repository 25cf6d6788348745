"""Device milliseconds per timed call in the named jitted programs (the
``XLA Modules`` events whose name is ``jit_<program>``); nothing when no
such program ran in the window."""
from bench.tracing import program_time


def read(view, ctx, programs):
    secs = program_time(view, programs)
    if not view.n_calls or secs <= 0:
        return None
    return secs * 1e3 / view.n_calls
