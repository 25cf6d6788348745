"""Host milliseconds per timed call in the self time of one of the
program's spans (its duration less the spans nested in it); nothing when
the span was not recorded in the window."""
from bench.tracing import span_self_time


def read(view, ctx, span):
    secs, count = span_self_time(view, span)
    if not view.n_calls or not count:
        return None
    return secs * 1e3 / view.n_calls
