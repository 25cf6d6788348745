"""Device-busy milliseconds per timed call: the union of the device's
operation intervals in the traced window over the calls in it. It names no
operation, so a renamed or rewritten program is still counted."""


def read(view, ctx):
    if not view.n_calls or view.busy_s <= 0:
        return None
    return view.busy_s * 1e3 / view.n_calls
