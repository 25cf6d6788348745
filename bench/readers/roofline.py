"""Share of the roofline: the least time the chip could take for one call's
work, over the device-busy time per call.

The work is counted from the problem's own statistics (``f_m``, ``nnz_c``
of the configuration), with per-unit coefficients from the metric's file:
never from a capacity or from what one implementation gathers, so every
implementation of the call is judged on the same work. The least time is
the larger of operations over peak FLOP/s and bytes over peak HBM bytes/s
(``peaks.json``, by the device's kind).
"""


def read(view, ctx, flops, bytes):
    if not view.n_calls or view.busy_s <= 0:
        return None
    stats, peak = ctx["stats"], ctx["peak"]
    n_flops = sum(c * stats[k] for k, c in flops.items())
    n_bytes = sum(c * stats[k] for k, c in bytes.items())
    least = max(n_flops / peak["flops_per_s"], n_bytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / (view.busy_s / view.n_calls)
