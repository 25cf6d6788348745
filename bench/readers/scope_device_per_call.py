"""Device milliseconds per timed call in one named scope of one jitted
program: the union of the intervals of the device operations that start
inside a ``jit_<program>`` module (``XLA Modules``) in the traced window
and whose ``tf_op`` path has ``scope`` as a component, so that nested
operations (a while loop and its body) count once; averaged over the
devices. Nothing when no operation matches.

``TraceView`` keeps no op metadata, so the reader opens the run's own
trace, the newest ``.xplane.pb`` under ``bench/.traces/``, and gives
nothing unless that trace holds, on each device, the view's number of
operations inside the window.
"""
import bisect
import glob
import os
import re
from pathlib import Path

from bench.readers import xspace
from bench.tracing import union

TRACES = Path(__file__).resolve().parents[1] / ".traces"


def newest_trace() -> str | None:
    found = glob.glob(os.path.join(str(TRACES), "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def in_window(intervals, window) -> int:
    lo, hi = window
    return sum(1 for s, e in intervals if e > lo and s < hi)


def scope_seconds(view, path, program: str, scope: str) -> float | None:
    """Seconds of the window in which an op of ``scope`` inside
    ``jit_<program>`` ran, averaged over the devices; None when the trace
    at ``path`` is not the view's own."""
    devices = xspace.device_ops(path)
    if len(devices) != len(view.ops) or any(
            in_window([(s, e) for s, e, _ in mine], view.window)
            != in_window([(s, e) for _, s, e in theirs], view.window)
            for mine, theirs in zip(devices, view.ops)):
        return None
    pat = re.compile(r"^(?:jit_)?%s(?:[.(\[]|$)" % re.escape(program))
    total = 0
    for d, ops in enumerate(devices):
        mods = sorted((s, e) for name, s, e in view.modules[d]
                      if pat.match(name))
        starts = [s for s, _ in mods]

        def inside(t):
            i = bisect.bisect_right(starts, t) - 1
            return i >= 0 and t < mods[i][1]

        picked = [(s, e) for s, e, tf_op in ops
                  if xspace.has_scope(tf_op, scope) and inside(s)]
        total += sum(e - s for s, e in union(picked, view.window))
    return total * 1e-9 / max(len(devices), 1)


def read(view, ctx, program, scope):
    path = newest_trace()
    if path is None or not view.n_calls:
        return None
    secs = scope_seconds(view, path, program, scope)
    if not secs:
        return None
    return secs * 1e3 / view.n_calls
