"""Reduction of a profiler trace to the numbers the per-layer readers use.

A traced run wraps its window in a ``bench.window`` annotation and each
timed call's sending in a ``bench.call`` one, and runs the program's spans
in ``xprof`` mode, so the host's spans and the device's operations share
the profiler's clock. ``TraceView`` holds, for the traced window (the
``bench.window`` span; in a trace without one, first ``bench.call`` start to
last ``bench.call`` end):

- ``ops``: per device, the intervals of its operations (the ``XLA Ops``
  line of each ``/device:`` plane);
- ``modules``: per device, the intervals of its programs (``XLA Modules``);
- ``spans``: the host spans whose names the caller asked for.

Busy time is the union of a device's operation intervals inside the window,
averaged over the devices; idle is the rest of the window.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

CALL_SPAN = "bench.call"
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class TraceView:
    window: tuple  # (start_ns, end_ns)
    calls: list  # [(start_ns, end_ns)] of bench.call
    ops: list  # per device: [(name, start_ns, end_ns)]
    modules: list  # per device: [(name, start_ns, end_ns)]
    spans: list = field(default_factory=list)  # [(name, start_ns, end_ns, line)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def n_calls(self) -> int:
        return len(self.calls)

    def busy_intervals(self, device: int) -> list:
        """Merged intervals in which an operation ran on ``device``, clipped
        to the window."""
        return union([(s, e) for _, s, e in self.ops[device]], self.window)

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(sum(e - s for s, e in self.busy_intervals(d))
                   for d in range(len(self.ops))) * 1e-9 / len(self.ops)


def union(intervals, window) -> list:
    """Merge (start, end) intervals, clipped to ``window``."""
    lo, hi = window
    merged: list = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(x) for x in merged]


def latest_xplane(trace_dir) -> str:
    """The newest ``.xplane.pb`` the profiler wrote under ``trace_dir``."""
    found = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load(path, span_names) -> TraceView:
    """Read an ``.xplane.pb`` into a ``TraceView`` (see the module doc)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops, modules, spans = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            ops.append(_events(lines[OPS_LINE]))
            modules.append(_events(lines[MODULES_LINE])
                           if MODULES_LINE in lines else [])
        elif plane.name.startswith("/host:"):
            for n, line in enumerate(plane.lines):
                spans += [(name, s, e, n) for name, s, e in _events(line)
                          if name in span_names
                          or name in (CALL_SPAN, WINDOW_SPAN)]
    calls = sorted((s, e) for name, s, e, _ in spans if name == CALL_SPAN)
    if not calls:
        raise ValueError(f"{path}: no {CALL_SPAN} span in the trace")
    return TraceView(window=window_of(spans, calls), calls=calls, ops=ops,
                     modules=modules, spans=spans)


def window_of(spans, calls) -> tuple:
    """The ``bench.window`` span, or in a trace without one the first
    call's start to the last call's end."""
    windows = [(s, e) for name, s, e, _ in spans if name == WINDOW_SPAN]
    return windows[0] if windows else (calls[0][0], calls[-1][1])


def _events(line) -> list:
    out = []
    for ev in line.events:
        s = int(ev.start_ns)
        out.append((ev.name, s, s + int(ev.duration_ns)))
    return out


def program_time(view: TraceView, programs) -> float:
    """Seconds the device spent in the programs (``XLA Modules`` events)
    whose jitted name is one of ``programs``, inside the window, summed
    over devices and averaged over them."""
    pat = re.compile(r"^(?:jit_)?(%s)(?:[.(\[]|$)" % "|".join(
        re.escape(p) for p in programs))
    lo, hi = view.window
    total = 0
    for dev in view.modules:
        for name, s, e in dev:
            if pat.match(name):
                total += max(0, min(e, hi) - max(s, lo))
    return total * 1e-9 / max(len(view.modules), 1)


def span_self_time(view: TraceView, name: str) -> tuple:
    """(seconds, count): the summed self time of the host spans called
    ``name`` inside the window (their duration less the parts that other
    recorded spans nested in them cover), and how many there were."""
    lo, hi = view.window
    mine = [sp for sp in view.spans if sp[0] == name and lo <= sp[1] <= hi]
    total = 0
    for _, s, e, line in mine:
        kids = [(cs, ce) for cn, cs, ce, cl in view.spans
                if cl == line and s <= cs and ce <= e and (cs, ce) != (s, e)]
        total += (e - s) - sum(b - a for a, b in union(kids, (s, e)))
    return total * 1e-9, len(mine)


def breakdown(view: TraceView, top: int = 10) -> dict:
    """The device operations that took most time, and the idle gaps summed
    by the innermost host span open over each gap's middle."""
    lo, hi = view.window
    by_op: dict = defaultdict(int)
    for d, dev in enumerate(view.ops):
        mods = sorted(view.modules[d], key=lambda m: m[1]) if d < len(
            view.modules) else []
        starts = [m[1] for m in mods]
        for name, s, e in dev:
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][0] if i >= 0 and s < mods[i][2] else ""
            by_op[op_label(mod, name)] += max(0, min(e, hi) - max(s, lo))
    n_dev = max(len(view.ops), 1)
    ops = sorted(((k, v * 1e-9 / n_dev) for k, v in by_op.items() if v),
                 key=lambda kv: -kv[1])[:top]
    gaps: dict = defaultdict(int)
    for d in range(len(view.ops)):
        edge = lo
        for s, e in view.busy_intervals(d) + [(hi, hi)]:
            if s > edge:
                gaps[_open_span(view, (edge + s) // 2)] += s - edge
            edge = max(edge, e)
    idle = sorted(((k, v * 1e-9 / n_dev) for k, v in gaps.items()),
                  key=lambda kv: -kv[1])[:top]
    return {"device_ops": [list(x) for x in ops],
            "idle_gaps": [list(x) for x in idle]}


def op_label(module: str, op: str) -> str:
    """``program:op result-type`` from a module's and an HLO op's names,
    e.g. ``_apply_impl:%fusion.1 f32[134217728]``."""
    mod = re.sub(r"\(\d+\)$", "", module)
    mod = mod[4:] if mod.startswith("jit_") else mod
    m = re.match(r"(%\S+) = (\S+?)(?:\{|\s|$)", op)
    short = f"{m.group(1)} {m.group(2)}" if m else op[:80]
    return f"{mod}:{short}" if mod else short


def _open_span(view: TraceView, t: int) -> str:
    """Name of the innermost recorded host span open at ``t``."""
    best = None
    for name, s, e, _ in view.spans:
        if s <= t < e and (best is None or s > best[1]
                           or (s == best[1] and e < best[2])):
            best = (name, s, e)
    return best[0] if best else "no span"
