"""The trace reduction on a device trace recorded on a TPU v5e: one
``ReuseExecutor.apply`` replay of a 5-point stencil on a 256 x 256 grid
squared (2^21 products), traced by the harness's own ``--trace 1`` path.
The expected numbers are recomputed here from the raw profiler events by a
sweep over the event boundaries, and pinned as first read."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, tracing  # noqa: E402

TRACE = Path(__file__).parent / "data" / "replay_stencil256.xplane.pb"
SPANS = {"spgemm.prepare", "plan.build", "numeric.dispatch"}


@pytest.fixture(scope="module")
def view():
    return tracing.load(TRACE, SPANS)


def raw_events():
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(TRACE))
    dev = data.find_plane_with_name("/device:TPU:0")
    ops = [(e.start_ns, e.start_ns + e.duration_ns)
           for line in dev.lines if line.name == "XLA Ops" for e in line.events]
    mods = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for line in dev.lines if line.name == "XLA Modules"
            for e in line.events]
    host = data.find_plane_with_name("/host:CPU")
    calls = [(e.start_ns, e.start_ns + e.duration_ns) for line in host.lines
             for e in line.events if e.name == "bench.call"]
    return ops, mods, calls


def sweep_busy(intervals, lo, hi):
    """Covered length of [lo, hi) by a sweep over sorted boundaries."""
    points = sorted({lo, hi, *(p for iv in intervals for p in iv)})
    points = [p for p in points if lo <= p <= hi]
    return sum(b - a for a, b in zip(points, points[1:])
               if any(s <= a and b <= e for s, e in intervals))


def test_window_and_calls(view):
    _, _, calls = raw_events()
    assert view.n_calls == len(calls) == 1
    assert view.window == (calls[0][0], calls[0][1])
    assert view.window_s == pytest.approx(0.051464356, rel=1e-9)


def test_busy_and_idle_share(view):
    ops, _, calls = raw_events()
    busy = sweep_busy(ops, *view.window) * 1e-9
    assert view.busy_s == pytest.approx(busy, rel=1e-12)
    assert view.busy_s == pytest.approx(0.049272814, rel=1e-9)
    idle = harness.load_module(harness.BENCH_DIR, "readers", "idle_share")
    assert idle.read(view, {}) == pytest.approx(
        100 * (1 - busy / view.window_s), rel=1e-12)
    assert idle.read(view, {}) == pytest.approx(4.2584, abs=1e-4)


def test_per_program_time(view):
    _, mods, _ = raw_events()
    lo, hi = view.window
    want = sum(min(e, hi) - max(s, lo) for name, s, e in mods
               if name.startswith("jit__apply_impl(")) * 1e-9
    assert tracing.program_time(view, ["_apply_impl"]) == pytest.approx(want)
    assert want == pytest.approx(0.049272827, rel=1e-9)
    assert tracing.program_time(view, ["expand_and_sort"]) == 0


def test_the_replay_reader_and_the_breakdown(view):
    busy = harness.load_module(harness.BENCH_DIR, "readers",
                               "device_busy_per_call")
    assert busy.read(view, {}) == pytest.approx(49.272814, rel=1e-9)
    b = tracing.breakdown(view)
    top = [name for name, _ in b["device_ops"][:3]]
    assert all(name.startswith("_apply_impl:%fusion") for name in top)
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(
        view.window_s - view.busy_s)
    assert b["idle_gaps"][0][0] == "bench.call"
