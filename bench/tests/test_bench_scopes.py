"""The named-scope reader (``readers/scope_device_per_call.py``) and its
walk over the trace's protobuf (``readers/xspace.py``), on device traces
recorded on a TPU v5e by the harness's own ``--trace 1`` path, one call
each:

- ``oneshot_stencil128``: ``spgemm(a, a, plan_cache=False)`` of a 5-point
  stencil on a 128 x 128 grid (fm_cap 2^19), the program with the scopes
  ``spgemm_expand``, ``spgemm_sort``, ``spgemm_permute``;
- ``replay_stencil256_scoped``: one ``ReuseExecutor.apply`` replay on a
  256 x 256 grid (2^21 products), with ``replay_gather``, ``replay_scatter``;
- ``replay_stencil256``: the same replay recorded before the scopes existed.

The two newer files are kept without the profiler's ``/host:metadata``
plane (the programs' HLO, which no reader opens). Expected numbers are
recomputed here from the raw events by a sweep over their boundaries, and
pinned as first read.
"""
import os
import shutil
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, tracing  # noqa: E402
from bench.readers import xspace  # noqa: E402

DATA = Path(__file__).parent / "data"
ONESHOT = DATA / "oneshot_stencil128.xplane.pb"
REPLAY = DATA / "replay_stencil256_scoped.xplane.pb"
OLD_REPLAY = DATA / "replay_stencil256.xplane.pb"
SPANS = {"spgemm.prepare", "plan.key", "plan.build", "numeric.dispatch"}

# (trace, program, scope, device ms per call as first read)
SCOPES = [
    (ONESHOT, "expand_and_sort", "spgemm_expand", 96.812983),
    (ONESHOT, "expand_and_sort", "spgemm_sort", 0.50892),
    (ONESHOT, "expand_and_sort", "spgemm_permute", 31.293144),
    (REPLAY, "_apply_impl", "replay_gather", 32.954792),
    (REPLAY, "_apply_impl", "replay_scatter", 17.325592),
    (OLD_REPLAY, "_apply_impl", "jit(numeric_reuse)", 49.26312),
]


@pytest.fixture(scope="module")
def reader():
    return harness.load_module(harness.BENCH_DIR, "readers",
                               "scope_device_per_call")


@pytest.fixture(scope="module")
def views():
    return {p: tracing.load(p, SPANS) for p in (ONESHOT, REPLAY, OLD_REPLAY)}


def sweep(intervals, lo, hi) -> int:
    """Covered length of [lo, hi) by a sweep over sorted boundaries."""
    points = sorted({lo, hi, *(p for iv in intervals for p in iv)})
    points = [p for p in points if lo <= p <= hi]
    return sum(b - a for a, b in zip(points, points[1:])
               if any(s <= a and b <= e for s, e in intervals))


def raw_scope_ops(path, program: str, scope: str) -> list:
    """The ops of ``scope`` inside ``jit_<program>`` modules, from the
    profiler's own events (ProfileData) and the walker's tf_op paths."""
    from jax.profiler import ProfileData

    dev = ProfileData.from_file(str(path)).find_plane_with_name(
        "/device:TPU:0")
    lines = {line.name: line for line in dev.lines}
    mods = [(e.start_ns, e.start_ns + e.duration_ns)
            for e in lines["XLA Modules"].events
            if e.name.startswith(f"jit_{program}(")]
    ops = [(e.start_ns, e.start_ns + e.duration_ns)
           for e in lines["XLA Ops"].events]
    paths = [tf for _, _, tf in xspace.device_ops(path)[0]]
    return [(s, e) for (s, e), tf in zip(ops, paths)
            if xspace.has_scope(tf, scope)
            and any(ms <= s < me for ms, me in mods)]


@pytest.mark.parametrize("path", [ONESHOT, REPLAY, OLD_REPLAY],
                         ids=lambda p: p.stem)
def test_the_walk_reads_the_profilers_own_ops(views, path):
    ops = xspace.device_ops(path)
    assert [[(s, e) for s, e, _ in dev] for dev in ops] == [
        [(s, e) for _, s, e in dev] for dev in views[path].ops]
    assert any(tf for s, e, tf in ops[0])


@pytest.mark.parametrize("path,program,scope,ms", SCOPES,
                         ids=[s[2] for s in SCOPES])
def test_each_scope_is_attributed(reader, views, path, program, scope, ms):
    view = views[path]
    secs = reader.scope_seconds(view, path, program, scope)
    want = sweep(raw_scope_ops(path, program, scope), *view.window) * 1e-9
    assert secs == pytest.approx(want, rel=1e-12)
    assert secs * 1e3 / view.n_calls == pytest.approx(ms, rel=1e-9)


def test_the_expansion_loop_counts_once(reader, views):
    """The searchsorted loop's body runs once per bisection step inside the
    ``while`` op; the scope counts the covered time, not the sum of the
    ``while`` and its body the breakdown lists side by side."""
    view = views[ONESHOT]
    ops = xspace.device_ops(ONESHOT)[0]
    (loop,) = [(s, e) for (name, s, e), (_, _, tf) in zip(view.ops[0], ops)
               if name.startswith("%while")]
    body = [(s, e) for s, e, tf in ops
            if tf and "/while/body/" in tf and loop[0] <= s and e <= loop[1]]
    assert len(body) >= 17  # one per bisection step over 2^17 A slots
    top = dict(tracing.breakdown(view)["device_ops"])
    listed = sum(v for k, v in top.items() if "%while" in k or
                 k.startswith("expand_and_sort:%fusion.35 "))
    secs = reader.scope_seconds(view, ONESHOT, "expand_and_sort",
                                "spgemm_expand")
    assert listed > 1.9 * (loop[1] - loop[0]) * 1e-9  # counted twice there
    assert secs < listed


def test_nested_ops_of_one_scope_count_once(reader, monkeypatch):
    """A loop op and its body ops, all of the scope: covered time once."""
    ms = 1_000_000
    ops = [[(0, 100 * ms, "jit(f)/s/while:"), (10 * ms, 20 * ms, "jit(f)/s/add:"),
            (30 * ms, 40 * ms, "jit(f)/s/while/body/gather:"),
            (120 * ms, 130 * ms, "jit(f)/other:")]]
    view = tracing.TraceView(
        window=(0, 200 * ms), calls=[(0, 1), (1, 2)],
        ops=[[("op", s, e) for s, e, _ in ops[0]]],
        modules=[[("jit_f(1)", 0, 150 * ms)]])
    monkeypatch.setattr(reader.xspace, "device_ops", lambda _: ops)
    assert reader.scope_seconds(view, "t", "f", "s") == pytest.approx(0.1)
    assert reader.scope_seconds(view, "t", "f", "other") == pytest.approx(0.01)
    assert reader.scope_seconds(view, "t", "g", "s") == 0


def test_plan_build_scopes_and_plan_from_sorted_make_the_plan_build(
        reader, views):
    view = views[ONESHOT]
    parts = sum(reader.scope_seconds(view, ONESHOT, "expand_and_sort", s)
                for s in ("spgemm_expand", "spgemm_sort", "spgemm_permute"))
    parts += tracing.program_time(view, ["plan_from_sorted"])
    whole = tracing.program_time(view, ["expand_and_sort", "plan_from_sorted"])
    assert parts == pytest.approx(whole, rel=0.01)
    assert parts <= whole


def test_replay_scopes_make_the_replay(reader, views):
    view = views[REPLAY]
    parts = sum(reader.scope_seconds(view, REPLAY, "_apply_impl", s)
                for s in ("replay_gather", "replay_scatter"))
    assert parts == pytest.approx(view.busy_s, rel=0.01)
    assert parts <= view.busy_s


@pytest.mark.parametrize("path,program,scope", [
    (ONESHOT, "expand_and_sort", "replay_gather"),
    (ONESHOT, "plan_from_sorted", "spgemm_sort"),
    (OLD_REPLAY, "_apply_impl", "replay_gather"),  # recorded before scopes
], ids=["other_scope", "other_program", "before_the_scopes"])
def test_a_missing_scope_gives_nothing(reader, views, monkeypatch, tmp_path,
                                       path, program, scope):
    view = views[path]
    assert not reader.scope_seconds(view, path, program, scope)
    place(tmp_path, path, "cell")
    monkeypatch.setattr(reader, "TRACES", tmp_path)
    assert reader.read(view, {}, program=program, scope=scope) is None


def place(root: Path, trace: Path, cell: str, mtime: float | None = None):
    """``trace`` where the harness writes a cell's trace under ``root``."""
    dest = root / cell / "plugins" / "profile" / "2026_01_01" / "host.xplane.pb"
    dest.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(trace, dest)
    if mtime is not None:
        os.utime(dest, (mtime, mtime))
    return dest


def test_read_takes_the_newest_trace_and_only_the_views_own(
        reader, views, monkeypatch, tmp_path):
    monkeypatch.setattr(reader, "TRACES", tmp_path)
    view = views[REPLAY]
    assert reader.read(view, {}, program="_apply_impl",
                       scope="replay_gather") is None  # no trace at all
    place(tmp_path, ONESHOT, "a.oneshot", mtime=1_000)
    place(tmp_path, REPLAY, "b.replay", mtime=2_000)
    assert reader.read(view, {}, program="_apply_impl",
                       scope="replay_gather") == pytest.approx(32.954792)
    # a newer trace of another run is not the view's own: nothing
    place(tmp_path, ONESHOT, "a.oneshot", mtime=3_000)
    assert reader.scope_seconds(view, ONESHOT, "_apply_impl",
                                "replay_gather") is None
    assert reader.read(view, {}, program="_apply_impl",
                       scope="replay_gather") is None


@pytest.mark.parametrize("tf_op,scope,hit", [
    ("jit(_apply_impl)/jit(numeric_reuse)/replay_gather/gather:", "replay_gather", True),
    ("jit(_apply_impl)/jit(numeric_reuse)/replay_gather/gather:", "gather", True),
    ("jit(_apply_impl)/jit(numeric_reuse)/replay_gathers/gather:", "replay_gather", False),
    ("jit(expand_and_sort)/spgemm_sort/jit(argsort)/sort:", "spgemm_sort", True),
    ("jit(expand_and_sort)/spgemm_sort_x/sort:", "spgemm_sort", False),
    (None, "spgemm_sort", False),
])
def test_a_scope_is_a_whole_component_of_the_path(tf_op, scope, hit):
    assert xspace.has_scope(tf_op, scope) is hit


def test_the_metric_files_name_the_scopes():
    spec = harness.load_json(harness.BENCH_DIR.parent / "BENCHMARK.json")
    want = {"expand_device_ms.oneshot": ("expand_and_sort", "spgemm_expand"),
            "sort_device_ms.oneshot": ("expand_and_sort", "spgemm_sort"),
            "permute_device_ms.oneshot": ("expand_and_sort", "spgemm_permute"),
            "replay_gather_device_ms.replay": ("_apply_impl", "replay_gather"),
            "replay_scatter_device_ms.replay": ("_apply_impl", "replay_scatter")}
    layers = {m["name"]: m for m in spec["per_layer"]}
    for name, (program, scope) in want.items():
        m = harness.load_json(harness.BENCH_DIR / "metrics" / f"{name}.json")
        assert m["reader"] == "scope_device_per_call"
        assert m["params"] == {"program": program, "scope": scope}
        assert layers[name]["source"] == "device_trace"
    key = harness.load_json(harness.BENCH_DIR / "metrics"
                            / "structure_key_ms.oneshot.json")
    assert key["params"] == {"span": "plan.key"}
