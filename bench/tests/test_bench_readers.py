"""The trace reduction and the per-layer readers, on traces whose numbers
are known by construction."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, tracing  # noqa: E402

MS = 1_000_000  # ns
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def reader(name):
    return harness.load_module(harness.BENCH_DIR, "readers", name)


def metric(name):
    return harness.load_json(harness.BENCH_DIR / "metrics" / f"{name}.json")


@pytest.fixture
def view():
    """Two calls in a 100 ms window. Device: ops at [5, 25) and [20, 40)
    (overlapping: busy 35 ms) and [60, 90); a program ``expand_and_sort``
    covers [5, 40). Host: ``spgemm.prepare`` [0, 10) with ``plan.build``
    nested at [4, 6), and a stray op outside the window."""
    calls = [(0, 50 * MS), (50 * MS, 100 * MS)]
    ops = [[("sort", 5 * MS, 25 * MS), ("gather", 20 * MS, 40 * MS),
            ("scatter", 60 * MS, 90 * MS), ("late", 120 * MS, 130 * MS)]]
    modules = [[("jit_expand_and_sort(3)", 5 * MS, 40 * MS),
                ("jit_numeric_reuse", 60 * MS, 90 * MS),
                ("jit_expand_and_sort_other", 95 * MS, 99 * MS)]]
    spans = [("bench.call", s, e, 0) for s, e in calls] + [
        ("spgemm.prepare", 0, 10 * MS, 0), ("plan.build", 4 * MS, 6 * MS, 0),
        ("numeric.dispatch", 55 * MS, 95 * MS, 0)]
    return tracing.TraceView(window=(0, 100 * MS), calls=calls, ops=ops,
                             modules=modules, spans=spans)


def test_busy_is_the_union_inside_the_window(view):
    assert view.busy_intervals(0) == [(5 * MS, 40 * MS), (60 * MS, 90 * MS)]
    assert view.busy_s == pytest.approx(0.065)
    assert view.window_s == pytest.approx(0.1)


def test_idle_share(view):
    assert reader("idle_share").read(view, {}) == pytest.approx(35.0)


def test_device_busy_per_call(view):
    assert reader("device_busy_per_call").read(view, {}) == pytest.approx(32.5)


def test_programs_per_call_matches_names_exactly(view):
    m = metric("plan_build_device_ms.oneshot")
    got = reader(m["reader"]).read(view, {}, **m["params"])
    assert got == pytest.approx(35.0 / 2)


def test_span_self_time_leaves_out_nested_spans(view):
    m = metric("prepare_ms.oneshot")
    got = reader(m["reader"]).read(view, {}, **m["params"])
    assert got == pytest.approx((10 - 2) / 2)


def test_readers_return_nothing_when_nothing_ran(view):
    empty = tracing.TraceView(window=view.window, calls=view.calls,
                              ops=[[]], modules=[[]], spans=view.spans[:2])
    for name in ("device_busy_per_call", "roofline"):
        m = {"roofline": metric("replay_roofline")["params"]}.get(name, {})
        assert reader(name).read(empty, {"stats": {}, "peak": PEAK}, **m) is None
    assert reader("programs_per_call").read(
        empty, {}, programs=["expand_and_sort"]) is None
    assert reader("span_self_per_call").read(
        empty, {}, span="spgemm.prepare") is None


def test_replay_roofline_counts_f_m_and_nnz_c_only(view):
    m = metric("replay_roofline")
    stats = {"f_m": 104_783_880, "nnz_c": 54_484_996}
    got = reader(m["reader"]).read(view, {"stats": stats, "peak": PEAK},
                                   **m["params"])
    least = (20 * stats["f_m"] + 4 * stats["nnz_c"]) / PEAK["hbm_bytes_per_s"]
    assert got == pytest.approx(100 * least / 0.0325)
    caps = {**stats, "fm_cap": 2**27, "nnz_cap": 2**26}  # never read
    assert reader(m["reader"]).read(view, {"stats": caps, "peak": PEAK},
                                    **m["params"]) == got
    assert set(m["params"]["bytes"]) | set(m["params"]["flops"]) == {
        "f_m", "nnz_c"}


def test_breakdown_names_ops_and_gaps_by_the_open_span(view):
    b = tracing.breakdown(view)
    assert b["device_ops"][0] == ["numeric_reuse:scatter",
                                  pytest.approx(0.030)]
    assert {k for k, _ in b["device_ops"]} == {
        "expand_and_sort:sort", "expand_and_sort:gather",
        "numeric_reuse:scatter"}
    gaps = dict(b["idle_gaps"])
    # [0, 5) under plan.build's parent prepare, [40, 60) under bench.call
    # then numeric.dispatch, [90, 100) under numeric.dispatch
    assert gaps["spgemm.prepare"] == pytest.approx(0.005)
    assert sum(gaps.values()) == pytest.approx(0.035)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_the_window_is_the_window_span_where_there_is_one():
    """With calls queued ahead the device works past the last call's
    sending: the window is the bench.window span around all of it."""
    calls = [(10, 12), (20, 22)]
    spans = [("bench.call", s, e, 0) for s, e in calls]
    assert tracing.window_of(spans, calls) == (10, 22)
    spans.append(("bench.window", 9, 40, 0))
    assert tracing.window_of(spans, calls) == (9, 40)
