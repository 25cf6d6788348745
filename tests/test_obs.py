"""repro.obs acceptance suite: tracing, histograms, flight recorder.

Two contracts anchor this file (ISSUE "acceptance criteria"):

  * tracing OFF — the pinned-replay hot path is *dispatch-identical* to the
    untraced build: zero added trace/hash counters, zero buffered events,
    zero recorder entries on success (test_tracing_off_is_dispatch_identical);
  * tracing ON — a chaos run through ``SparseService`` exports a valid
    Chrome trace whose spans carry request trace ids end-to-end with nonzero
    durations, and the injected kernel failure left
    a flight-recorder trail naming the kernel and its fallback hop
    (test_service_chaos_traced_end_to_end).

Everything else here pins the unit surfaces those two lean on.
"""
import json
import math

import jax.numpy as jnp
import pytest

from repro import obs
from repro.core import telemetry
from repro.core.executor import ReuseExecutor
from repro.core.plan_cache import PlanCache
from repro.core.spgemm import spgemm
from repro.obs.trace import _NOOP, SPAN_NAMES
from repro.runtime import faults
from repro.runtime.watchdog import Heartbeat
from repro.sparse import random_csr


@pytest.fixture
def ab():
    return random_csr(32, 24, 4.0, seed=1), random_csr(24, 40, 4.0, seed=2)


# --------------------------------------------------------------------------
# trace: mode resolution and the $REPRO_TRACE default
# --------------------------------------------------------------------------


def test_resolve_trace_mode_args_and_aliases():
    assert obs.resolve_trace_mode(True) == "on"
    assert obs.resolve_trace_mode(False) == "off"
    for m in obs.TRACE_MODES:
        assert obs.resolve_trace_mode(m) == m
    with pytest.raises(ValueError, match="unknown trace mode"):
        obs.resolve_trace_mode("verbose")


def test_trace_env_default(monkeypatch):
    monkeypatch.delenv(obs.TRACE_ENV_VAR, raising=False)
    assert obs.resolve_trace_mode(None) == "off"
    for raw, want in (("1", "on"), ("true", "on"), ("on", "on"),
                      ("0", "off"), ("false", "off"), ("xprof", "xprof")):
        monkeypatch.setenv(obs.TRACE_ENV_VAR, raw)
        assert obs.resolve_trace_mode(None) == want
    monkeypatch.setenv(obs.TRACE_ENV_VAR, "banana")
    with pytest.raises(ValueError, match="REPRO_TRACE"):
        obs.resolve_trace_mode(None)


def test_env_drives_enabled_lazily(monkeypatch):
    # set_tracing(None) re-defers to the env, resolved on next check
    monkeypatch.setenv(obs.TRACE_ENV_VAR, "on")
    obs.set_tracing(None)
    assert obs.enabled()
    monkeypatch.setenv(obs.TRACE_ENV_VAR, "off")
    obs.set_tracing(None)
    assert not obs.enabled()


# --------------------------------------------------------------------------
# trace: spans
# --------------------------------------------------------------------------


def test_disabled_span_is_the_shared_noop():
    assert not obs.enabled()  # conftest reset -> off
    assert obs.span("plan.build") is _NOOP
    assert obs.trace_context("req-1") is _NOOP
    assert obs.trace_scope(None) is _NOOP
    with obs.span("plan.build", fm_cap=8) as sp:
        sp.set("nnz_cap", 64)  # settable, still a no-op
    assert obs.events() == []


def test_span_records_nesting_and_attrs():
    obs.set_tracing("on")
    with obs.span("outer", method="sparse") as sp:
        sp.set("kernel", "xla")
        with obs.span("inner"):
            pass
    evs = obs.events()
    assert [e["name"] for e in evs] == ["inner", "outer"]  # close order
    inner, outer = evs
    assert inner["depth"] == 1 and outer["depth"] == 0
    assert outer["args"] == {"method": "sparse", "kernel": "xla"}
    assert outer["dur"] >= inner["dur"] >= 0.0


def test_span_records_exception_and_reraises():
    obs.set_tracing("on")
    with pytest.raises(RuntimeError):
        with obs.span("doomed"):
            raise RuntimeError("boom")
    (ev,) = obs.events()
    assert ev["args"]["error"] == "RuntimeError"


def test_span_feeds_phase_and_kernel_histograms():
    """A traced dispatch records its ``kernel`` attr on the span event and
    feeds no histogram: a span times the enqueue, not the device work."""
    obs.set_tracing("on")
    with obs.span("numeric.dispatch", kernel="pallas"):
        pass
    (ev,) = obs.events()
    assert ev["name"] == "numeric.dispatch"
    assert ev["args"]["kernel"] == "pallas"
    assert obs.default_registry().snapshot()["histograms"] == {}


def test_trace_scope_restores_ambient_mode():
    assert not obs.enabled()
    with obs.trace_scope("on"):
        assert obs.enabled()
        with obs.span("scoped"):
            pass
    assert not obs.enabled()
    assert [e["name"] for e in obs.events()] == ["scoped"]


def test_trace_context_stamps_and_restores_id():
    obs.set_tracing("on")
    assert obs.current_trace_id() is None
    with obs.trace_context("req-7"):
        assert obs.current_trace_id() == "req-7"
        with obs.span("inside"):
            pass
    assert obs.current_trace_id() is None
    with obs.span("outside"):
        pass
    inside, outside = obs.events()
    assert inside["args"]["trace_id"] == "req-7"
    assert "trace_id" not in outside["args"]


def test_export_chrome_trace_file(tmp_path):
    obs.set_tracing("on")
    with obs.trace_context(obs.new_trace_id("req")):
        with obs.span("plan.build", structure_key="k1"):
            pass
    path = tmp_path / "trace.json"
    payload = obs.export_chrome_trace(str(path))
    loaded = json.loads(path.read_text())
    assert loaded == json.loads(json.dumps(payload))
    (ev,) = loaded["traceEvents"]
    assert ev["ph"] == "X" and ev["cat"] == "repro"
    assert ev["name"] == "plan.build"
    assert isinstance(ev["ts"], (int, float)) and ev["dur"] >= 0
    assert ev["args"]["structure_key"] == "k1"
    assert ev["args"]["trace_id"] == "req-1"
    assert loaded["otherData"]["dropped_events"] == 0


def test_spgemm_trace_kwarg(ab):
    a, b = ab
    cache = PlanCache()  # fresh: the traced call must pay the plan build
    traced = spgemm(a, b, method="sparse", plan_cache=cache, trace=True)
    names = {e["name"] for e in obs.events()}
    # the three single-device phases fired, and every recorded span name
    # comes from the exported taxonomy (no free-typed strings)
    assert {"spgemm.prepare", "plan.build", "numeric.dispatch"} <= names
    assert names <= SPAN_NAMES, names - SPAN_NAMES
    assert not obs.enabled()  # trace=True scoped to the one call
    n_events = len(obs.events())
    res = spgemm(a, b, method="sparse", plan_cache=cache)  # ambient: off
    assert len(obs.events()) == n_events  # added no events
    assert bool(jnp.all(traced.c.values == res.c.values))


# --------------------------------------------------------------------------
# metrics: histograms, gauges, exporters
# --------------------------------------------------------------------------


def test_histogram_percentiles():
    h = obs.Histogram("t")
    assert math.isnan(h.percentile(50.0))
    h.observe(0.004)
    assert h.percentile(50.0) == pytest.approx(0.004)  # single obs: exact
    for _ in range(99):
        h.observe(0.001)
    s = h.summary()
    assert s["count"] == 100
    assert s["p50"] == pytest.approx(0.001, rel=0.5)  # in the 1ms bucket
    assert s["p99"] <= 0.004 and s["p99"] > s["p50"]
    assert s["min"] == 0.001 and s["max"] == 0.004
    assert s["mean"] == pytest.approx(h.sum / 100)


def test_gauge_live_callback():
    reg = obs.MetricsRegistry("t")
    box = {"v": 1.0}
    reg.gauge("box", fn=lambda: box["v"])
    assert reg.snapshot()["gauges"]["box"] == 1.0
    box["v"] = 5.0
    assert reg.snapshot()["gauges"]["box"] == 5.0  # read at export time
    reg.set_gauge("box", 2.0)  # set() unbinds the callback
    box["v"] = 9.0
    assert reg.snapshot()["gauges"]["box"] == 2.0


def test_exporters_unify_counters_histograms_gauges():
    reg = obs.MetricsRegistry("t")
    reg.observe("serve.step", 0.25)
    reg.set_gauge("queue_depth", 3)
    telemetry.DISPATCH_COUNTS["apply"] += 2  # counters come from telemetry
    lines = [json.loads(l) for l in reg.to_jsonl().splitlines()]
    kinds = {l["type"] for l in lines}
    assert kinds == {"counter", "histogram", "gauge"}
    assert {"group": "dispatch", "key": "apply", "value": 2}.items() <= next(
        l for l in lines if l["type"] == "counter").items()
    prom = reg.to_prometheus()
    assert 'repro_dispatch_total{key="apply"} 2' in prom
    assert 'repro_serve_step_seconds{quantile="0.5"}' in prom
    assert "repro_serve_step_seconds_count 1" in prom
    assert "repro_queue_depth 3" in prom


# --------------------------------------------------------------------------
# recorder: ring bounding and the auto-dump hook
# --------------------------------------------------------------------------


def test_flight_recorder_ring_bounds_and_dump():
    rec = obs.FlightRecorder(capacity=4)
    for i in range(10):
        rec.record("dispatch", kernel="pallas", seqno=i)
    assert len(rec) == 4
    assert [e["seqno"] for e in rec.events()] == [6, 7, 8, 9]  # oldest gone
    d = rec.dump(reason="test")
    assert d["recorded"] == 10 and d["capacity"] == 4
    assert len(d["events"]) == 4


def test_recorder_note_error_auto_dumps(capsys):
    rec = obs.FlightRecorder(capacity=8)
    rec.record("dispatch", kernel="pallas", verdict="ok")
    dump = rec.note_error(RuntimeError("kernel died"), kernel="pallas",
                          site="executor")
    assert rec.last_dump is dump
    assert "RuntimeError" in dump["reason"]
    last = dump["events"][-1]
    assert last["event"] == "error" and last["kernel"] == "pallas"
    assert "FLIGHT-RECORDER" in capsys.readouterr().err


# --------------------------------------------------------------------------
# telemetry.diff (satellite: the snapshot-diff helper)
# --------------------------------------------------------------------------


def test_telemetry_diff_semantics():
    before = telemetry.snapshot()
    assert telemetry.diff(before, telemetry.snapshot()) == {}
    telemetry.DISPATCH_COUNTS["apply"] += 3
    telemetry.HASH_COUNTS["structure_key"] += 1
    delta = telemetry.diff(before, telemetry.snapshot())
    assert delta == {"dispatch": {"apply": 3}, "hash": {"structure_key": 1}}
    telemetry.reset_all()  # vanished keys surface as negative deltas
    assert telemetry.diff(delta and telemetry.snapshot() or before,
                          telemetry.snapshot()) == {}
    after_reset = telemetry.diff(
        {"dispatch": {"apply": 3}}, telemetry.snapshot())
    assert after_reset["dispatch"]["apply"] == -3


# --------------------------------------------------------------------------
# heartbeat gauge (satellite: live write_errors visibility)
# --------------------------------------------------------------------------


def test_heartbeat_write_errors_is_a_live_gauge(tmp_path):
    hb = Heartbeat(str(tmp_path / "beat.json"), interval_s=60.0)
    hb.start()
    try:
        reg = obs.default_registry()
        assert reg.snapshot()["gauges"]["heartbeat.write_errors"] == 0
        hb.write_errors = 2  # simulate failed liveness writes
        assert reg.snapshot()["gauges"]["heartbeat.write_errors"] == 2
        assert "repro_heartbeat_write_errors 2" in reg.to_prometheus()
    finally:
        hb.stop()


# --------------------------------------------------------------------------
# the OFF contract: dispatch-identical hot path
# --------------------------------------------------------------------------


def test_tracing_off_is_dispatch_identical(ab):
    a, b = ab
    ex = ReuseExecutor.from_matrices(a, b)
    ex.apply(a.values, b.values)  # warm
    before = telemetry.snapshot()
    for _ in range(10):
        ex.apply(a.values, b.values)
    delta = telemetry.diff(before, telemetry.snapshot())
    # replay adds dispatches and NOTHING else: no traces, no hashes
    assert delta == {"dispatch": {"apply": 10}}
    assert obs.events() == []                      # no spans buffered
    assert len(obs.default_recorder()) == 0        # no ring entries
    assert obs.default_registry().snapshot()["histograms"] == {}


# --------------------------------------------------------------------------
# the ON contract: traced chaos run through the serving tier
# --------------------------------------------------------------------------


def test_service_chaos_traced_end_to_end(tmp_path):
    """The ISSUE's acceptance run: SparseService under an injected kernel
    failure with tracing on. The exported Chrome trace must carry request
    trace ids end-to-end, each phase's spans must have real durations, and
    the flight recorder must name the failing kernel and its fallback hop."""
    from repro.serve import SparseService

    structures = [
        (random_csr(32, 24, 4.0, seed=1), random_csr(24, 40, 4.0, seed=2)),
        (random_csr(16, 24, 3.0, seed=7), random_csr(24, 8, 3.0, seed=8)),
    ]
    refs, fast_refs = [], []
    for a, b in structures:
        res = spgemm(a, b, method="sparse")
        refs.append(res.c.to_dense())
        # a healthy fast-path response is bitwise the kernel's own replay
        # (its f32 window sums round differently from XLA's scatter)
        fast = ReuseExecutor(res.plan, backend="pallas",
                             on_kernel_failure="raise")
        fast_refs.append(fast.to_csr(fast.apply(a.values, b.values)).to_dense())
    obs.set_tracing("on")
    svc = SparseService(backend="pallas", max_batch=2, breaker_threshold=3,
                        retries=1, sleep=lambda _: None)

    resps = []
    with faults.failpoint("kernel:pallas"):  # the injected kernel failure
        resps.append(svc.submit(*structures[0]))
        svc.drain()
    for i in range(1, 4):  # recovery traffic
        resps.append(svc.submit(*structures[i % 2]))
    svc.drain()
    for i, r in enumerate(resps):
        want = (fast_refs if r.backend == "pallas" and not r.degraded
                else refs)[i % 2]
        assert r.ok and bool(jnp.all(r.value.to_dense() == want))

    # -- every request got a trace id, and it reached the nested spans -----
    assert [r.trace_id for r in resps] == ["req-0", "req-1", "req-2", "req-3"]
    payload = obs.export_chrome_trace(str(tmp_path / "chaos_trace.json"))
    loaded = json.loads((tmp_path / "chaos_trace.json").read_text())
    assert loaded["traceEvents"] == payload["traceEvents"]  # valid JSON file
    by_tid = {}
    for ev in payload["traceEvents"]:
        assert ev["ph"] == "X" and ev["dur"] >= 0
        by_tid.setdefault(ev["args"].get("trace_id"), set()).add(ev["name"])
    for tid in ("req-0", "req-1", "req-2", "req-3"):
        # admission and the executor dispatch both carry the request's id:
        # end-to-end propagation, not just a stamp at the door
        assert "serve.admit" in by_tid[tid], tid
        assert "numeric.dispatch" in by_tid[tid], tid
    all_names = set().union(*by_tid.values())
    assert "plan.build" in all_names
    assert all_names <= SPAN_NAMES, all_names - SPAN_NAMES  # taxonomy-closed

    # -- each phase's recorded spans carry real, nonzero durations --------
    for phase in ("plan.build", "numeric.dispatch"):
        durs = [ev["dur"] for ev in payload["traceEvents"]
                if ev["name"] == phase]
        assert durs, phase
        assert min(durs) > 0.0, phase
    assert obs.default_registry().snapshot()["histograms"] == {}

    # -- the flight recorder caught the kernel failure and the hop ---------
    ring = obs.default_recorder().events()
    hops = [e for e in ring if e.get("fallback")]
    assert hops and hops[0]["kernel"] == "pallas"
    assert hops[0]["fallback"] == "pallas->xla"
    assert any(e.get("trace_id") == "req-0" for e in ring)

    # -- stats(debug=True) exposes the dump + metrics on demand ------------
    dbg = svc.stats(debug=True)
    assert dbg["flight_recorder"]["events"] == ring
    assert dbg["metrics"]["histograms"]["serve.request"]["count"] == 4
    assert "flight_recorder" not in svc.stats()


def test_stats_debug_off_by_default(ab):
    from repro.serve import SparseService

    a, b = ab
    svc = SparseService(sleep=lambda _: None)
    svc.submit(a, b)
    svc.drain()
    out = svc.stats()
    assert "flight_recorder" not in out and "metrics" not in out
    assert out["request_latency"]["count"] == 1
    assert "est_step_s" in out


# --------------------------------------------------------------------------
# names inside the programs: device scopes and the structure-hash span
# --------------------------------------------------------------------------

PLAN_SCOPES = ("spgemm_expand", "spgemm_sort", "spgemm_permute")
REPLAY_SCOPES = ("replay_gather", "replay_scatter")


def _op_names(compiled_text: str, opcode: str, rows: int | None = None) -> list:
    """The ``op_name`` metadata of every ``opcode`` instruction in an
    optimized HLO text; with ``rows``, of those whose result has that
    leading dimension."""
    import re

    call = re.compile(r"[\]})] %s\(" % opcode)
    name = re.compile(r'op_name="([^"]*)"')
    lead = re.compile(r"= \w+\[%s\b" % (r"\d+" if rows is None else rows))
    return [name.search(line).group(1) for line in compiled_text.splitlines()
            if call.search(line) and name.search(line)
            and (rows is None or lead.search(line))]


@pytest.mark.parametrize("scope", PLAN_SCOPES)
def test_expand_and_sort_lowers_with_its_phase_scopes(ab, scope):
    from repro.core.spgemm import expand_and_sort

    a, b = ab
    text = expand_and_sort.lower(a, b, fm_cap=256).as_text(debug_info=True)
    assert f"jit(expand_and_sort)/{scope}/" in text


@pytest.mark.parametrize("scope", REPLAY_SCOPES)
def test_numeric_reuse_lowers_with_its_phase_scopes(ab, scope):
    from repro.core.spgemm import numeric_reuse

    a, b = ab
    plan = spgemm(a, b, method="sparse", plan_cache=False).plan
    text = numeric_reuse.lower(plan, a.values, b.values).as_text(
        debug_info=True)
    assert f"jit(numeric_reuse)/{scope}/" in text


@pytest.mark.parametrize("k", [40, 2**30])  # one packed key; three keys
def test_each_heavy_op_lies_in_its_phase_scope(ab, k):
    """The ops the benchmark's scope metrics time: the expansion's scatters
    and its one gather over the products under ``spgemm_expand``, with no
    loop anywhere in the plan build's front half, the sort under
    ``spgemm_sort``, the replay's gathers and scatter under theirs
    (optimized HLO on the CPU)."""
    from repro.core.spgemm import expand_and_sort, numeric_reuse
    from repro.sparse.formats import CSR

    a, b = ab
    wide = CSR(b.indptr, b.indices, b.values, shape=(b.m, k))
    fm_cap = 256
    text = expand_and_sort.lower(a, wide, fm_cap=fm_cap).compile().as_text()
    assert not _op_names(text, "while")
    spread = _op_names(text, "scatter", rows=fm_cap)
    assert spread and all("/spgemm_expand/" in n for n in spread)
    over_products = [n for n in _op_names(text, "gather", rows=fm_cap)
                     if "/spgemm_expand/" in n]
    assert len(over_products) == 1
    sorts = _op_names(text, "sort")
    assert sorts and all("/spgemm_sort/" in n for n in sorts)
    plan = spgemm(a, b, method="sparse", plan_cache=False).plan
    text = numeric_reuse.lower(plan, a.values, b.values).compile().as_text()
    gathers = _op_names(text, "gather")
    assert len(gathers) == 2
    assert all("/replay_gather/" in n for n in gathers)
    scatters = _op_names(text, "scatter")
    assert scatters and all("/replay_scatter/" in n for n in scatters)


def test_structure_hash_records_plan_key_inside_the_call(ab):
    """``spgemm(trace="on")`` hashes the structure under ``plan.key``:
    after the operands are prepared and before the plan is built, with the
    hash counted once."""
    from repro.core.plan_cache import HASH_COUNTS

    a, b = ab
    before = HASH_COUNTS["structure_key"]
    spgemm(a, b, method="sparse", plan_cache=False, trace="on")
    assert HASH_COUNTS["structure_key"] == before + 1
    evs = obs.events()
    names = [e["name"] for e in evs]
    assert names.count("plan.key") == 1
    key = evs[names.index("plan.key")]
    prep = evs[names.index("spgemm.prepare")]
    build = evs[names.index("plan.build")]
    assert prep["ts"] + prep["dur"] <= key["ts"]
    assert key["ts"] + key["dur"] <= build["ts"]
    assert key["dur"] > 0.0
    assert key["depth"] == prep["depth"] == build["depth"]
    assert not obs.enabled()  # trace="on" was scoped to the call
