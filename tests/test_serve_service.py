"""Serving-tier contract tests: bounded admission with typed backpressure,
deadline shedding at both ends, grouped single-dispatch batching, the
circuit breaker's full state walk, traffic-log warming, and the retry /
eviction / breaker telemetry satellites."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import telemetry
from repro.core.executor import DISPATCH_COUNTS, ReuseExecutor
from repro.core.plan_cache import EVICT_COUNTS, PlanCache
from repro.core.spgemm import spgemm
from repro.runtime import faults
from repro.runtime.retry import retry_call
from repro.runtime.validate import (AdmissionRejected, DeadlineExceeded,
                                    SpgemmError, SpgemmInputError)
from repro.serve import (CircuitBreaker, SparseService, TrafficLog,
                         warm_plan_cache)
from repro.serve.breaker import CLOSED, HALF_OPEN, OPEN
from repro.sparse import random_csr


class FakeClock:
    def __init__(self, now=0.0):
        self.now = now

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


@pytest.fixture
def ab():
    return random_csr(32, 24, 4.0, seed=1), random_csr(24, 40, 4.0, seed=2)


def oracle_dense(a, b):
    return spgemm(a, b, method="sparse").c.to_dense()


def kernel_dense(a, b):
    """The healthy segsum kernel's own replay of ``a @ b``: its f32 window
    sums round differently from XLA's scatter, so a fast-path response is
    held bitwise to this, and every other response to ``oracle_dense``."""
    res = spgemm(a, b, method="sparse")
    ex = ReuseExecutor(res.plan, backend="pallas", on_kernel_failure="raise")
    return ex.to_csr(ex.apply(a.values, b.values)).to_dense()


# --------------------------------------------------------------------------
# Admission: backpressure, validation at the door, deadline feasibility
# --------------------------------------------------------------------------


def test_queue_full_rejects_typed(ab):
    a, b = ab
    svc = SparseService(max_queue=2)
    r1, r2 = svc.submit(a, b), svc.submit(a, b)
    r3 = svc.submit(a, b)
    assert not r1.done and not r2.done
    assert r3.done and isinstance(r3.error, AdmissionRejected)
    assert isinstance(r3.error, SpgemmError)  # taxonomy, catchable as such
    assert svc.counters["shed_queue_full"] == 1
    assert svc.queue_depth == 2  # the rejected request never queued


def test_corrupt_operand_rejected_at_door(ab):
    a, b = ab
    bad = faults.inject_csr("nan_values", a)
    svc = SparseService()  # validate="host" is the serving default
    r = svc.submit(bad, b)
    assert r.done and isinstance(r.error, SpgemmInputError)
    assert svc.counters["rejected_validation"] == 1
    assert svc.queue_depth == 0
    # a healthy request right after is unaffected
    assert not svc.submit(a, b).done


def test_validate_off_admits_anything(ab):
    a, b = ab
    bad = faults.inject_csr("nan_values", a)
    svc = SparseService(validate="off")
    assert not svc.submit(bad, b).done  # caller's risk, admitted


def test_infeasible_deadline_shed_at_admission(ab):
    a, b = ab
    clk = FakeClock()
    svc = SparseService(clock=clk)
    svc.step_hint_s = 1.0  # as if measured: one tick costs 1s
    r = svc.submit(a, b, deadline_s=0.5)
    assert r.done and isinstance(r.error, AdmissionRejected)
    assert "infeasible" in str(r.error)
    assert svc.counters["shed_deadline_infeasible"] == 1
    # a feasible deadline is admitted under the same estimate
    assert not svc.submit(a, b, deadline_s=5.0).done


def test_idle_service_admits_any_deadline(ab):
    a, b = ab
    svc = SparseService(clock=FakeClock())
    # no step has run -> no latency estimate -> optimistic admission
    assert not svc.submit(a, b, deadline_s=1e-9).done


def test_expired_deadline_shed_in_queue(ab):
    a, b = ab
    clk = FakeClock()
    svc = SparseService(clock=clk)
    r_dead = svc.submit(a, b, deadline_s=1.0)
    r_live = svc.submit(a, b)  # no deadline
    clk.advance(2.0)
    resolved = svc.step()
    assert resolved == 2
    assert isinstance(r_dead.error, DeadlineExceeded)
    assert isinstance(r_dead.error, TimeoutError)  # stdlib-catchable
    assert r_live.ok
    assert svc.counters["shed_deadline_expired"] == 1
    assert svc.counters["completed"] == 1
    assert svc.counters["failed"] == 0  # a shed is not a failure
    assert svc.stats()["shed_rate"] == 0.5


# --------------------------------------------------------------------------
# Batch loop: grouping, dispatch counts, priorities, the empty tick
# --------------------------------------------------------------------------


def test_grouped_batch_one_dispatch_per_group(ab):
    a, b = ab
    a2, b2 = random_csr(16, 24, 3.0, seed=7), random_csr(24, 8, 3.0, seed=8)
    svc = SparseService(max_batch=8)
    same = [svc.submit(a, b) for _ in range(3)]
    other = svc.submit(a2, b2)
    DISPATCH_COUNTS.clear()
    svc.step()
    # 3 same-structure requests -> ONE batched dispatch; the odd one out
    # dispatches alone
    assert DISPATCH_COUNTS["apply_batched"] == 1
    assert DISPATCH_COUNTS["apply"] == 1
    ref, ref2 = oracle_dense(a, b), oracle_dense(a2, b2)
    for r in same:
        assert r.ok and r.group_size == 3
        assert bool(jnp.all(r.value.to_dense() == ref))  # bitwise
    assert other.ok and other.group_size == 1
    assert bool(jnp.all(other.value.to_dense() == ref2))


def test_max_batch_spills_to_next_step(ab):
    a, b = ab
    svc = SparseService(max_batch=2)
    rs = [svc.submit(a, b) for _ in range(5)]
    assert svc.step() == 2 and svc.queue_depth == 3
    assert svc.drain() == 3
    assert all(r.ok for r in rs)
    assert svc.counters["steps"] == 3


def test_priority_order_under_scarce_batch(ab):
    a, b = ab
    svc = SparseService(max_batch=1)
    r_low = svc.submit(a, b, priority=0)
    r_high = svc.submit(a, b, priority=5)
    svc.step()
    assert r_high.done and not r_low.done  # higher priority jumped the line
    svc.step()
    assert r_low.done


def test_empty_step_is_a_noop():
    svc = SparseService()
    DISPATCH_COUNTS.clear()
    assert svc.step() == 0
    assert DISPATCH_COUNTS["apply"] == 0
    assert DISPATCH_COUNTS["apply_batched"] == 0


def test_plan_cache_eviction_mid_stream_is_invisible(ab):
    a, b = ab
    svc = SparseService()
    r1 = svc.submit(a, b)
    svc.step()
    svc.plan_cache.clear()  # forced eviction between steps
    r2 = svc.submit(a, b)
    svc.step()
    ref = oracle_dense(a, b)
    assert r1.ok and r2.ok
    assert bool(jnp.all(r2.value.to_dense() == ref))


# --------------------------------------------------------------------------
# Circuit breaker: unit walk + integrated routing
# --------------------------------------------------------------------------


def test_breaker_state_walk_with_fake_clock():
    clk = FakeClock()
    br = CircuitBreaker("k", failure_threshold=2, window_s=10.0,
                        cooldown_s=5.0, clock=clk)
    assert br.allow() and br.state == CLOSED
    br.record_failure()
    assert br.state == CLOSED  # below threshold
    br.record_failure()
    assert br.state == OPEN
    assert telemetry.BREAKER_COUNTS["k:open"] == 1
    assert not br.allow()  # short-circuit during cooldown
    assert telemetry.BREAKER_COUNTS["k:short_circuit"] == 1
    clk.advance(5.0)
    assert br.allow() and br.state == HALF_OPEN  # the probe
    assert telemetry.BREAKER_COUNTS["k:half_open"] == 1
    assert not br.allow()  # only ONE probe at a time
    br.record_failure()  # probe verdict: still broken
    assert br.state == OPEN
    assert telemetry.BREAKER_COUNTS["k:reopen"] == 1
    clk.advance(5.0)
    assert br.allow()  # second probe
    br.record_success()
    assert br.state == CLOSED
    assert telemetry.BREAKER_COUNTS["k:close"] == 1
    assert br.snapshot()["recent_failures"] == 0


def test_breaker_window_forgets_stale_failures():
    clk = FakeClock()
    br = CircuitBreaker("k", failure_threshold=2, window_s=1.0, clock=clk)
    br.record_failure()
    clk.advance(2.0)  # first failure ages out of the window
    br.record_failure()
    assert br.state == CLOSED


def test_service_breaker_routes_around_broken_kernel(ab):
    a, b = ab
    clk = FakeClock()
    svc = SparseService(backend="pallas", max_batch=1, clock=clk,
                        breaker_threshold=2, breaker_cooldown_s=5.0)
    ref, fast_ref = oracle_dense(a, b), kernel_dense(a, b)

    def serve_one():
        r = svc.submit(a, b)
        svc.step()
        want = fast_ref if r.backend == "pallas" and not r.degraded else ref
        assert r.ok and bool(jnp.all(r.value.to_dense() == want))
        return r

    with faults.failpoint("kernel:pallas"):
        # two degraded dispatches trip the breaker (correct via the ladder)
        for _ in range(2):
            assert serve_one().degraded
        assert svc._breakers["pallas"].state == OPEN
        # open: traffic short-circuits straight to XLA — no ladder cost
        fallbacks0 = telemetry.FALLBACK_COUNTS["fault:pallas->xla"]
        r = serve_one()
        assert r.backend == "xla" and not r.degraded
        assert telemetry.FALLBACK_COUNTS["fault:pallas->xla"] == fallbacks0
        # cooldown elapses while the kernel is STILL broken: probe fails,
        # breaker reopens, later traffic short-circuits again
        clk.advance(5.0)
        assert serve_one().degraded  # the probe (correct, via ladder)
        assert svc._breakers["pallas"].state == OPEN
        assert telemetry.BREAKER_COUNTS["pallas:reopen"] == 1
    # kernel fixed + cooldown elapsed: probe succeeds, fast path re-admitted
    clk.advance(5.0)
    r = serve_one()
    assert r.backend == "pallas" and not r.degraded
    assert svc._breakers["pallas"].state == CLOSED
    assert telemetry.BREAKER_COUNTS["pallas:close"] == 1
    assert serve_one().backend == "pallas"
    assert svc.counters["degraded_dispatches"] == 3


def test_batched_groups_never_consult_breaker(ab):
    a, b = ab
    svc = SparseService(backend="pallas", max_batch=4)
    rs = [svc.submit(a, b) for _ in range(3)]
    with faults.failpoint("kernel:pallas"):
        svc.step()  # batched -> XLA vmap formulation, failpoint never hit
    assert all(r.ok and r.backend == "xla" for r in rs)
    assert svc._breakers["pallas"].state == CLOSED
    assert svc._breakers["pallas"].snapshot()["recent_failures"] == 0


# --------------------------------------------------------------------------
# Retry integration + telemetry satellites
# --------------------------------------------------------------------------


def test_retry_counts_tick_and_reset():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("transient")
        return "ok"

    assert retry_call(flaky, retries=3, label="t", sleep=lambda _: None) == "ok"
    assert telemetry.RETRY_COUNTS["t:attempt"] == 3
    assert telemetry.RETRY_COUNTS["t:retry"] == 2
    assert telemetry.RETRY_COUNTS["t:giveup"] == 0
    assert telemetry.ALL_COUNTERS["retry"] is telemetry.RETRY_COUNTS
    telemetry.reset_all()  # the conftest fixture's hygiene, asserted
    assert not telemetry.RETRY_COUNTS
    assert not telemetry.BREAKER_COUNTS
    assert not EVICT_COUNTS


def test_retry_label_defaults_to_fn_name():
    def transient_once():
        raise OSError("nope")

    with pytest.raises(Exception):
        retry_call(transient_once, retries=1, sleep=lambda _: None)
    assert telemetry.RETRY_COUNTS["transient_once:attempt"] == 2
    assert telemetry.RETRY_COUNTS["transient_once:giveup"] == 1


def test_service_dispatch_retries_transient_straggler(ab):
    # a kernel:xla failpoint that clears after the first hit models a
    # transient device hiccup: retry_call lands the second attempt
    a, b = ab
    svc = SparseService(max_batch=1, retries=2, sleep=lambda _: None)
    r = svc.submit(a, b)
    faults.arm("kernel:xla")
    orig_sleep = svc._sleep

    def disarm_then(dt):
        faults.disarm("kernel:xla")
        orig_sleep(dt)

    svc._sleep = disarm_then
    svc.step()
    assert r.ok
    assert telemetry.RETRY_COUNTS["serve.dispatch:retry"] == 1
    assert svc.stats()["retry"]["retries"] == 1


def test_service_dispatch_gives_up_typed(ab):
    a, b = ab
    svc = SparseService(max_batch=1, retries=1, sleep=lambda _: None)
    r = svc.submit(a, b)
    with faults.failpoint("kernel:xla"):
        svc.step()
    assert r.done and not r.ok
    assert isinstance(r.error, SpgemmError)  # typed, never a bare crash
    assert telemetry.RETRY_COUNTS["serve.dispatch:giveup"] == 1
    assert svc.counters["failed"] == 1


# --------------------------------------------------------------------------
# Warmer: traffic log, prefetch, eviction tolerance
# --------------------------------------------------------------------------


def test_traffic_log_counts_structures(ab):
    a, b = ab
    a2, b2 = random_csr(16, 24, 3.0, seed=7), random_csr(24, 8, 3.0, seed=8)
    log = TrafficLog()
    for _ in range(3):
        log.record(a, b)
    log.record(a2, b2)
    assert len(log) == 2
    top = log.top()
    assert top[0].count == 3 and top[1].count == 1
    assert log.top(1) == [top[0]]


def test_warm_plan_cache_prefetches(ab):
    a, b = ab
    log = TrafficLog()
    log.record(a, b)
    cache = PlanCache(capacity=8, name="warmtest")
    stats = warm_plan_cache(log, cache)
    assert stats == {"built": 1, "hits": 0, "failed": 0, "evictions": 0}
    # warming again is all hits; serving after warming never misses
    assert warm_plan_cache(log, cache)["hits"] == 1
    svc = SparseService(plan_cache=cache)
    misses0 = cache.stats()["misses"]  # the warm's own build was the miss
    r = svc.submit(a, b)
    svc.step()
    assert r.ok and cache.stats()["misses"] == misses0


def test_warm_detects_cache_thrash(ab):
    # a warm set bigger than the cache must finish AND report the churn
    mats = [(random_csr(8 + 4 * i, 16, 2.0, seed=10 + i),
             random_csr(16, 8, 2.0, seed=50 + i)) for i in range(4)]
    log = TrafficLog()
    for a, b in mats:
        log.record(a, b)
    cache = PlanCache(capacity=2, name="thrash")
    stats = warm_plan_cache(log, cache)
    assert stats["built"] == 4
    assert stats["evictions"] == 2  # 4 plans through a 2-entry LRU
    assert EVICT_COUNTS["thrash"] == 2


def test_service_warms_from_its_own_traffic(ab):
    a, b = ab
    svc = SparseService()
    r = svc.submit(a, b)
    svc.step()
    assert r.ok
    svc.plan_cache.clear()
    stats = svc.warm()  # rebuild from the log recorded at admission
    assert stats["built"] == 1
    # the warmed entry serves the next request as a pure hit
    misses0 = svc.plan_cache.stats()["misses"]
    svc.submit(a, b)
    svc.step()
    assert svc.plan_cache.stats()["misses"] == misses0


def test_admission_records_traffic_without_extra_hash(ab):
    from repro.core import telemetry

    a, b = ab
    svc = SparseService()
    svc.submit(a, b)
    before = telemetry.snapshot()
    svc.submit(a, b)  # second request: still exactly one hash each
    delta = telemetry.diff(before, telemetry.snapshot())
    assert delta.get("hash") == {"structure_key": 1}, delta
    assert svc.traffic_log.top()[0].count == 2


# --------------------------------------------------------------------------
# Config validation
# --------------------------------------------------------------------------


def test_bad_config_raises():
    with pytest.raises(ValueError, match="backend"):
        SparseService(backend="cuda")
    with pytest.raises(ValueError, match="max_queue"):
        SparseService(max_queue=0)
    with pytest.raises(ValueError, match="failure_threshold"):
        CircuitBreaker("k", failure_threshold=0)
