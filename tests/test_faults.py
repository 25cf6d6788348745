"""Chaos suite: every registered fault must either raise its typed error
(validation on) or degrade to a bitwise-correct XLA-reference result with
FALLBACK_COUNTS evidence (validation off). No fault may produce silent
wrong values — that is the acceptance bar of the failure model (ROADMAP
"The failure model")."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import telemetry
from repro.core.executor import ReuseExecutor
from repro.core.plan_cache import PlanCache
from repro.core.spgemm import numeric_reuse, spgemm
from repro.kernels.ops import numeric_values
from repro.runtime import faults
from repro.runtime.validate import (CapacityOverflowError, KernelFallbackError,
                                    PlanMismatchError, SpgemmInputError,
                                    check_csr)
from repro.sparse import csr_to_ell, random_csr


@pytest.fixture
def ab():
    return random_csr(32, 24, 4.0, seed=1), random_csr(24, 40, 4.0, seed=2)


# --------------------------------------------------------------------------
# Data faults: validation ON -> the registered typed error, both modes
# --------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["host", "device"])
@pytest.mark.parametrize("name", [s.name for s in faults.data_faults()])
def test_data_fault_raises_typed_error(ab, name, mode):
    a, _ = ab
    bad = faults.inject_csr(name, a)
    spec = faults.FAULTS[name]
    with pytest.raises(spec.expects):
        check_csr(bad, mode, name="A")


@pytest.mark.parametrize("name", ["corrupt_indptr", "capacity_overflow"])
def test_data_fault_caught_at_spgemm_entry(ab, name):
    # spgemm(validate=...) must catch the corruption before any dispatch
    a, b = ab
    bad = faults.inject_csr(name, a)
    with pytest.raises(faults.FAULTS[name].expects):
        spgemm(bad, b, method="sparse", validate="host")


def test_typed_errors_are_valueerrors(ab):
    # back-compat: pre-taxonomy call sites catch ValueError
    a, _ = ab
    bad = faults.inject_csr("capacity_overflow", a)
    with pytest.raises(ValueError):
        check_csr(bad, "host")
    assert issubclass(CapacityOverflowError, ValueError)
    assert issubclass(SpgemmInputError, ValueError)
    assert issubclass(PlanMismatchError, ValueError)


def test_fault_injection_is_deterministic(ab):
    a, _ = ab
    x = faults.inject_csr("oob_col_index", a, seed=7)
    y = faults.inject_csr("oob_col_index", a, seed=7)
    assert np.array_equal(np.asarray(x.indices), np.asarray(y.indices))


# --------------------------------------------------------------------------
# Kernel faults: validation OFF -> degradation ladder, bitwise-correct XLA
# --------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["pallas", "pallas_lp"])
def test_executor_kernel_fault_degrades_bitwise(ab, backend):
    a, b = ab
    ex = ReuseExecutor.from_matrices(a, b, backend=backend)
    oracle = numeric_reuse(ex.plan, a.values, b.values)
    with faults.failpoint(f"kernel:{backend}"):
        out = ex.apply(a.values, b.values)
    assert bool(jnp.all(out == oracle))  # bitwise: same XLA reference
    assert ex.kernel_source == "fallback"
    assert telemetry.FALLBACK_COUNTS[f"fault:{backend}->xla"] == 1


def test_executor_kernel_fault_strict_raises(ab):
    a, b = ab
    ex = ReuseExecutor.from_matrices(a, b, backend="pallas",
                                     on_kernel_failure="raise")
    with faults.failpoint("kernel:pallas"):
        with pytest.raises(KernelFallbackError) as ei:
            ex.apply(a.values, b.values)
    assert isinstance(ei.value.__cause__, faults.InjectedFault)
    assert ex.kernel_source == "static"  # no silent fallback happened


def test_executor_recovers_after_fault_clears(ab):
    a, b = ab
    ex = ReuseExecutor.from_matrices(a, b, backend="pallas")
    with faults.failpoint("kernel:pallas"):
        ex.apply(a.values, b.values)
    oracle = numeric_reuse(ex.plan, a.values, b.values)
    out = ex.apply(a.values, b.values)  # failpoint disarmed: pallas again
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=1e-6)
    assert telemetry.FALLBACK_COUNTS["fault:pallas->xla"] == 1  # no new bump


@pytest.mark.parametrize("kernel", ["dense_acc", "flat_lp"])
def test_numeric_values_ladder_bitwise(ab, kernel):
    a, b = ab
    res = spgemm(a, b, method="sparse")
    c_ell = csr_to_ell(res.c)
    ref = numeric_values(a, b, c_ell.indices, c_ell.row_nnz, kernel="xla")
    with faults.failpoint(f"kernel:{kernel}"):
        out = numeric_values(a, b, c_ell.indices, c_ell.row_nnz,
                             kernel=kernel)
    assert bool(jnp.all(out == ref))
    assert telemetry.FALLBACK_COUNTS[f"fault:{kernel}->xla"] == 1
    assert telemetry.KERNEL_COUNTS["xla"] >= 1


def test_numeric_values_auto_ladder_exhausts_to_xla(ab):
    # every Pallas rung armed: auto must still land on the exact reference
    a, b = ab
    res = spgemm(a, b, method="sparse")
    c_ell = csr_to_ell(res.c)
    ref = numeric_values(a, b, c_ell.indices, c_ell.row_nnz, kernel="xla")
    with faults.failpoint("kernel:dense_acc"), \
            faults.failpoint("kernel:flat_lp"):
        out = numeric_values(a, b, c_ell.indices, c_ell.row_nnz,
                             kernel="auto")
    assert bool(jnp.all(out == ref))
    assert sum(v for k, v in telemetry.FALLBACK_COUNTS.items()
               if k.startswith("fault:")) >= 1


def test_numeric_values_ladder_exhausted_raises(ab):
    a, b = ab
    res = spgemm(a, b, method="sparse")
    c_ell = csr_to_ell(res.c)
    with faults.failpoint("kernel:dense_acc"), \
            faults.failpoint("kernel:flat_lp"), \
            faults.failpoint("kernel:xla"):
        with pytest.raises(KernelFallbackError, match="exhausted"):
            numeric_values(a, b, c_ell.indices, c_ell.row_nnz, kernel="auto")


# --------------------------------------------------------------------------
# NaN guard
# --------------------------------------------------------------------------


def test_nan_guard_recovers_kernel_side_poison(ab):
    a, b = ab
    ex = ReuseExecutor.from_matrices(a, b, nan_guard=True)
    oracle = numeric_reuse(ex.plan, a.values, b.values)
    with faults.failpoint("executor:poison_output"):
        out = ex.apply(a.values, b.values)
    assert bool(jnp.all(jnp.isfinite(out)))
    assert bool(jnp.all(out == oracle))
    assert ex.nan_events == [("recovered", "xla")]
    assert telemetry.FALLBACK_COUNTS["nan_guard:rerun"] == 1
    assert telemetry.FALLBACK_COUNTS["nan_guard:recovered"] == 1


def test_nan_guard_flags_data_nan(ab):
    a, b = ab
    ex = ReuseExecutor.from_matrices(a, b, nan_guard=True)
    bad_vals = np.asarray(a.values).copy()
    bad_vals[0] = np.nan
    out = ex.apply(jnp.asarray(bad_vals), b.values)
    assert not bool(jnp.all(jnp.isfinite(out)))  # data NaN: flagged, not hidden
    assert ex.nan_events and ex.nan_events[0][0] == "data"
    assert telemetry.FALLBACK_COUNTS["nan_guard:data"] == 1


def test_nan_guard_zero_overhead_path_clean_output(ab):
    a, b = ab
    ex = ReuseExecutor.from_matrices(a, b, nan_guard=True)
    ex.apply(a.values, b.values)
    assert ex.nan_events == []
    assert telemetry.FALLBACK_COUNTS["nan_guard:rerun"] == 0


def test_nan_guard_rejects_donate(ab):
    a, b = ab
    ex = ReuseExecutor.from_matrices(a, b, nan_guard=True)
    with pytest.raises(ValueError, match="donate"):
        ex.apply(a.values, b.values, donate=True)


# --------------------------------------------------------------------------
# Plan mismatch + cache eviction mid-replay
# --------------------------------------------------------------------------


def test_plan_mismatch_at_replay(ab):
    a, b = ab
    ex = ReuseExecutor.from_matrices(a, b, validate="host")
    with pytest.raises(PlanMismatchError, match="slots"):
        ex.apply(a.values[: max(ex._guard.a_req - 1, 1)], b.values)


def test_check_compat_detects_different_structure(ab):
    a, b = ab
    ex = ReuseExecutor.from_matrices(a, b)
    ex.check_compat(a, b)  # same structure: fine
    a2 = random_csr(32, 24, 6.0, seed=9)  # different sparsity pattern
    with pytest.raises(PlanMismatchError):
        ex.check_compat(a2, b)


def test_check_compat_requires_pinned_key(ab):
    a, b = ab
    res = spgemm(a, b, method="sparse")
    ex = ReuseExecutor(res.plan)  # bare plan: no structure key retained
    with pytest.raises(PlanMismatchError, match="no pinned structure key"):
        ex.check_compat(a, b)


def test_plan_cache_eviction_mid_replay(ab):
    # simulated eviction: the cache clears between calls; spgemm must
    # transparently rebuild (a "miss", never wrong values), and a pinned
    # executor must keep replaying its own plan unaffected
    a, b = ab
    cache = PlanCache(capacity=4)
    r1 = spgemm(a, b, method="sparse", plan_cache=cache)
    ex = ReuseExecutor.from_matrices(a, b, plan_cache=cache)
    assert spgemm(a, b, method="sparse", plan_cache=cache).stats["cache"] == "hit"
    cache.clear()  # the registered plan_cache_eviction fault
    r2 = spgemm(a, b, method="sparse", plan_cache=cache)
    assert r2.stats["cache"] == "miss"
    assert bool(jnp.all(r2.c.values == r1.c.values))
    out = ex.apply(a.values, b.values)  # pinned plan: eviction-proof
    assert bool(jnp.all(out == r1.c.values))


# --------------------------------------------------------------------------
# Failpoint hygiene
# --------------------------------------------------------------------------


def test_failpoint_context_disarms_on_error():
    with pytest.raises(RuntimeError):
        with faults.failpoint("kernel:pallas"):
            raise RuntimeError("body blew up")
    assert not faults.armed("kernel:pallas")


def test_registry_covers_both_fault_kinds():
    kinds = {s.kind for s in faults.FAULTS.values()}
    assert kinds == {"data", "kernel", "cache"}
    for s in faults.data_faults():
        assert s.expects is not None  # every data fault names its error
    for s in faults.kernel_faults():
        assert s.site and s.site.startswith("kernel:")


# --------------------------------------------------------------------------
# Chaos under traffic: the serving tier's acceptance bar
# --------------------------------------------------------------------------


def test_service_chaos_under_traffic():
    """Live traffic through SparseService while everything misbehaves at
    once — kernel failpoints flapping, one corrupt request in the stream, a
    forced plan-cache eviction mid-stream. The bar is the failure model's:
    every COMPLETED response is bitwise-equal to the reference of the path
    that served it — the XLA reference for XLA, batched and degraded
    dispatches, the healthy kernel's own replay for fast-path dispatches
    (its f32 window sums round differently from XLA's scatter) — and every
    non-completion is a typed SpgemmError; nothing silent, nothing dropped.
    """
    from repro.serve import SparseService
    from repro.runtime.validate import SpgemmError, SpgemmInputError

    structures = [
        (random_csr(32, 24, 4.0, seed=1), random_csr(24, 40, 4.0, seed=2)),
        (random_csr(16, 24, 3.0, seed=7), random_csr(24, 8, 3.0, seed=8)),
        (random_csr(48, 16, 2.0, seed=9), random_csr(16, 48, 3.0, seed=10)),
    ]
    refs, fast_refs = [], []
    for a, b in structures:
        res = spgemm(a, b, method="sparse")
        refs.append(res.c.to_dense())
        fast = ReuseExecutor(res.plan, backend="pallas",
                             on_kernel_failure="raise")
        fast_refs.append(fast.to_csr(fast.apply(a.values, b.values)).to_dense())
    svc = SparseService(backend="pallas", max_batch=2, breaker_threshold=2,
                        retries=1, sleep=lambda _: None)
    ledger = []  # (response, reference | None for the corrupt one)

    def pump(i, corrupt=False):
        a, b = structures[i % len(structures)]
        if corrupt:
            a = faults.inject_csr("nan_values", a)
        ledger.append((svc.submit(a, b), None if corrupt else i % 3))

    for i in range(4):  # clean warm-up traffic
        pump(i)
    svc.drain()
    with faults.failpoint("kernel:pallas"):  # fast kernel starts flapping
        for i in range(4):
            pump(i)
        svc.drain()
        pump(0, corrupt=True)  # a hostile request inside the fault window
        svc.plan_cache.clear()  # and the cache evicts mid-stream
        for i in range(3):
            pump(i)
        svc.drain()
    for i in range(3):  # recovery traffic, failpoint cleared
        pump(i)
    svc.drain()

    assert len(ledger) == 15
    completed = rejected = 0
    for resp, which in ledger:
        assert resp.done  # nothing silently dropped
        if which is None:  # the corrupt request: typed rejection at the door
            assert isinstance(resp.error, SpgemmInputError)
            rejected += 1
        else:
            assert resp.ok, f"unexpected failure: {resp.error!r}"
            served_fast = resp.backend == "pallas" and not resp.degraded
            ref = (fast_refs if served_fast else refs)[which]
            assert bool(jnp.all(resp.value.to_dense() == ref))  # bitwise
            completed += 1
    assert completed == 14 and rejected == 1
    # the chaos left evidence, not wreckage: ladder fallbacks were counted,
    # and the flapping kernel tripped the breaker
    assert telemetry.FALLBACK_COUNTS["fault:pallas->xla"] >= 1
    assert telemetry.BREAKER_COUNTS["pallas:open"] >= 1
    stats = svc.stats()
    assert stats["rejected_validation"] == 1
    assert stats["completed"] == 14
    assert stats["failed"] == 0
