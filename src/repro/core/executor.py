"""High-throughput reuse engine: pinned plans, batched replay, grouping.

The paper's Reuse case pays for the two-phase split only if the numeric
replay is cheap to *dispatch*, not just cheap to compute: Nagasaka et al.
(arXiv:1804.01698) show the numeric phase is bandwidth-bound, so per-call
host overheads (structure hashing, cache probes, one XLA dispatch per
multiply) dominate exactly the workloads the paper targets — multigrid
setup, graph analytics with changing weights, now at serving rates.

``ReuseExecutor`` closes that gap in three steps:

  * **pin**: the plan is hashed and resolved once at construction (one
    ``structure_key`` call, ever — ``plan_cache.HASH_COUNTS`` proves it);
  * **replay**: ``apply(a_values, b_values)`` is a single jitted dispatch of
    the precomposed v2 plan (two gathers + one sorted segment-sum), with an
    optional donating variant for serving loops that discard their inputs;
  * **batch**: ``apply_batched`` maps the replay over stacked value arrays
    ``(batch, nnz_cap)`` — same structure, new values, ONE XLA dispatch for
    the whole batch instead of ``batch`` round-trips through the runtime.

``spgemm_grouped`` extends this to mixed batches: multiplies are grouped by
``plan_cache.structure_key`` (one hash per multiply, the unavoidable
minimum — input prep and plan resolution share ``spgemm()``'s code path)
and each structure group becomes one batched dispatch.

Backends: ``backend="xla"`` (the default that ``"auto"`` resolves to)
replays through ``numeric_reuse``; ``backend="pallas"`` opts into the
``kernels/segsum_reuse`` flat-parallel TPU kernel (``interpret=True``
off-TPU); ``backend="pallas_lp"`` opts into the ``kernels/spgemm_lp``
LP-hash accumulator replay — the KKLP position, for measuring the paper's
accumulator trade-off on the replay hot loop. The Pallas kernels are
explicit opt-in — not what ``"auto"`` picks — until they have real-TPU
compile coverage (CI only exercises interpret mode), and they accumulate in
f32, so f64/int operands route back to XLA. Batched replay always uses the
XLA path — one dispatch for the whole batch is the point of batching.
"""
from __future__ import annotations

import time
from collections import Counter, OrderedDict
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.meta import DEFAULT_PAD_POLICY, f32_accumulation_ok
from repro.core.plan_cache import default_plan_cache, structure_key
from repro.obs import trace as obs_trace
from repro.core.spgemm import (
    SpgemmPlan,
    _note_trace,
    lp_replay_values,
    numeric_reuse,
    prepare_sparse_inputs,
    resolve_plan,
    spgemm,
)
from repro.runtime import faults
from repro.runtime.validate import (
    KernelFallbackError,
    PlanGuard,
    SpgemmConfigError,
    SpgemmError,
    check_plan_compat,
    resolve_mode,
)
from repro.runtime.watchdog import StragglerDetected
from repro.sparse.formats import CSR

BACKENDS = ("auto", "xla", "pallas", "pallas_lp")

# Dispatch telemetry: counts *calls* (not traces — that's TRACE_COUNTS), so
# tests can assert grouping really issues one batched dispatch per structure.
DISPATCH_COUNTS: Counter = Counter()


def reset_dispatch_counts() -> None:
    DISPATCH_COUNTS.clear()


def _resolve_backend(backend: str) -> str:
    if backend not in BACKENDS:
        raise SpgemmConfigError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    # "auto" stays on XLA even on TPU: the Pallas kernel is explicit opt-in
    # until it has real-TPU compile coverage (tests only run interpret mode).
    return "xla" if backend == "auto" else backend


def _replay(plan: SpgemmPlan, a_values, b_values, backend: str, interpret: bool):
    if backend == "pallas_lp":
        # shared LP dispatch: Pallas kernel or the exact-XLA dtype fallback
        return lp_replay_values(plan, a_values, b_values, interpret)[0]
    if backend == "pallas" and f32_accumulation_ok(a_values.dtype,
                                                   b_values.dtype):
        from repro.kernels.segsum_reuse import segsum_reuse  # lazy: kernels dep

        return segsum_reuse(plan, a_values, b_values, interpret=interpret)
    # XLA path — also the fallback for f64 (the Pallas kernels accumulate in
    # f32, which would halve double precision) and for integer dtypes (f32
    # rounding above 2^24 would break integer exactness).
    return numeric_reuse(plan, a_values, b_values)


def _apply_impl(plan, a_values, b_values, backend, interpret):
    _note_trace("executor_apply")
    return _replay(plan, a_values, b_values, backend, interpret)


_apply = jax.jit(_apply_impl, static_argnames=("backend", "interpret"))
# serving-loop variants: per-operand buffer donation, so a loop with one
# fixed operand (multigrid's P) can donate only the per-step values
_apply_donated = {
    (True, True): jax.jit(_apply_impl, static_argnames=("backend", "interpret"),
                          donate_argnums=(1, 2)),
    (True, False): jax.jit(_apply_impl, static_argnames=("backend", "interpret"),
                           donate_argnums=(1,)),
    (False, True): jax.jit(_apply_impl, static_argnames=("backend", "interpret"),
                           donate_argnums=(2,)),
}


def _pallas_replay_fits(plan, a_values, b_values) -> bool:
    """Do the Pallas replay kernels' VMEM bounds (``kernels.limits``) admit
    this plan and these value buffers?"""
    from repro.kernels.limits import replay_misfit  # lazy: kernels dep
    from repro.kernels.segsum_reuse import VAL_TILE

    def pad(n: int) -> int:
        return -(-n // VAL_TILE) * VAL_TILE

    return replay_misfit(pad(a_values.shape[-1]), pad(b_values.shape[-1]),
                         plan.indices.shape[0]) is None


def fitting_backend(backend: str, plan, a_values, b_values) -> str:
    """A selected replay backend, or "xla" when it is a Pallas kernel whose
    size bound the plan exceeds (a measured winner is recorded per size
    bucket, so it may come from a smaller problem)."""
    if backend in ("pallas", "pallas_lp") and not _pallas_replay_fits(
            plan, a_values, b_values):
        return "xla"
    return backend


def replay_candidates(plan, a_values, b_values, interpret: bool) -> dict:
    """The eligible replay backends for these operands, as autotuner thunks.

    This *is* the PR 5 selection table in measurable form: XLA is always
    eligible; the f32-accumulating Pallas kernels (segsum + LP-hash) join
    only when ``f32_accumulation_ok`` admits the operand dtypes and the
    plan fits their size bounds — measure mode must never time (let alone
    pick) a kernel the dispatch would refuse.
    """
    cands = {"xla": lambda: _apply(plan, a_values, b_values,
                                   backend="xla", interpret=interpret)}
    if f32_accumulation_ok(a_values.dtype, b_values.dtype) and \
            _pallas_replay_fits(plan, a_values, b_values):
        for name in ("pallas", "pallas_lp"):
            cands[name] = (lambda nm=name: _apply(
                plan, a_values, b_values, backend=nm, interpret=interpret))
    return cands


def map_batch(replay, a_values, b_values, a_axis, b_axis):
    """``replay(av, bv)`` for each element of the stacked operand(s), as one
    ``lax.map`` loop inside the caller's dispatch.

    ``a_axis``/``b_axis``: 0 for a stacked ``(batch, n)`` operand, None for
    one shared by every element. A loop rather than ``vmap``: vmapping the
    gathers and the scatter makes XLA hold every element's f_m products at
    once, and on a TPU it lays the (f_m, batch) temporary out with the batch
    as the 128-lane minor dimension, so the replay of a 2^26-product plan at
    batch 4 needed 32 GiB and did not compile for a 16 GB v5e. Each element
    is exactly the single replay, so results equal per-call replays bitwise.
    """
    if a_axis is None:
        return jax.lax.map(lambda bv: replay(a_values, bv), b_values)
    if b_axis is None:
        return jax.lax.map(lambda av: replay(av, b_values), a_values)
    return jax.lax.map(lambda ab: replay(*ab), (a_values, b_values))


@partial(jax.jit, static_argnames=("a_axis", "b_axis"))
def _apply_batched(plan, a_values, b_values, a_axis, b_axis):
    _note_trace("executor_apply_batched")
    return map_batch(lambda av, bv: numeric_reuse(plan, av, bv),
                     a_values, b_values, a_axis, b_axis)


class ReuseExecutor:
    """A pinned ``SpgemmPlan`` exposed as a replay engine.

    Construction is the only host-side work: from then on every ``apply`` /
    ``apply_batched`` is a pure jitted dispatch — zero structure hashing,
    zero cache probes, zero retraces (for fixed operand shapes/dtypes).

    ``tune="measure"`` defers the backend choice to first ``apply``: the
    autotuner's bucket table is consulted (a previous executor on a
    same-bucket problem already paid the sweep), else the eligible replay
    backends are micro-benchmarked once on the first real operands; every
    later ``apply`` re-dispatches the pinned winner with zero re-tuning.
    ``kernel_source`` records the provenance ("static" until the first
    measured apply, then "measured"). Requires ``backend="auto"`` — an
    explicit backend pin and measure mode are contradictory instructions.
    ``apply_batched`` stays on the XLA formulation regardless: one
    fused dispatch is the point of batching, and the Pallas kernels have no
    batched formulation (module docstring).

    Robustness knobs (PR 7, see ROADMAP "The failure model"):
    ``validate="off"|"host"|"device"`` builds a pin-time ``PlanGuard`` and
    checks operand buffers O(1) per replay ("device" adds a finiteness
    sweep); ``nan_guard=True`` re-runs non-finite outputs once through the
    XLA oracle and classifies kernel-vs-data; ``watchdog=StepWatchdog(...)``
    deadlines each replay (blocking on the result); ``on_kernel_failure``
    picks between the degradation ladder ("fallback": any Pallas failure
    re-dispatches exact XLA, counted in ``telemetry.FALLBACK_COUNTS`` and
    visible as ``kernel_source == "fallback"``) and a typed
    ``KernelFallbackError`` ("raise").
    """

    def __init__(self, plan: SpgemmPlan, *, backend: str = "auto",
                 interpret: bool | None = None, tune: str | None = None,
                 validate: str | None = "off", nan_guard: bool = False,
                 watchdog=None, on_kernel_failure: str = "fallback"):
        from repro.core import autotune  # lazy: keep ctor import-light

        if plan is None:
            raise SpgemmConfigError(
                "ReuseExecutor needs a SpgemmPlan; got None — the dense "
                "spgemm method returns plan=None (no Reuse path), build the "
                "plan with method='sparse'"
            )
        autotune.validate_tune(tune)
        if tune == "measure" and backend != "auto":
            raise SpgemmConfigError(
                f"tune='measure' requires backend='auto' (got "
                f"backend={backend!r}): measure mode picks the backend "
                f"empirically, an explicit pin contradicts it")
        if on_kernel_failure not in ("fallback", "raise"):
            raise SpgemmConfigError(
                f"on_kernel_failure must be 'fallback' or 'raise', got "
                f"{on_kernel_failure!r}")
        self.plan = plan
        self.backend = _resolve_backend(backend)
        self.tune = tune
        self.kernel_source = "static"
        self._needs_measure = tune == "measure"
        # Pallas only lowers on TPU; everywhere else run it interpreted.
        self.interpret = (
            jax.default_backend() != "tpu" if interpret is None else interpret
        )
        # robustness layer (PR 7). Note the executor's validate default is a
        # literal "off", NOT None: replay is the hot path, and the
        # $REPRO_VALIDATE escape hatch changing its dispatch behind a
        # serving loop's back would be a perf landmine — opt in explicitly.
        self.validate_mode = resolve_mode(validate)
        self.nan_guard = nan_guard
        self.watchdog = watchdog
        self.on_kernel_failure = on_kernel_failure
        self.nan_events: list[tuple] = []
        # pin-time plan digest: one host sync here buys O(1) per-replay
        # operand checks (PlanGuard also vets the plan's own indptr)
        self._guard = PlanGuard(plan) if self.validate_mode != "off" else None
        self._skey: str | None = None  # set by from_matrices/pin
        self._pad_policy: str | None = None
        self._fm_cap: int | None = None

    @classmethod
    def from_matrices(cls, a: CSR, b: CSR, *, pad_policy: str | None = None,
                      plan_cache=None, backend: str = "auto",
                      interpret: bool | None = None,
                      tune: str | None = None,
                      validate: str | None = "off", nan_guard: bool = False,
                      watchdog=None,
                      on_kernel_failure: str = "fallback") -> "ReuseExecutor":
        """Build (or fetch from the plan cache) the plan for ``a @ b`` and pin
        it. This is the one and only structure hash in the executor's life.
        The hash's structure key is retained, enabling ``check_compat``."""
        res = spgemm(a, b, method="sparse", pad_policy=pad_policy,
                     plan_cache=plan_cache, validate=validate)
        ex = cls(res.plan, backend=backend, interpret=interpret, tune=tune,
                 validate=validate, nan_guard=nan_guard, watchdog=watchdog,
                 on_kernel_failure=on_kernel_failure)
        ex._skey = res.stats["structure_key"]
        ex._pad_policy = res.stats["pad_policy"]
        ex._fm_cap = res.stats["fm_cap"]
        return ex

    # the serving-facing name for pinning a plan from operands
    pin = from_matrices

    def check_compat(self, a: CSR, b: CSR) -> None:
        """Structure-key recheck: would these operands rebuild *this* plan?

        Raises ``PlanMismatchError`` if not (or if the executor was built
        from a bare plan and has no pinned key). Costs one ``structure_key``
        digest (HASH_COUNTS bumps) — an opt-in integrity check, not part of
        the replay hot path.
        """
        policy = self._pad_policy or DEFAULT_PAD_POLICY
        a, b, _, _, fm_cap = prepare_sparse_inputs(a, b, policy)
        if self._skey is not None and fm_cap != self._fm_cap:
            from repro.runtime.validate import PlanMismatchError

            raise PlanMismatchError(
                f"operand expansion bucket fm_cap={fm_cap} != the pinned "
                f"plan's {self._fm_cap}")
        check_plan_compat(self._skey, a, b, fm_cap, policy)

    def _measure(self, a_values: jax.Array, b_values: jax.Array) -> None:
        """First-apply backend measurement (tune="measure" only).

        Bucket table first — a hit reuses another executor's sweep; else
        micro-bench the eligible backends on these operands and record the
        winner for the bucket. Either way the winner is pinned: later
        applies are plain dispatches.
        """
        from repro.core import autotune

        m, k = (int(x) for x in self.plan.shape)
        bkey = autotune.bucket_key(m, k, self.fm_cap, a_values.dtype,
                                   b_values.dtype, table="replay")
        winner = autotune.lookup_measured(bkey)
        if winner is None:
            winner, _ = autotune.measure_and_record(
                bkey, replay_candidates(self.plan, a_values, b_values,
                                        self.interpret))
        self.backend = fitting_backend(winner, self.plan, a_values, b_values)
        self.kernel_source = "measured"
        self._needs_measure = False

    @property
    def shape(self) -> tuple:
        return tuple(self.plan.shape)

    @property
    def nnz_cap(self) -> int:
        return self.plan.indices.shape[0]

    @property
    def fm_cap(self) -> int:
        return self.plan.seg_ids.shape[0]

    def apply(self, a_values: jax.Array, b_values: jax.Array, *,
              donate: bool | str = False) -> jax.Array:
        """Replay the pinned plan on new operand values: (nnz_cap,) C values.

        donate: ``True``/``"both"`` donates both value buffers to the
        dispatch; ``"a"``/``"b"`` donates only that operand — use these when
        the other operand is fixed across calls (multigrid's P), since a
        donated buffer must not be passed again. Donation is permission, not
        a guarantee: XLA only aliases a donated operand into the output when
        their shapes/dtypes line up (operand ``nnz_cap`` == plan ``nnz_cap``
        bucket), and warns-and-copies otherwise — leave it off unless the
        buckets match.
        """
        DISPATCH_COUNTS["apply"] += 1
        if self._needs_measure:
            # measurement never donates: the sweep replays the same buffers
            self._measure(a_values, b_values)
        if donate:
            if self.nan_guard:
                raise SpgemmConfigError(
                    "nan_guard and donate are incompatible: the guard's "
                    "oracle re-run reads the operand buffers after dispatch, "
                    "which donation invalidates")
            key = {True: (True, True), "both": (True, True),
                   "a": (True, False), "b": (False, True)}.get(donate)
            if key is None:
                raise SpgemmConfigError(
                    f"donate must be bool, 'a', 'b' or 'both'; got {donate!r}")
            fn = _apply_donated[key]
        else:
            fn = _apply
        if self._guard is not None:
            self._guard.check_values(a_values, b_values, self.validate_mode)
        out = self._dispatch(fn, a_values, b_values)
        if self.nan_guard:
            out = self._nan_check(out, a_values, b_values)
        return out

    def _dispatch(self, fn, a_values, b_values):
        """One replay dispatch under the degradation ladder + watchdog.

        Tracing split: when the tracer is off (the default), this is exactly
        the bare ladder — no span, no clock read, no recorder entry on
        success (fallbacks and errors are always recorded; they are rare and
        already off the fast path). When tracing is on, the dispatch gets a
        ``numeric.dispatch`` span (feeding the per-kernel histograms) and a
        flight-recorder event with the host-side duration.
        """
        backend = self.backend
        if backend in ("pallas", "pallas_lp") and not f32_accumulation_ok(
                a_values.dtype, b_values.dtype):
            # the dtype guard inside _replay/lp_replay_values will route this
            # dispatch to exact XLA; record the provenance eagerly
            from repro.core.telemetry import FALLBACK_COUNTS  # lazy: cycle

            FALLBACK_COUNTS["dtype:executor->xla"] += 1
        if not obs_trace.enabled():
            return self._run_ladder(fn, a_values, b_values, backend)
        from repro.obs import recorder  # lazy: off the untraced hot path

        t0 = time.perf_counter()
        with obs_trace.span("numeric.dispatch", kernel=backend,
                            site="executor") as sp:
            out = self._run_ladder(fn, a_values, b_values, backend, sp=sp)
        recorder.record(
            "dispatch", kernel=self.backend, structure_key=self._skey,
            shapes=f"{tuple(a_values.shape)}x{tuple(b_values.shape)}",
            duration_s=time.perf_counter() - t0,
            verdict=("fallback" if sp.attrs.get("fallback") else "ok"),
            trace_id=obs_trace.current_trace_id())
        return out

    def _run_ladder(self, fn, a_values, b_values, backend, sp=None):
        """The degradation ladder proper (tracing-agnostic).

        Failure catching lives HERE, outside jit: a trace that dies is never
        cached, so re-dispatching ``backend="xla"`` compiles into its own
        (clean) cache entry — the failed backend cannot poison it. All
        counter bumps are eager host-side for the same reason.
        """
        try:
            faults.check(f"kernel:{backend}")
            out = self._timed(fn, a_values, b_values, backend)
        except (SpgemmError, StragglerDetected):
            # typed validation errors and watchdog deadline verdicts are not
            # kernel failures — the ladder must not absorb either
            raise
        except Exception as e:
            if self.on_kernel_failure == "raise" or backend == "xla":
                err = KernelFallbackError(
                    f"replay backend {backend!r} failed"
                    + ("" if backend == "xla"
                       else " and on_kernel_failure='raise'"))
                from repro.obs import recorder  # lazy: error path only

                recorder.note_error(err, kernel=backend, site="executor",
                                    structure_key=self._skey,
                                    trace_id=obs_trace.current_trace_id())
                raise err from e
            from repro.core.telemetry import FALLBACK_COUNTS  # lazy: cycle
            from repro.obs import recorder  # lazy: fallback path only

            FALLBACK_COUNTS[f"fault:{backend}->xla"] += 1
            self.kernel_source = "fallback"
            recorder.record("fallback", kernel=backend,
                            fallback=f"{backend}->xla", verdict="fallback",
                            site="executor", structure_key=self._skey,
                            trace_id=obs_trace.current_trace_id())
            if sp is not None:
                sp.set("fallback", f"{backend}->xla")
            out = self._timed(_apply, a_values, b_values, "xla")
        if faults.armed("executor:poison_output") and jnp.issubdtype(
                out.dtype, jnp.floating):
            # chaos hook: simulate a kernel writing garbage (exercises the
            # NaN guard's recovered path without a real miscompile)
            out = out.at[:1].set(jnp.nan)
        return out

    def _timed(self, fn, a_values, b_values, backend):
        """Run one dispatch, under the watchdog's deadline when one is set.

        The watchdog measures wall time to *completed results*, so the
        guarded path blocks on the output; unguarded dispatch keeps JAX's
        async semantics untouched.
        """
        if self.watchdog is None:
            return fn(self.plan, a_values, b_values,
                      backend=backend, interpret=self.interpret)
        with self.watchdog.step(DISPATCH_COUNTS["apply"]
                                + DISPATCH_COUNTS["apply_batched"]):
            out = fn(self.plan, a_values, b_values,
                     backend=backend, interpret=self.interpret)
            return jax.block_until_ready(out)

    def _nan_check(self, out, a_values, b_values):
        """Opt-in output guard: on non-finite output, re-run once through
        the exact-XLA oracle (``numeric_reuse``) and classify — "recovered"
        (kernel-side fault: oracle output finite, returned instead) vs
        "data" (operands themselves carry NaN/Inf: flagged, oracle output
        returned so the two verdicts are at least consistent)."""
        if not jnp.issubdtype(out.dtype, jnp.floating):
            return out
        if bool(jnp.all(jnp.isfinite(out))):
            return out
        from repro.core.telemetry import FALLBACK_COUNTS  # lazy: cycle

        FALLBACK_COUNTS["nan_guard:rerun"] += 1
        oracle = numeric_reuse(self.plan, a_values, b_values)
        if bool(jnp.all(jnp.isfinite(oracle))):
            FALLBACK_COUNTS["nan_guard:recovered"] += 1
            self.nan_events.append(("recovered", self.backend))
            return oracle
        FALLBACK_COUNTS["nan_guard:data"] += 1
        self.nan_events.append(("data", self.backend))
        return oracle

    def apply_batched(self, a_values: jax.Array, b_values: jax.Array) -> jax.Array:
        """Replay over stacked values in ONE dispatch: (batch, nnz_cap).

        Either operand may be stacked ``(batch, operand_nnz_cap)`` or shared
        unbatched ``(operand_nnz_cap,)`` (e.g. a fixed prolongator P against
        a batch of A values). At least one side must be stacked.
        """
        DISPATCH_COUNTS["apply_batched"] += 1
        a_axis = 0 if a_values.ndim == 2 else None
        b_axis = 0 if b_values.ndim == 2 else None
        if a_axis is None and b_axis is None:
            raise SpgemmConfigError(
                "apply_batched needs at least one stacked (batch, nnz) operand; "
                "use apply() for a single replay"
            )
        if self._guard is not None:
            self._guard.check_values(a_values, b_values, self.validate_mode,
                                     batched=True)
        batch = a_values.shape[0] if a_axis == 0 else b_values.shape[0]
        # batched replay is always the XLA formulation (module docstring)
        with obs_trace.span("numeric.dispatch", kernel="xla",
                            site="executor", batch=batch):
            if self.watchdog is None:
                return _apply_batched(self.plan, a_values, b_values,
                                      a_axis=a_axis, b_axis=b_axis)
            with self.watchdog.step(DISPATCH_COUNTS["apply"]
                                    + DISPATCH_COUNTS["apply_batched"]):
                out = _apply_batched(self.plan, a_values, b_values,
                                     a_axis=a_axis, b_axis=b_axis)
                return jax.block_until_ready(out)

    def to_csr(self, values: jax.Array) -> CSR:
        """Wrap one replay's values in the plan's C structure."""
        return CSR(indptr=self.plan.indptr, indices=self.plan.indices,
                   values=values, shape=self.shape)


def spgemm_grouped(pairs: Sequence[tuple[CSR, CSR]], *,
                   pad_policy: str | None = None, plan_cache=None,
                   backend: str = "auto", interpret: bool | None = None,
                   tune: str | None = None) -> list[CSR]:
    """Mixed-structure batch: group by structure, one dispatch per group.

    Each (A, B) multiply is hashed once with ``plan_cache.structure_key``;
    multiplies sharing a structure (and operand value dtypes — stacking must
    not promote a mixed group) are stacked and replayed through a single
    ``apply_batched`` dispatch (plans come from — and land in — the plan
    cache, so repeated batches skip expansion entirely). Results come back
    in input order as CSR matrices sharing their group's structure arrays.

    tune="measure": singleton groups dispatch the measured replay winner —
    the plan-cache entry's recorded winner when one exists (zero re-tuning
    across calls), else a first-sight measurement whose winner is written
    back to the entry, exactly mirroring ``spgemm(tune="measure")``.
    Batched (>1) groups keep the XLA formulation — one batched dispatch
    is the point of batching (see ReuseExecutor). Requires backend="auto".
    """
    from repro.core import autotune  # lazy, mirrors ReuseExecutor

    autotune.validate_tune(tune)
    if tune == "measure" and backend != "auto":
        raise SpgemmConfigError(
            f"tune='measure' requires backend='auto' (got "
            f"backend={backend!r}): measure mode picks the backend "
            f"empirically, an explicit pin contradicts it")
    policy = DEFAULT_PAD_POLICY if pad_policy is None else pad_policy
    pairs = list(pairs)
    if not pairs:
        # The empty batch is a legal no-op (a serving tick with nothing
        # admitted), not an error: return the empty result explicitly so a
        # generator input or an all-shed batch can never fall through to an
        # opaque downstream IndexError.
        return []
    if plan_cache is None:
        cache = default_plan_cache()
    elif plan_cache is False:
        cache = None
    else:
        cache = plan_cache

    prepared: list[tuple[CSR, CSR, int]] = []
    groups: OrderedDict[tuple, list[int]] = OrderedDict()
    for a, b in pairs:
        a, b, _, _, fm_cap = prepare_sparse_inputs(a, b, policy)
        skey = structure_key(a, b, fm_cap, policy)  # the one hash per multiply
        # dtypes join the grouping (not the plan key): jnp.stack on a mixed
        # group would silently promote, diverging from the per-call contract
        gkey = (skey, str(a.values.dtype), str(b.values.dtype))
        groups.setdefault(gkey, []).append(len(prepared))
        prepared.append((a, b, fm_cap))

    results: list[CSR | None] = [None] * len(prepared)
    for (skey, adt, bdt), idxs in groups.items():
        a0, b0, fm_cap = prepared[idxs[0]]
        plan, _, _ = resolve_plan(a0, b0, fm_cap, policy, cache, key=skey)
        group_tune = tune if len(idxs) == 1 else None  # batched stays XLA
        meta_key = ("tuned_backend", adt, bdt)
        if group_tune == "measure" and cache is not None:
            pinned = cache.get_meta(skey, meta_key)
            if pinned is not None:
                # a prior measured call already decided for this entry:
                # dispatch the winner directly, zero re-tuning
                autotune.TUNE_COUNTS["plan_meta_hit"] += 1
                ex = ReuseExecutor(plan, backend=pinned, interpret=interpret)
                results[idxs[0]] = ex.to_csr(ex.apply(a0.values, b0.values))
                continue
        ex = ReuseExecutor(plan, backend=backend, interpret=interpret,
                           tune=group_tune)
        if len(idxs) == 1:
            results[idxs[0]] = ex.to_csr(ex.apply(a0.values, b0.values))
            if ex.kernel_source == "measured" and cache is not None:
                cache.set_meta(skey, meta_key, ex.backend)
            continue
        a_stack = jnp.stack([prepared[i][0].values for i in idxs])
        b_stack = jnp.stack([prepared[i][1].values for i in idxs])
        vals = ex.apply_batched(a_stack, b_stack)
        for j, i in enumerate(idxs):
            results[i] = ex.to_csr(vals[j])
    return results
