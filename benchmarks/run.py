"""Benchmark harness — one function per paper table/figure.

Protocol matches the paper (§4): 1 warmup + average of 5 timed runs.
Output: ``name,us_per_call,derived`` CSV rows.

  bench_methods      — Fig 5/7: GFLOPS/s per method (KKDENSE / KKMEM-analog
                       sparse / KKSPGEMM auto) per matrix
  bench_profile      — Fig 6: performance-profile summary (wins, max
                       slowdown vs best)
  bench_compression  — Table 3 / §4.3: CF, CMRF, symbolic time +/- compression
  bench_reuse        — Fig 6(d)/(f): NoReuse vs Reuse numeric phase
  bench_reuse_batched — batched reuse replay: ReuseExecutor.apply_batched
                       (one dispatch per batch) vs a per-call numeric_reuse
                       loop; throughput in multiplies/s
  bench_compile      — recompile counts + plan-cache hit rate: same-bucket
                       structures share executables, repeats hit the cache
  bench_accumulators — the paper's accumulator trade-off: dense-acc vs
                       sorted-segment vs LP-hash numeric phase across
                       avg-row-flop regimes, with choose_kernel's pick and
                       the measured winner per regime (the Figure-style
                       crossover, tracked per-PR via BENCH_accum_*.json)
  bench_fm_groups    — Fig 8: meta-vs-fixed speedup grouped by f_m
  bench_distributed  — §multi-pod: 1-D row-wise SpGEMM scaling terms
  bench_dist         — repro.dist sharded-plan replay: latency per replay
                       count on a pinned ShardedReuseExecutor (flat curve =
                       zero per-replay host work); mesh shape in the row
  bench_train_smoke  — LM substrate: tokens/s of a smoke train step
  bench_guard        — guarded-mode overhead: replay latency per validate
                       mode (off/host/device), nan_guard and watchdog rows
                       (overhead ratios vs validate=off), plus a retry_call
                       machinery row — the failure-model cost artifact
                       (BENCH_guard_*.json)
  bench_serve        — serving-tier acceptance: sustained QPS + p50/p99
                       latency over a synthetic mixed-structure trace,
                       admission shed rates under a deliberate overload
                       burst, and breaker open/short-circuit/recovery
                       behavior with kernel faults injected mid-stream
                       (BENCH_serve_*.json)
  bench_obs          — observability overhead gate: disabled-span unit
                       cost, tracing-off replay overhead (the <= 2% CI
                       gate, with a telemetry-asserted dispatch-identity
                       bit), tracing-on ratio, and a traced chaos mini-run
                       exported as trace_obs_sample.json
                       (BENCH_obs_*.json)
  bench_autotune     — autotuner regret table: static vs fitted vs measured
                       kernel picks over the accumulator sweep (regret in us
                       vs the static rule; the acceptance artifact for
                       core/autotune), plus a live tune="measure" first-
                       sight + cached-winner replay demo with telemetry

``--quick`` runs a CI-sized smoke subset (2 suite cases; compile, reuse,
batched-reuse and dist benches only). ``--devices N`` forces an N-device
host platform (must be set before jax initializes — the flag is injected at
the top of main()) so the shard_map paths run mesh-wide on CPU-only
runners. ``--json PATH`` additionally writes the rows as machine-readable
JSON (exact derived metric values; the CSV column is a rendering of them)
so CI can archive a BENCH_*.json trajectory. Every row (and the payload)
is stamped with backend/platform/jax_version so fitted thresholds are
keyed per backend, and all bench RNG seeds are fixed constants
(``BENCH_SEED`` plus per-generator literals) so artifacts are comparable
across PRs.

``--fit-thresholds BENCH_JSON`` is a subcommand, not a bench: it loads a
previously archived benchmark payload (any run containing
``accumulators/*`` rows), fits per-backend thresholds with
``repro.core.autotune.fit_thresholds``, writes the ``TunedThresholds``
table to --json (the ``BENCH_autotune_<sha>.json`` CI artifact) and exits.
"""
from __future__ import annotations

import argparse
import json
import os
import time
import uuid

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.suite import suite
from repro.core import (
    PlanCache,
    ReuseExecutor,
    compress_matrix,
    compression_decision,
    numeric_reuse,
    reset_trace_counts,
    round_capacity,
    spgemm,
    symbolic,
)
from repro.core.spgemm import TRACE_COUNTS, numeric_fresh, symbolic_plain, symbolic_compressed
from repro.core.compression import flops_stats
from repro.sparse import CSR, random_csr

ROWS: list[str] = []
RESULTS: list[dict] = []  # structured mirror of ROWS for --json
CASES: list = []  # populated by main(); benches iterate this, not suite()

# One seed for every ad-hoc bench RNG (values-only resamples etc.); matrix
# generators carry their own per-case literals. Fixed so BENCH_*.json
# artifacts are comparable across PRs.
BENCH_SEED = 0


def _fmt_val(v) -> str:
    return f"{v:.6g}" if isinstance(v, float) else str(v)


# One id per harness invocation: lets BENCH_*.json artifacts from different
# runs be ordered (timestamp) and joined (run_id) into a trajectory.
RUN_ID = uuid.uuid4().hex[:12]


def _env_stamp() -> dict:
    """backend/platform/jax-version + run identity stamp attached to every
    result row, so downstream consumers (``autotune.fit_thresholds``, the
    BENCH trajectory) can key per-backend fits and join rows across runs
    without trusting payload-level context."""
    dev = jax.devices()[0]
    return {
        "backend": jax.default_backend(),
        "platform": getattr(dev, "device_kind", "unknown"),
        "jax_version": jax.__version__,
        "run_id": RUN_ID,
        "timestamp": time.time(),
    }


def emit(name: str, us: float, derived: dict | None = None):
    """Record one result row. ``derived`` holds the exact metric values; the
    CSV display string is rendered from it (not the other way around), so
    --json archives full precision."""
    derived = derived or {}
    text = ";".join(f"{k}={_fmt_val(v)}" for k, v in derived.items())
    row = f"{name},{us:.1f},{text}"
    ROWS.append(row)
    RESULTS.append({"name": name, "us_per_call": us, "derived": derived,
                    **_env_stamp()})
    print(row, flush=True)


def timeit(fn, *args, reps: int = 5):
    """Paper protocol: 1 excluded warmup + mean of ``reps``."""
    out = fn(*args)
    jax.block_until_ready(out)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.mean(ts)) * 1e6, out


def _fm(a, b) -> int:
    return int(flops_stats(a, b.row_nnz())[0])


def bench_methods():
    """GFLOPS/s (2*f_m flops, as the paper counts) per method per matrix."""
    results = {}
    for name, a, b in CASES:
        fm = _fm(a, b)
        res = spgemm(a, b)  # warm caches, get caps
        fm_cap = round_capacity(fm)
        nnz_cap = round_capacity(int(res.c.nnz()))
        per_method = {}
        us_sym, _ = timeit(lambda: symbolic(a, b)[0])
        us_num, _ = timeit(lambda: numeric_fresh(a, b, fm_cap, nnz_cap)[0])
        per_method["sparse"] = us_sym + us_num
        if b.k < 250_000 and a.m * b.k * 8 <= (1 << 30):
            from repro.core.spgemm import numeric_dense_acc
            us_dnum, _ = timeit(lambda: numeric_dense_acc(a, b, fm_cap, nnz_cap))
            per_method["dense"] = us_sym + us_dnum
        us_auto = per_method.get(res.stats["method"], per_method["sparse"])
        per_method["kkspgemm"] = us_auto
        results[name] = (fm, per_method)
        for meth, us in per_method.items():
            gflops = 2 * fm / (us * 1e-6) / 1e9
            emit(f"methods/{name}/{meth}", us, {"gflops": gflops, "fm": fm})
    return results


def bench_profile(results):
    """Fig 6 summary: per method, #wins and max slowdown vs per-problem best."""
    methods = ["sparse", "dense", "kkspgemm"]
    wins = {m: 0 for m in methods}
    max_slow = {m: 1.0 for m in methods}
    for name, (fm, per) in results.items():
        best = min(per.values())
        for m in methods:
            if m in per:
                if per[m] <= best * 1.005:
                    wins[m] += 1
                max_slow[m] = max(max_slow[m], per[m] / best)
    for m in methods:
        emit(f"profile/{m}", 0.0,
             {"wins": wins[m], "max_slowdown": max_slow[m]})


def bench_compression():
    """CF / CMRF + symbolic-phase time with vs without compression."""
    for name, a, b in CASES:
        bc = compress_matrix(b)
        cf, cmrf, use = compression_decision(a, b, bc)
        fm = _fm(a, b)
        cap_plain = round_capacity(fm)
        us_plain, _ = timeit(lambda: symbolic_plain(a, b, cap_plain))
        fm_c = int(jnp.sum(jnp.where(
            a.valid_mask(),
            bc.row_nnz()[jnp.minimum(a.indices, bc.indptr.shape[0] - 2)], 0)))
        cap_c = round_capacity(max(fm_c, 1))
        us_comp, _ = timeit(
            lambda: symbolic_compressed(a, bc, a.m, cap_c))
        emit(f"compression/{name}", us_comp,
             {"cf": cf, "cmrf": cmrf, "applied": int(use),
              "plain_us": us_plain, "speedup": us_plain / us_comp})


def bench_reuse():
    """Reuse (numeric only, cached plan) vs NoReuse (symbolic+numeric)."""
    for name, a, b in CASES:
        res = spgemm(a, b, method="sparse")
        fm = _fm(a, b)
        fm_cap = round_capacity(fm)
        nnz_cap = round_capacity(int(res.c.nnz()))
        us_sym, _ = timeit(lambda: symbolic(a, b)[0])
        us_fresh, _ = timeit(lambda: numeric_fresh(a, b, fm_cap, nnz_cap)[0])
        us_reuse, _ = timeit(
            lambda: numeric_reuse(res.plan, a.values, b.values))
        noreuse = us_sym + us_fresh
        emit(f"reuse/{name}", us_reuse,
             {"noreuse_us": noreuse, "speedup": noreuse / us_reuse})


def bench_reuse_batched(batches=(8, 32)):
    """Batched reuse replay (the executor's acceptance benchmark).

    Per case and batch size: stack ``batch`` value sets on one pinned plan
    and compare ONE ``ReuseExecutor.apply_batched`` dispatch against a
    per-call ``numeric_reuse`` loop. Reports both in multiplies/s — the
    north-star serving metric. A small dispatch-bound case rides along so
    the dispatch-amortization effect is visible even when the suite cases
    are compute-bound.
    """
    small = random_csr(256, 256, 4.0, 123)
    cases = [("rand256_AxA", small, small)] + list(CASES[:2])
    for name, a, b in cases:
        ex = ReuseExecutor.from_matrices(a, b, plan_cache=PlanCache())
        rng = np.random.default_rng(BENCH_SEED)
        for batch in batches:
            a_stack = jnp.asarray(
                rng.standard_normal((batch, a.nnz_cap)), jnp.float32)
            b_stack = jnp.asarray(
                rng.standard_normal((batch, b.nnz_cap)), jnp.float32)
            # pre-split so the loop pays dispatch, not slicing
            a_list = [jnp.asarray(a_stack[i]) for i in range(batch)]
            b_list = [jnp.asarray(b_stack[i]) for i in range(batch)]

            us_batched, _ = timeit(lambda: ex.apply_batched(a_stack, b_stack))
            us_loop, _ = timeit(
                lambda: [numeric_reuse(ex.plan, av, bv)
                         for av, bv in zip(a_list, b_list)])
            emit(f"reuse_batched/{name}/b{batch}", us_batched,
                 {"loop_us": us_loop,
                  "speedup": us_loop / us_batched,
                  "mult_per_s": batch / (us_batched * 1e-6),
                  "loop_mult_per_s": batch / (us_loop * 1e-6)})


def bench_compile():
    """Recompile counts + plan-cache hit rate through the public spgemm().

    Three calls tell the whole bucketing/caching story:
      1. fresh structure       -> traces every pipeline stage once (miss)
      2. same-bucket structure -> different graph, same capacity buckets:
                                  zero new traces (executables shared)
      3. repeated structure    -> new values only: plan-cache hit, zero
                                  traces, no expansion/sort at all
    """
    jax.clear_caches()  # measure traces from a clean slate
    reset_trace_counts()
    cache = PlanCache(capacity=8)
    mk = lambda seed: random_csr(256, 256, 5.0, seed)
    a1, b1 = mk(101), mk(102)
    a2, b2 = mk(103), mk(104)  # same shape/density -> same capacity buckets

    def one_call(a, b):
        """Single timed call — compile cost included, that's the point."""
        t0 = time.perf_counter()
        res = spgemm(a, b, method="sparse", plan_cache=cache)
        jax.block_until_ready(res.c.values)
        return (time.perf_counter() - t0) * 1e6, res

    us1, res1 = one_call(a1, b1)
    traces_first = sum(TRACE_COUNTS.values())

    us2, res2 = one_call(a2, b2)
    traces_same_bucket = sum(TRACE_COUNTS.values()) - traces_first

    rng = np.random.default_rng(BENCH_SEED)
    a1v = CSR(a1.indptr, a1.indices,
              jnp.asarray(rng.standard_normal(a1.nnz_cap), jnp.float32), a1.shape)
    us3, res3 = one_call(a1v, b1)
    traces_hit = sum(TRACE_COUNTS.values()) - traces_first - traces_same_bucket

    cs = cache.stats()
    emit("compile/fresh", us1,
         {"traces": traces_first,
          "expansions": TRACE_COUNTS["expand_and_sort"],
          "cache": res1.stats["cache"]})
    emit("compile/same_bucket", us2,
         {"new_traces": traces_same_bucket, "cache": res2.stats["cache"]})
    emit("compile/cache_hit", us3,
         {"new_traces": traces_hit, "cache": res3.stats["cache"]})
    emit("compile/cache", 0.0,
         {"hits": cs["hits"], "misses": cs["misses"],
          "hit_rate": cs["hit_rate"]})


def _accum_regimes(quick: bool) -> list[tuple]:
    """The avg-row-flop regimes straddling the KKLP cutoff — shared by
    bench_accumulators (the crossover artifact) and bench_autotune (the
    regret table), so the fit is evaluated on exactly the sweep it is
    fitted from."""
    regimes = [
        ("low_flops", random_csr(128, 128, 3.0, 41), random_csr(128, 128, 3.0, 42)),
        ("high_flops", random_csr(8, 32, 12.0, 45), random_csr(32, 96, 32.0, 46)),
    ]
    if not quick:
        regimes.insert(1, (
            "mid_flops", random_csr(64, 96, 8.0, 43), random_csr(96, 128, 8.0, 44)))
    return regimes


def _time_accum_arms(a, b, stats: dict, interpret: bool) -> dict[str, float]:
    """Time the three accumulator arms (full from-scratch numeric phase) on
    one problem: {"dense_acc": us, "segsum": us, "lp_hash": us}."""
    from repro.core import numeric_fresh, numeric_lp
    from repro.core.spgemm import numeric_dense_acc

    fm_cap, nnz_cap = stats["fm_cap"], stats["nnz_cap"]
    per: dict[str, float] = {}
    per["dense_acc"], _ = timeit(
        lambda: numeric_dense_acc(a, b, fm_cap, nnz_cap))
    per["segsum"], _ = timeit(
        lambda: numeric_fresh(a, b, fm_cap, nnz_cap)[0])
    per["lp_hash"], _ = timeit(
        lambda: numeric_lp(a, b, fm_cap, nnz_cap, interpret=interpret)[0])
    return per


def bench_accumulators(quick: bool = False):
    """Accumulator crossover (the paper's central performance claim): time
    the FULL numeric phase (structure + values, from-scratch) through each
    accumulator data structure across avg-row-flop regimes straddling the
    KKLP cutoff (256) — all three arms pay their structure-extraction work,
    so the comparison is apples-to-apples:

      dense_acc — XLA dense (m, k) scatter accumulator + nonzero-scan CSR
                  extraction (``numeric_dense_acc``, the KKDENSE position)
      segsum    — single-expansion pipeline + sorted-segment accumulation
                  (``numeric_fresh``, the Thread-Flat-Parallel position)
      lp_hash   — same pipeline, values through the Pallas LP-hash
                  accumulator (``numeric_lp``, the KKLP position)

    Each row records avg_row_flops, ``choose_kernel``'s pick and that arm's
    own backend (dense_acc/segsum are compiled XLA everywhere; lp_hash is
    Pallas on TPU, interpret mode elsewhere); the ``crossover`` row per
    regime names the measured winner so the BENCH_accum_*.json trajectory
    shows where the crossover sits. Off-TPU the LP arm pays interpret
    overhead, so the winner comparison is not hardware-meaningful there —
    the crossover row carries ``comparable=0`` in that case and readers of
    the artifact should track the dense/segsum columns plus the
    choose_kernel pick until real-TPU CI exists.
    """
    from repro.core import choose_kernel

    interpret = jax.default_backend() != "tpu"
    arm_backend = {"dense_acc": "xla", "segsum": "xla",
                   "lp_hash": "interpret" if interpret else "pallas"}
    for name, a, b in _accum_regimes(quick):
        res = spgemm(a, b, method="sparse", plan_cache=PlanCache())
        fm = res.stats["fm"]
        avg_row_flops = fm / max(a.m, 1)
        chosen = choose_kernel(a, b, {"fm": fm})
        per = _time_accum_arms(a, b, res.stats, interpret)
        for acc, us in per.items():
            emit(f"accumulators/{name}/{acc}", us,
                 {"avg_row_flops": avg_row_flops, "fm": fm,
                  "chosen": chosen, "backend": arm_backend[acc],
                  "gflops": 2 * fm / (us * 1e-6) / 1e9})
        winner = min(per, key=per.get)
        emit(f"accumulators/{name}/crossover", 0.0,
             {"avg_row_flops": avg_row_flops, "chosen": chosen,
              "winner": winner, "comparable": int(not interpret),
              "lp_over_segsum": per["lp_hash"] / per["segsum"],
              "dense_over_segsum": per["dense_acc"] / per["segsum"]})


def bench_autotune(quick: bool = False):
    """Autotuner acceptance: regret of each selection mode vs the static rule.

    Reruns the accumulator sweep, then asks each mode which arm it would
    pick per regime and charges it that arm's measured time:

      static   — the paper rule at AVG_ROW_FLOPS_CUTOFF (the baseline;
                 regret 0 by definition)
      fitted   — thresholds fitted (in-run) from this very sweep via
                 ``fit_thresholds``; by construction its TOTAL time over the
                 sweep is <= static's (the fit minimizes exactly that), so
                 ``autotune/regret_total`` must be <= 0 up to timing noise
      measured — the per-regime argmin, what ``tune="measure"`` converges
                 to; pointwise regret <= 0 by definition

    A live ``spgemm(tune="measure")`` demo rides along: first sight pays one
    micro-bench (TUNE_COUNTS delta proves it), the pinned-plan replay
    re-dispatches the cached winner with zero re-tuning (plan_meta_hit, no
    new micro_bench).
    """
    from repro.core import (
        AVG_ROW_FLOPS_CUTOFF,
        fit_thresholds,
        set_tuned_thresholds,
    )
    from repro.core.autotune import ARM_OF_PICK, TUNE_COUNTS

    interpret = jax.default_backend() != "tpu"
    stamp = _env_stamp()
    sweep = []  # (regime, avg_row_flops, per-arm times)
    for name, a, b in _accum_regimes(quick):
        res = spgemm(a, b, method="sparse", plan_cache=PlanCache())
        fm = res.stats["fm"]
        per = _time_accum_arms(a, b, res.stats, interpret)
        sweep.append((name, fm / max(a.m, 1), per))

    # feed the fitter the same row shape bench_accumulators archives
    fit_rows = [
        {"name": f"accumulators/{name}/{arm}", "us_per_call": us,
         "backend": stamp["backend"], "platform": stamp["platform"],
         "derived": {"avg_row_flops": arf}}
        for name, arf, per in sweep for arm, us in per.items()
    ]
    table = fit_thresholds({"rows": fit_rows, **stamp})
    fit = table.for_backend()
    cutoff = fit.avg_row_flops_cutoff if fit else None
    emit("autotune/fit", 0.0,
         {"fitted_cutoff": -1.0 if cutoff is None else float(cutoff),
          "static_cutoff": float(AVG_ROW_FLOPS_CUTOFF),
          "n_points": fit.n_points if fit else 0})

    totals = {"static": 0.0, "fitted": 0.0, "measured": 0.0}
    for name, arf, per in sweep:
        choosable = {k: per[v] for k, v in ARM_OF_PICK.items()}
        static_pick = ("dense_acc" if arf < AVG_ROW_FLOPS_CUTOFF
                       else "flat_lp")
        fitted_pick = (static_pick if cutoff is None
                       else "dense_acc" if arf < cutoff else "flat_lp")
        t_static = choosable[static_pick]
        t_fitted = choosable[fitted_pick]
        t_measured = min(choosable.values())
        totals["static"] += t_static
        totals["fitted"] += t_fitted
        totals["measured"] += t_measured
        emit(f"autotune/{name}/regret", 0.0,
             {"avg_row_flops": arf, "static_pick": static_pick,
              "fitted_pick": fitted_pick,
              "measured_pick": min(choosable, key=choosable.get),
              "static_us": t_static,
              "regret_fitted_us": t_fitted - t_static,
              "regret_measured_us": t_measured - t_static})
    emit("autotune/regret_total", 0.0,
         {"static_us": totals["static"],
          "regret_fitted_us": totals["fitted"] - totals["static"],
          "regret_measured_us": totals["measured"] - totals["static"]})

    # live measure-mode demo on a pinned plan cache
    cache = PlanCache()
    a = random_csr(96, 96, 4.0, 47)
    b = random_csr(96, 96, 4.0, 48)
    mb0, pm0 = TUNE_COUNTS["micro_bench"], TUNE_COUNTS["plan_meta_hit"]
    us_first, _ = timeit(
        lambda: spgemm(a, b, method="sparse", plan_cache=cache,
                       tune="measure").c.values, reps=1)
    mb_first = TUNE_COUNTS["micro_bench"] - mb0
    us_replay, _ = timeit(
        lambda: spgemm(a, b, method="sparse", plan_cache=cache,
                       tune="measure").c.values)
    emit("autotune/measure_demo", us_replay,
         {"first_call_us": us_first,
          "micro_bench_first": mb_first,
          "micro_bench_new_on_replay":
              TUNE_COUNTS["micro_bench"] - mb0 - mb_first,
          "plan_meta_hits": TUNE_COUNTS["plan_meta_hit"] - pm0})


def bench_fm_groups(results):
    """Fig 8: geometric-mean speedup of kkspgemm vs single fixed method,
    grouped by f_m size."""
    rows = sorted(results.items(), key=lambda kv: kv[1][0])
    half = max(len(rows) // 2, 1)
    for label, grp in (("small_fm", rows[:half]), ("large_fm", rows[half:])):
        sp = []
        for name, (fm, per) in grp:
            base = per["sparse"]
            sp.append(base / per["kkspgemm"])
        gm = float(np.exp(np.mean(np.log(np.maximum(sp, 1e-9)))))
        emit(f"fm_groups/{label}", 0.0,
             {"geomean_speedup_vs_sparse": gm, "n": len(grp)})


def bench_distributed():
    """1-D row-wise distributed SpGEMM phase costs (single real device:
    reports the sharded-path overhead vs local)."""
    from repro.compat import make_mesh
    from repro.core import distributed_spgemm

    mesh = make_mesh((1,), ("data",))
    for name, a, b in CASES[:3]:
        us_local, _ = timeit(lambda: spgemm(a, b).c.values)
        us_dist, _ = timeit(
            lambda: distributed_spgemm(a, b, mesh).values)
        emit(f"distributed/{name}", us_dist,
             {"local_us": us_local, "overhead": us_dist / us_local})


def bench_dist(n_windows=5, window=16):
    """repro.dist acceptance benchmark: replay latency flat vs replay count.

    Pins one ShardedReuseExecutor on the full host mesh and runs ONE stream
    of ``n_windows * window`` blocked replays, each individually timed,
    split into DISJOINT equal-sized windows. Row ``r{n}`` reports the
    median latency of the window starting at stream position n — a genuine
    "does the Nth replay cost more than the 1st" measurement (overlapping
    windows would mostly compare samples with themselves), so flatness
    across windows rules out accumulating per-replay host work (cache
    growth, re-partitioning, leak-driven drift). The deterministic half of
    the proof rides in the same rows: retraces and structure hashes counted
    over the whole stream (both must be 0 — constant per-replay overhead
    would not show up as slope, the counters catch it instead). Medians,
    not means: shared CI runners throttle in multi-second windows and the
    spikes land in the tail. The mesh shape rides in every row so the
    --json artifact records the decomposition the numbers were taken on.
    """
    from repro.core import HASH_COUNTS, PlanCache
    from repro.core.spgemm import TRACE_COUNTS
    from repro.dist import ShardedReuseExecutor
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh()
    mesh_shape = "x".join(str(s) for s in mesh.devices.shape)
    a = random_csr(512, 512, 4.0, 7)
    b = random_csr(512, 512, 4.0, 8)
    for placement in ("replicated", "allgather"):
        ex = ShardedReuseExecutor.from_matrices(
            a, b, mesh, b_placement=placement, plan_cache=PlanCache())
        rng = np.random.default_rng(BENCH_SEED)
        av = jnp.asarray(rng.standard_normal(a.nnz_cap), jnp.float32)
        bv = jnp.asarray(rng.standard_normal(b.nnz_cap), jnp.float32)
        for _ in range(3):  # warm the dispatch path
            jax.block_until_ready(ex.apply(av, bv))
        traces0 = sum(TRACE_COUNTS.values())
        hashes0 = sum(HASH_COUNTS.values())
        ts = []
        for _ in range(n_windows * window):
            t0 = time.perf_counter()
            jax.block_until_ready(ex.apply(av, bv))
            ts.append(time.perf_counter() - t0)
        retraces = sum(TRACE_COUNTS.values()) - traces0
        hashes = sum(HASH_COUNTS.values()) - hashes0
        per_window = {}
        for w in range(n_windows):
            n = w * window + 1  # 1-based stream position of window start
            seg = ts[w * window: (w + 1) * window]
            med_us = float(np.median(seg)) * 1e6
            per_window[n] = med_us
            emit(f"dist/{placement}/r{n}", med_us,
                 {"us_per_replay": med_us, "replay_index": n,
                  "window": window,
                  "window_total_us": float(np.sum(seg)) * 1e6,
                  "retraces": retraces, "hashes": hashes,
                  "mesh_shape": mesh_shape, "b_placement": placement})
        flatness = max(per_window.values()) / min(per_window.values())
        emit(f"dist/{placement}/flatness", 0.0,
             {"max_over_min": flatness, "retraces": retraces,
              "hashes": hashes, "mesh_shape": mesh_shape})


def bench_guard(quick: bool = False):
    """Guarded-mode overhead (the failure model's acceptance artifact).

    One pinned ``ReuseExecutor`` per validation mode on the same problem:

      guard/validate_off    — the baseline replay (no guard object at all)
      guard/validate_host   — O(1) host-side PlanGuard checks per replay
      guard/validate_device — + one jitted bitmask reduction per operand
                              (a scalar device sync per replay)
      guard/nan_guard       — + the post-replay finiteness check on clean
                              output (the guard's happy path)
      guard/watchdog        — deadline-wrapped replay: the dispatch blocks
                              via block_until_ready inside the step timer,
                              so the row prices losing async dispatch too

    Every row carries ``overhead`` = us / validate-off us, so the
    BENCH_guard_*.json trajectory answers "what does hardening cost this
    PR". A ``guard/retry`` row rides along: retry_call around a closure
    that fails twice then succeeds, with the deterministic backoff summed
    (sleep stubbed out — the row prices the machinery, not the waiting).
    """
    from repro.runtime import StepWatchdog
    from repro.runtime.retry import backoff_schedule, retry_call

    a = random_csr(256, 256, 4.0, 51)
    b = random_csr(256, 256, 4.0, 52)

    def replay_us(**kw):
        ex = ReuseExecutor.from_matrices(a, b, plan_cache=PlanCache(), **kw)
        us, _ = timeit(lambda: ex.apply(a.values, b.values))
        return us

    base = replay_us()
    emit("guard/validate_off", base, {"overhead": 1.0})
    for mode in ("host", "device"):
        us = replay_us(validate=mode)
        emit(f"guard/validate_{mode}", us, {"overhead": us / base})
    us_nan = replay_us(nan_guard=True)
    emit("guard/nan_guard", us_nan, {"overhead": us_nan / base})
    wd = StepWatchdog(deadline_s=60.0, policy="warn")
    us_wd = replay_us(watchdog=wd)
    emit("guard/watchdog", us_wd,
         {"overhead": us_wd / base, "slow_steps": len(wd.slow_steps)})

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] % 3:  # fails twice, succeeds on the 3rd, every cycle
            raise RuntimeError("transient")
        return calls["n"]

    us_retry, _ = timeit(
        lambda: retry_call(flaky, retries=3, sleep=lambda d: None,
                           seed=BENCH_SEED))
    sched = backoff_schedule(3, seed=BENCH_SEED)
    emit("guard/retry", us_retry,
         {"attempts_per_success": 3,
          "backoff_total_s": float(sum(sched))})


def bench_serve(quick: bool = False):
    """Serving-tier acceptance (BENCH_serve_*.json): one synthetic trace
    through ``SparseService``, in phases:

      serve/warm     — traffic-log plan prefetch before traffic (built/hits)
      serve/steady   — sustained mixed-structure load: requests round-robin
                       over N structures, stepped as they queue; reports
                       sustained QPS and p50/p99 request latency (admission
                       -> completion, batching wait included)
      serve/overload — a deliberate burst past max_queue plus infeasible
                       deadlines: the shed-rate row (every shed typed, none
                       silent — the counters are the evidence)
      serve/chaos    — kernel:pallas armed mid-stream over singleton
                       traffic: ladder fallbacks until the breaker opens,
                       then short-circuits straight to XLA (the row carries
                       both counts — short_circuits are the requests that
                       SKIPPED paying the fault)
      serve/recovery — fault cleared, cooldown elapsed: the half-open probe
                       re-admits the fast path; breaker_closed=1 is the
                       acceptance bit
    """
    from repro.core import telemetry
    from repro.runtime import faults
    from repro.serve import SparseService

    n_structs = 2 if quick else 4
    n_steady = 32 if quick else 128
    n_chaos = 8 if quick else 16
    structures = [
        (random_csr(64 + 32 * i, 64, 3.0, 61 + i),
         random_csr(64, 48, 3.0, 81 + i))
        for i in range(n_structs)
    ]
    svc = SparseService(backend="pallas", max_batch=8, max_queue=64,
                        breaker_threshold=3, breaker_cooldown_s=0.05,
                        retries=1, sleep=lambda _: None)

    # -- warm: record one request per structure, then prefetch the plans
    for a, b in structures:
        svc.submit(a, b)
    svc.drain()
    svc.plan_cache.clear()  # force the warm to do real work
    ws = svc.warm()
    emit("serve/warm", 0.0, {"structures": len(structures), **ws})

    # -- steady traffic: round-robin structures, step whenever a batch fills
    t0 = time.perf_counter()
    for i in range(n_steady):
        a, b = structures[i % n_structs]
        svc.submit(a, b, deadline_s=60.0)
        if svc.queue_depth >= svc.max_batch:
            svc.step()
    svc.drain()
    steady_s = time.perf_counter() - t0
    pct = svc.latency_percentiles()
    completed = svc.counters["completed"]
    emit("serve/steady", steady_s * 1e6 / max(n_steady, 1),
         {"qps": n_steady / steady_s, "completed": completed,
          "p50_ms": pct["p50"] * 1e3, "p99_ms": pct["p99"] * 1e3,
          "group_dispatches": svc.counters["group_dispatches"]})

    # -- overload: a burst past the queue bound + infeasible deadlines
    a, b = structures[0]
    for _ in range(8):
        svc.submit(a, b, deadline_s=1e-9)  # infeasible vs the measured EWMA
    for i in range(svc.max_queue + 16):
        svc.submit(a, b)
    svc.drain()
    st = svc.stats()
    emit("serve/overload", 0.0,
         {"shed_rate": st["shed_rate"],
          "shed_queue_full": st["shed_queue_full"],
          "shed_deadline_infeasible": st["shed_deadline_infeasible"],
          "shed_deadline_expired": st["shed_deadline_expired"],
          "failed": st["failed"]})

    # -- chaos: fast kernel faults mid-stream on singleton traffic
    fb0 = telemetry.FALLBACK_COUNTS["fault:pallas->xla"]
    deg0 = svc.counters["degraded_dispatches"]
    with faults.failpoint("kernel:pallas"):
        for i in range(n_chaos):
            svc.submit(*structures[i % n_structs])
            svc.step()  # singleton steps: the breaker-governed path
    br = svc.stats()["breakers"]["pallas"]
    emit("serve/chaos", 0.0,
         {"requests": n_chaos,
          "degraded": svc.counters["degraded_dispatches"] - deg0,
          "fallbacks": telemetry.FALLBACK_COUNTS["fault:pallas->xla"] - fb0,
          "breaker_opens": telemetry.BREAKER_COUNTS["pallas:open"],
          "short_circuits": telemetry.BREAKER_COUNTS["pallas:short_circuit"],
          "breaker_open": int(br["state"] != "closed")})

    # -- recovery: cooldown elapses, the half-open probe closes the breaker
    time.sleep(0.06)
    for i in range(4):
        svc.submit(*structures[i % n_structs])
        svc.step()
    br = svc.stats()["breakers"]["pallas"]
    emit("serve/recovery", 0.0,
         {"breaker_closed": int(br["state"] == "closed"),
          "closes": telemetry.BREAKER_COUNTS["pallas:close"],
          "reopens": telemetry.BREAKER_COUNTS["pallas:reopen"],
          "completed_total": svc.counters["completed"]})


def bench_obs(quick: bool = False):
    """Observability overhead gate (BENCH_obs_*.json).

    The PR-9 contract is "tracing off costs nothing measurable on the pinned
    replay hot path". Rows:

      obs/span_off      — unit cost of one *disabled* span() call (amortized
                          over 10k calls): the only thing tracing-off adds
                          per span site
      obs/replay_off    — the pinned replay with tracing off. Its
                          ``off_overhead`` derived metric is the CI gate:
                          span-site count on the replay path x the measured
                          disabled-span unit cost, as a fraction of the
                          replay latency (must stay <= 0.02). The row also
                          carries ``dispatch_identical`` — a telemetry diff
                          over the timed loop proving zero added traces and
                          zero added hashes
      obs/replay_traced — the same replay with tracing ON (informational:
                          what turning the layer on costs)
      obs/sample_trace  — a traced mini chaos run through ``SparseService``
                          (kernel:pallas armed, then recovery) exported as
                          Chrome trace-event JSON to trace_obs_sample.json;
                          the row counts exported spans and flight-recorder
                          events (both must be nonzero — the artifact CI
                          uploads next to the BENCH json)
    """
    from repro import obs
    from repro.core import telemetry
    from repro.runtime import faults
    from repro.serve import SparseService

    obs.set_tracing("off")
    a = random_csr(256, 256, 4.0, 71)
    b = random_csr(256, 256, 4.0, 72)
    ex = ReuseExecutor.from_matrices(a, b, plan_cache=PlanCache())
    jax.block_until_ready(ex.apply(a.values, b.values))  # warm the dispatch

    # dispatch identity: the timed tracing-off loop must bump zero trace and
    # zero hash counters (the telemetry-asserted half of the contract)
    before = telemetry.snapshot()
    us_off, _ = timeit(lambda: ex.apply(a.values, b.values))
    delta = telemetry.diff(before, telemetry.snapshot())
    identical = int("trace" not in delta and "hash" not in delta)

    n = 10_000
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("bench.noop"):
            pass
    span_off_us = (time.perf_counter() - t0) * 1e6 / n
    # the replay path crosses one enabled() check in the executor; price two
    # full disabled span() calls per replay to stay conservative
    spans_per_replay = 2
    off_overhead = spans_per_replay * span_off_us / us_off
    emit("obs/span_off", span_off_us, {"calls": n})
    emit("obs/replay_off", us_off,
         {"off_overhead": off_overhead, "dispatch_identical": identical,
          "spans_per_replay": spans_per_replay})

    obs.set_tracing("on")
    obs.clear()
    us_on, _ = timeit(lambda: ex.apply(a.values, b.values))
    emit("obs/replay_traced", us_on, {"traced_ratio": us_on / us_off})

    # sample artifact: a traced chaos mini-run through the serving tier
    obs.reset_obs()
    obs.set_tracing("on")
    sa = random_csr(48, 48, 3.0, 73)
    sb = random_csr(48, 32, 3.0, 74)
    svc = SparseService(backend="pallas", max_batch=2, breaker_threshold=3,
                        retries=1, sleep=lambda _: None)
    with faults.failpoint("kernel:pallas"):
        svc.submit(sa, sb)
        svc.step()  # faulting fast path: ladder fallback, recorder event
    for _ in range(3):
        svc.submit(sa, sb)
        svc.step()
    path = "trace_obs_sample.json"
    payload = obs.export_chrome_trace(path)
    rec_events = len(obs.default_recorder().events())
    emit("obs/sample_trace", 0.0,
         {"trace_events": len(payload["traceEvents"]),
          "recorder_events": rec_events,
          "fallbacks": telemetry.FALLBACK_COUNTS["fault:pallas->xla"]})
    obs.set_tracing(None)  # back to the $REPRO_TRACE default
    obs.reset_obs()


def bench_train_smoke():
    """End-to-end LM substrate: smoke-model training step throughput."""
    from repro.configs import get_config
    from repro.data import SyntheticLMDataset
    from repro.models import NO_SHARDING, init_params
    from repro.train import AdamWConfig, adamw_init, make_train_step

    for arch in ("llama3.2-1b", "qwen3-moe-30b-a3b", "mamba2-2.7b"):
        cfg = get_config(arch, smoke=True)
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw_init(params)
        data = SyntheticLMDataset(vocab_size=cfg.vocab_size, seq_len=64,
                                  global_batch=4)
        step = jax.jit(make_train_step(cfg, NO_SHARDING, AdamWConfig()))
        batch = {k: jnp.asarray(v) for k, v in data.get_batch(0).items()}

        def run(p, o):
            p2, o2, m = step(p, o, batch)
            return m["loss"]

        us, _ = timeit(lambda: run(params, opt))
        toks = 4 * 64
        emit(f"train_smoke/{arch}", us,
             {"tokens_per_s": toks / (us * 1e-6)})


# Self-contained benches addressable via --bench (no cross-bench inputs).
# Each callable takes the --quick flag (most ignore it; bench_accumulators
# shrinks its regime list).
BENCHES = {
    "compile": lambda quick: bench_compile(),
    "reuse": lambda quick: bench_reuse(),
    "reuse_batched": lambda quick: bench_reuse_batched(),
    "accumulators": bench_accumulators,
    "autotune": bench_autotune,
    "dist": lambda quick: bench_dist(),
    "guard": bench_guard,
    "serve": bench_serve,
    "obs": bench_obs,
    "distributed": lambda quick: bench_distributed(),
    "train_smoke": lambda quick: bench_train_smoke(),
}


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--quick", action="store_true",
        help="CI smoke subset: 2 suite cases; compile, reuse and "
             "batched-reuse benches only",
    )
    parser.add_argument(
        "--bench", action="append", metavar="NAME", default=None,
        choices=sorted(BENCHES),
        help="run only the named self-contained bench(es); repeatable. "
             "Combines with --quick (e.g. the CI accumulator artifact runs "
             "--quick --bench accumulators)",
    )
    parser.add_argument(
        "--json", metavar="PATH", default=None,
        help="also write results as machine-readable JSON to PATH",
    )
    parser.add_argument(
        "--fit-thresholds", metavar="BENCH_JSON", default=None,
        help="subcommand: fit per-backend autotuner thresholds from a "
             "previously archived benchmark payload (needs accumulators/* "
             "rows), write the TunedThresholds table to --json, and exit "
             "without running any benches",
    )
    parser.add_argument(
        "--devices", type=int, default=0, metavar="N",
        help="force an N-device host platform (CPU shard_map benches); "
             "0 keeps the platform's real device count",
    )
    args = parser.parse_args(argv)
    from repro.compile_cache import place_compilation_cache

    place_compilation_cache()
    if args.fit_thresholds:
        from repro.core import fit_thresholds

        if not args.json:
            parser.error("--fit-thresholds requires --json OUT (the path "
                         "the fitted TunedThresholds table is written to)")
        with open(args.fit_thresholds) as f:
            payload = json.load(f)
        table = fit_thresholds(payload, source=args.fit_thresholds)
        table.save(args.json)
        for bkey, fit in sorted(table.fits.items()):
            print(f"fit,{bkey},avg_row_flops_cutoff="
                  f"{fit.avg_row_flops_cutoff:.6g},n_points={fit.n_points}")
        if not table.fits:
            print("# no accumulators/* rows with dense_acc+lp_hash arms in "
                  f"{args.fit_thresholds}; wrote an empty table")
        print(f"# wrote {args.json} ({len(table.fits)} backend fits)")
        return
    if args.devices > 1:
        # must land before jax touches its backend (lazy: nothing above
        # builds arrays) — same mechanism the distributed tests use
        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.devices}").strip()
    CASES[:] = list(suite())[:2] if args.quick else list(suite())
    print("name,us_per_call,derived")
    if args.bench:
        for name in args.bench:
            BENCHES[name](args.quick)
    elif args.quick:
        bench_compile()
        bench_reuse()
        bench_reuse_batched()
        bench_dist()
    else:
        results = bench_methods()
        bench_profile(results)
        bench_compression()
        bench_reuse()
        bench_reuse_batched()
        bench_compile()
        bench_accumulators()
        bench_fm_groups(results)
        bench_distributed()
        bench_dist()
        bench_guard()
        bench_serve()
        bench_train_smoke()
    print(f"# {len(ROWS)} rows")
    if args.json:
        stamp = _env_stamp()
        payload = {
            "schema": 1,
            "quick": bool(args.quick),
            "jax_version": stamp["jax_version"],
            "backend": stamp["backend"],
            "platform": stamp["platform"],
            "device_count": jax.device_count(),
            "rows": RESULTS,
        }
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"# wrote {args.json} ({len(RESULTS)} rows)")


if __name__ == "__main__":
    main()
