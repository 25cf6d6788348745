"""Public jit'd wrappers around the Pallas kernels.

These own the format plumbing (CSR -> ELL / bitmask, lane padding) and the
backend dispatch: on non-TPU backends the kernels run in interpret mode
(Pallas lowers only to TPU), so the same call sites work on the CPU test rig
and on real hardware. ``impl="xla"`` falls back to the pure-jnp references
— the dry-run path, since the CPU dry-run cannot lower TPU kernels.

Numeric-phase kernel selection is the paper's GPU rule
(``core.meta.choose_kernel``): ``kernel="auto"`` routes modest rows to the
dense-tile kernel (``dense_acc``) and flop-heavy rows (avg row flops >= 256)
to the LP-hash kernel (``flat_lp``) — and forces the ``xla`` reference path
for f64/int value dtypes, since the Pallas kernels accumulate in f32.
``KERNEL_COUNTS`` records every resolved dispatch so tests and benchmarks
can assert the routing (e.g. that ``flat_lp`` no longer lands on the dense
accumulator).
"""
from __future__ import annotations

import functools
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import bitmask_rows, flops_stats
from repro.core.meta import (DEFAULT_PAD_POLICY, choose_kernel,
                             f32_accumulation_ok, round_capacity)
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.grouped_matmul import TM, grouped_matmul
from repro.kernels.limits import ell_misfit
from repro.kernels.spgemm_lp import spgemm_lp_bucketed
from repro.kernels.spgemm_numeric import spgemm_numeric_bucketed
from repro.kernels.spgemm_symbolic import spgemm_symbolic_bucketed
from repro.sparse.formats import CSR, csr_to_ell

NUMERIC_KERNELS = ("auto", "dense_acc", "flat_lp", "xla")

# Dispatch telemetry: resolved kernel name per numeric_values call.
KERNEL_COUNTS: Counter = Counter()


def reset_kernel_counts() -> None:
    KERNEL_COUNTS.clear()


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _bucketed(widths, pad_policy: str | None) -> tuple[int, ...]:
    """ELL widths as the bucketed kernel wrappers pad them."""
    policy = DEFAULT_PAD_POLICY if pad_policy is None else pad_policy
    return tuple(round_capacity(w, policy) for w in widths)


def numeric_kernel_misfit(kname: str, a: CSR, b: CSR,
                          widths: tuple[int, int, int]) -> str | None:
    """Why ELL kernel ``kname`` cannot take this problem on the chip (see
    ``kernels.limits``), or None; the XLA reference always fits."""
    if kname == "xla":
        return None
    r_a, r_b, r_c = widths
    return ell_misfit(kname, m=a.m, n=b.m, r_a=r_a, r_b=r_b, r_c=r_c, k=b.k)


def resolve_numeric_kernel(a: CSR, b: CSR, kernel: str = "auto",
                           fm: int | None = None,
                           widths: tuple[int, int, int] | None = None) -> str:
    """Resolve ``kernel`` to a concrete numeric-phase implementation.

    "auto" applies ``core.meta.choose_kernel`` (the avg-row-flops rule,
    static or fitted — see ``core.autotune``) after the dtype guard: f64/int
    accumulation cannot run on the f32 Pallas kernels, so those inputs
    resolve to "xla" regardless of regime. When the autotuner holds a
    measured winner for this problem's structure-stats bucket (recorded by
    ``numeric_values(..., tune="measure")``), that winner takes precedence
    over the threshold rule — measured beats fitted beats static.

    fm: the total multiplication count, if the caller already has it (e.g.
    from ``spgemm`` stats). Computing it here costs an O(nnz) ``flops_stats``
    pass plus a device->host sync per call — replay loops over a pinned
    structure should pass their constant ``fm`` instead of re-paying that.

    "auto" never resolves to a kernel whose size bound (``kernels.limits``)
    the problem exceeds: such problems resolve to "xla". widths: the
    bucketed ELL widths (rA, rB, rC) when the caller has them; by default
    rA/rB come from the operands and rC is taken as 0.
    """
    from repro.core import autotune  # lazy: avoid kernels<->core cycle

    from repro.runtime.validate import SpgemmConfigError  # cycle-free

    if kernel not in NUMERIC_KERNELS:
        raise SpgemmConfigError(
            f"unknown kernel {kernel!r}; expected one of {NUMERIC_KERNELS}")
    f32_ok = f32_accumulation_ok(a.values.dtype, b.values.dtype)
    if kernel != "auto":
        # an explicit Pallas kernel the dtypes cannot run correctly must fail
        # loudly — silently accumulating f64/int in f32 would corrupt results
        if kernel != "xla" and not f32_ok:
            raise SpgemmConfigError(
                f"kernel={kernel!r} accumulates in f32 and cannot take "
                f"{a.values.dtype}/{b.values.dtype} operands exactly; "
                f"use kernel='xla' (what 'auto' resolves to for them)")
        return kernel
    if not f32_ok:
        return "xla"
    if fm is None:
        fm = int(flops_stats(a, b.row_nnz())[0])
    measured = autotune.lookup_measured(autotune.bucket_key(
        a.m, b.k, fm, a.values.dtype, b.values.dtype, table="numeric"))
    pick = measured if measured is not None else choose_kernel(a, b, {"fm": fm})
    if widths is None:
        widths = _bucketed(
            [max(int(np.max(np.diff(np.asarray(x.indptr)))), 1)
             for x in (a, b)] + [0], None)
    return "xla" if numeric_kernel_misfit(pick, a, b, widths) else pick


def symbolic_rowsizes(a: CSR, b: CSR, *, pad_policy: str | None = None) -> jax.Array:
    """Kernel-backed symbolic phase: (m,) row sizes of C = A*B. ELL widths go
    through the same capacity buckets as the host driver, so similarly-sized
    matrices reuse one compiled kernel."""
    ell = csr_to_ell(a)
    bm = bitmask_rows(b)
    pad = (-bm.shape[1]) % 128
    if pad:
        bm = jnp.pad(bm, ((0, 0), (0, pad)))
    return spgemm_symbolic_bucketed(
        ell.indices, ell.row_nnz, bm, pad_policy=pad_policy,
        interpret=_interpret(),
    )


def numeric_values(a: CSR, b: CSR, c_idx: jax.Array, c_nnz: jax.Array, *,
                   pad_policy: str | None = None, kernel: str = "auto",
                   fm: int | None = None,
                   tune: str | None = None,
                   on_kernel_failure: str = "fallback") -> jax.Array:
    """Kernel-backed numeric phase: ELL-layout values of C at the symbolic
    structure ``c_idx``/``c_nnz`` (the Reuse entry point). Widths bucketed.

    kernel: "auto" (meta-algorithm rule + dtype guard — see
    ``resolve_numeric_kernel``), "dense_acc" (dense-tile Pallas kernel),
    "flat_lp" (LP-hash Pallas kernel), or "xla" (pure-jnp reference; the
    f64/int fallback). Replay loops should pass a concrete ``kernel`` or a
    precomputed ``fm`` — "auto" without ``fm`` pays an O(nnz) flops pass and
    a host sync per call to apply the selection rule.

    tune="measure" (with kernel="auto" only) replaces the threshold rule by
    a first-sight micro-bench: the eligible kernels are timed on these real
    operands, the winner runs and is recorded in the autotuner's bucket
    table — later same-bucket calls (through here *or* through
    ``resolve_numeric_kernel``) dispatch it with zero re-tuning.

    on_kernel_failure: "fallback" (default) walks the degradation ladder on
    any kernel exception — measured/resolved pick, then the static
    ``choose_kernel`` pick (auto modes only), then the exact-XLA reference —
    recording each step in ``telemetry.FALLBACK_COUNTS`` as
    ``"fault:<failed>-><next>"``; "raise" converts the first failure into a
    typed ``KernelFallbackError``. The ladder catches *outside* jit, so a
    failed trace is never cached and the fallback compiles cleanly.
    """
    from repro.core import autotune  # lazy: avoid kernels<->core cycle
    from repro.runtime import faults  # lazy: keep kernels import-light
    from repro.runtime.validate import (KernelFallbackError,
                                        SpgemmConfigError, SpgemmError)

    autotune.validate_tune(tune)
    if tune == "measure" and kernel != "auto":
        raise SpgemmConfigError(
            f"tune='measure' requires kernel='auto' (got kernel={kernel!r}):"
            f" measure mode picks the kernel empirically, an explicit pin "
            f"contradicts it")
    if on_kernel_failure not in ("fallback", "raise"):
        raise SpgemmConfigError(
            f"on_kernel_failure must be 'fallback' or 'raise', got "
            f"{on_kernel_failure!r}")
    ea = csr_to_ell(a)
    eb = csr_to_ell(b)

    def run(kname: str) -> jax.Array:
        faults.check(f"kernel:{kname}")
        if kname == "xla":
            return ref.spgemm_numeric_ref(
                ea.indices, ea.values, eb.indices, eb.values, c_idx, c_nnz,
                b.k)
        if kname == "flat_lp":
            return spgemm_lp_bucketed(
                ea.indices, ea.values, ea.row_nnz, eb.indices, eb.values,
                eb.row_nnz, c_idx, c_nnz, pad_policy=pad_policy,
                interpret=_interpret(),
            )
        return spgemm_numeric_bucketed(
            ea.indices, ea.values, ea.row_nnz, eb.indices, eb.values,
            c_idx, c_nnz, k=b.k, pad_policy=pad_policy,
            interpret=_interpret(),
        )

    # the auto paths need fm anyway (selection rule / bucket key); computing
    # it up front also prices the ladder's static rung at zero extra passes
    if kernel == "auto" and fm is None:
        fm = int(flops_stats(a, b.row_nnz())[0])
    widths = _bucketed((ea.indices.shape[1], eb.indices.shape[1],
                        c_idx.shape[1]), pad_policy)

    def fits(kname: str) -> bool:
        return numeric_kernel_misfit(kname, a, b, widths) is None

    if tune == "measure":
        bkey = autotune.bucket_key(a.m, b.k, fm, a.values.dtype,
                                   b.values.dtype, table="numeric")
        resolved = autotune.lookup_measured(bkey)
        if resolved is not None and not fits(resolved):
            resolved = "xla"
        if resolved is None:
            # candidate set = the dtype-eligible rows of the selection table
            # that fit the chip's kernel bounds
            cands = {"xla": lambda: run("xla")}
            if f32_accumulation_ok(a.values.dtype, b.values.dtype):
                for kname in ("dense_acc", "flat_lp"):
                    if fits(kname):
                        cands[kname] = lambda kn=kname: run(kn)
            resolved, _ = autotune.measure_and_record(bkey, cands)
    else:
        resolved = resolve_numeric_kernel(a, b, kernel, fm=fm, widths=widths)
        if (kernel == "auto" and resolved == "xla"
                and not f32_accumulation_ok(a.values.dtype, b.values.dtype)):
            from repro.core.telemetry import FALLBACK_COUNTS  # lazy: cycle

            FALLBACK_COUNTS["dtype:numeric_auto->xla"] += 1

    # degradation ladder: resolved/measured pick -> static choose_kernel
    # pick (auto modes only) -> exact-XLA reference, deduplicated in order
    ladder = [resolved]
    if kernel == "auto" or tune == "measure":
        static_pick = choose_kernel(a, b, {"fm": fm})
        if static_pick not in ladder and fits(static_pick):
            ladder.append(static_pick)
    if "xla" not in ladder:
        ladder.append("xla")

    from repro.obs import trace as obs_trace  # stdlib-only module, cheap

    for i, kname in enumerate(ladder):
        try:
            with obs_trace.span("numeric.kernel", kernel=kname, rung=i):
                out = run(kname)
        except SpgemmError:
            raise  # typed validation errors are not kernel failures
        except Exception as e:
            from repro.obs import recorder  # lazy: failure path only

            if on_kernel_failure == "raise":
                err = KernelFallbackError(
                    f"numeric kernel {kname!r} failed and "
                    f"on_kernel_failure='raise'")
                recorder.note_error(err, kernel=kname, site="numeric_values",
                                    trace_id=obs_trace.current_trace_id())
                raise err from e
            if i + 1 >= len(ladder):
                err = KernelFallbackError(
                    "numeric kernel ladder exhausted "
                    f"({' -> '.join(ladder)})")
                recorder.note_error(err, kernel=kname, site="numeric_values",
                                    trace_id=obs_trace.current_trace_id())
                raise err from e
            from repro.core.telemetry import FALLBACK_COUNTS  # lazy: cycle

            FALLBACK_COUNTS[f"fault:{kname}->{ladder[i + 1]}"] += 1
            recorder.record("fallback", kernel=kname,
                            fallback=f"{kname}->{ladder[i + 1]}",
                            verdict="fallback", site="numeric_values",
                            trace_id=obs_trace.current_trace_id())
            continue
        KERNEL_COUNTS[kname] += 1
        return out


def pallas_spgemm(a: CSR, b: CSR, *,
                  kernel: str = "auto") -> tuple[jax.Array, jax.Array, jax.Array]:
    """Full two-phase kernel pipeline. Returns (c_nnz, c_idx, c_val) with C
    in ELL layout; the host decides rC between the phases (two-phase
    contract). Structure extraction uses the core sort path; the numeric
    kernel follows ``kernel`` (default: the meta-algorithm rule)."""
    from repro.core.spgemm import host_fm_cap, numeric_fresh

    sizes = symbolic_rowsizes(a, b)
    r_c = max(int(jnp.max(sizes)), 1)
    # structure via the core path (host-mediated static sizes); one
    # flops_stats pass serves both the expansion cap and kernel selection
    fm = int(flops_stats(a, b.row_nnz())[0])
    fm_cap = host_fm_cap(a, b, fm=fm)
    nnz = int(jnp.sum(sizes))
    nnz_cap = max(-(-nnz // 8) * 8, 8)
    c, _ = numeric_fresh(a, b, fm_cap, nnz_cap)
    # CSR -> ELL structure for the kernel
    c_ell = csr_to_ell(
        CSR(indptr=c.indptr, indices=c.indices, values=c.values, shape=c.shape),
        r_pad=r_c,
    )
    vals = numeric_values(a, b, c_ell.indices, c_ell.row_nnz, kernel=kernel,
                          fm=fm)
    return c_ell.row_nnz, c_ell.indices, vals


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True,
              window: int | None = None, softcap: float | None = None,
              impl: str = "auto", segment_pos=None) -> jax.Array:
    """Multi-head attention over (H, T, D) tensors with GQA broadcast.

    impl: "pallas" (TPU kernel / interpret), "xla" (reference einsum path —
    used by the dry-run), "auto" (pallas on TPU, xla elsewhere).
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla" or segment_pos is not None:
        return ref.flash_attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap,
            segment_pos=segment_pos,
        )
    tq = q.shape[1]
    bq = min(128, tq)
    bk = min(128, k.shape[1])
    return flash_attention(
        q, k, v, causal=causal, window=window, softcap=softcap,
        block_q=bq, block_k=bk, interpret=_interpret(),
    )


def expert_matmul(x: jax.Array, w: jax.Array, block_expert: jax.Array, *,
                  impl: str = "auto") -> jax.Array:
    """Grouped (expert) matmul for expert-sorted token blocks of width TM."""
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl == "xla":
        gid = jnp.repeat(block_expert, TM, total_repeat_length=x.shape[0])
        return ref.grouped_matmul_ref(x, w, gid)
    return grouped_matmul(x, w, block_expert, interpret=_interpret())
