"""Two-phase SpGEMM (paper Alg. 2/3) adapted to XLA's static-shape regime.

Phase contract (identical to the paper's host/device split):
  1. ``symbolic``  — jitted; returns per-row nnz of C (no FLOPs). Uses the
     compressed matrix when the CF <= 0.85 rule fires.
  2. host         — materializes ``indptr`` and the concrete nnz(C).
  3. ``numeric``  — jitted at that size; fills C. The first run also emits a
     ``SpgemmPlan`` (structure + product->slot map). Re-running with new
     values but the same structure (the paper's *Reuse* case) is a pure
     gather/segment-sum — no hashing, no sort, no recompile.

Accumulation strategy per the TPU adaptation (DESIGN.md §2): sorted-segment
accumulation (Thread-Flat-Parallel semantics — associative, atomic-free) and
dense scatter accumulation (KKDENSE). Hash accumulators live in
``core/accumulators.py`` (jittable LL/LP ports) and ``kernels/`` (Pallas).

Pipeline & Reuse
----------------
A fresh ``spgemm()`` runs a *single-expansion* pipeline: one
``expand_products`` call and **one** sort feed both the symbolic row counts
and the numeric ``SpgemmPlan``. The sort packs ``(row, col)`` into a single
integer key and argsorts once (``_single_sort_order``) — replacing the two
stable passes of ``lexsort`` — and its contract is exact equivalence with
``jnp.lexsort((col, row))``: stable, lexicographic by row then column. The
stages are:

  ``expand_and_sort``  (jit, static fm_cap)  -> sorted products + row sizes
  host                                       -> nnz(C), bucketed nnz_cap
  ``plan_from_sorted`` (jit, static nnz_cap) -> SpgemmPlan (v2, precomposed)
  ``numeric_reuse``    (jit)                 -> C values

The plan is *precomposed* (v2): ``plan_from_sorted`` folds the sort
permutation into the slot maps at build time (``a_slot_s = a_slot[order]``,
``b_slot_s = b_slot[order]``) and folds validity into sentinel ``seg_ids``
(padding products point at slot ``nnz_cap`` and are dropped by the scatter).
A numeric replay is therefore two gathers + one ``indices_are_sorted``
segment-sum — no O(f_m) permutation pass, no mask — and accumulates in
``jnp.result_type(a_values, b_values)`` so mixed-precision operands never
silently downcast.

Static capacities (``fm_cap``, ``nnz_cap``, and the CSR buffer caps of A and
B) are rounded up to geometric x2 buckets under ``core.meta.round_capacity``
(knob: ``pad_policy``, default "pow2"), so matrices of similar size share one
compiled executable instead of each minting its own. On top of that,
``spgemm()`` consults a structure-keyed LRU plan cache
(``core/plan_cache.py``): a repeated structure with new values skips the
expansion and sort entirely and replays ``numeric_reuse`` — the paper's Reuse
case with zero caller bookkeeping and zero recompiles. For reuse-dominated
workloads (multigrid setup, graph analytics with changing weights),
``core/executor.py`` goes one step further: a ``ReuseExecutor`` pins a plan
once (one structure hash, ever) and replays it as a single jitted dispatch —
optionally batched over stacked value arrays and optionally through the
Pallas ``kernels/segsum_reuse.py`` flat-parallel kernel.

Note the dense method returns ``plan=None``: the KKDENSE path has no
product->slot map, so it offers no Reuse fast path — use ``method="sparse"``
(or an executor) when structure reuse matters. ``TRACE_COUNTS`` records
retraces of every jitted stage so benchmarks and tests can assert the
one-expansion/one-sort contract and the bucketing's recompile savings.
"""
from __future__ import annotations

from collections import Counter
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compression import (
    CompressedMatrix,
    compress_matrix,
    compression_decision,
    flops_stats,
)
from repro.core.meta import DEFAULT_PAD_POLICY, round_capacity
from repro.core.utils import popcount, segmented_scan, segment_ends
from repro.obs.trace import span, trace_scope
from repro.sparse.formats import CSR, csr_row_ids

# Retrace telemetry: each jitted stage bumps its counter at *trace* time only,
# so the counts measure XLA recompiles, not calls. Benchmarks (bench_compile)
# and tests read these to verify the single-expansion contract and that
# capacity bucketing actually shares executables.
TRACE_COUNTS: Counter = Counter()


def _note_trace(name: str) -> None:
    TRACE_COUNTS[name] += 1


def reset_trace_counts() -> None:
    TRACE_COUNTS.clear()


class ProductExpansion(NamedTuple):
    """Flattened multiplication space: the paper's Thread-Flat-Parallel view.

    Product t multiplies A-slot ``a_slot[t]`` with B-slot ``b_slot[t]`` and
    lands in C row ``row[t]``, column ``col[t]``. ``valid`` masks padding.
    """

    row: jax.Array
    col: jax.Array
    a_slot: jax.Array
    b_slot: jax.Array
    valid: jax.Array


class SortedExpansion(NamedTuple):
    """One expansion + one sort: everything both phases need.

    Produced by ``expand_and_sort``; consumed by the host (``row_sizes`` ->
    nnz(C)) and by ``plan_from_sorted`` (everything else). ``heads`` marks the
    first product of each distinct (row, col) group in sorted order;
    ``seg_ids`` maps each sorted product to its C slot.
    """

    order: jax.Array  # (fm_cap,) int32 — the single sort permutation
    rows_s: jax.Array  # (fm_cap,) int32 — rows in sorted order
    cols_s: jax.Array  # (fm_cap,) int32 — cols in sorted order
    valid_s: jax.Array  # (fm_cap,) bool — validity in sorted order
    heads: jax.Array  # (fm_cap,) bool — group heads (padding mints none)
    seg_ids: jax.Array  # (fm_cap,) int32 — sorted product -> C slot
    a_slot: jax.Array  # (fm_cap,) int32 — unsorted, from the expansion
    b_slot: jax.Array  # (fm_cap,) int32
    valid: jax.Array  # (fm_cap,) bool
    row_sizes: jax.Array  # (m,) int32 — the symbolic output


class SpgemmPlan(NamedTuple):
    """Cached numeric plan enabling the Reuse fast path (v2, precomposed).

    The sort permutation is composed into the slot maps at plan-build time:
    ``a_slot_s``/``b_slot_s`` are already in sorted product order, and
    ``seg_ids`` folds validity in as a sentinel (padding products map to slot
    ``nnz_cap``, which the ``mode="drop"`` scatter discards). A replay is two
    gathers + one sorted segment-sum — no permutation gather, no mask.
    """

    indptr: jax.Array  # (m+1,) int32 — C row pointers
    indices: jax.Array  # (nnz_cap,) int32 — C columns, sorted per row
    seg_ids: jax.Array  # (fm_cap,) int32 — sorted product -> C slot
    #                     (nnz_cap sentinel for padding -> dropped)
    a_slot_s: jax.Array  # (fm_cap,) int32 — A slot per sorted product
    b_slot_s: jax.Array  # (fm_cap,) int32 — B slot per sorted product
    shape: tuple  # (m, k) of C


def _single_sort_order(rows: jax.Array, keys: jax.Array, m: int,
                       key_bound: int | None) -> jax.Array:
    """Stable sort permutation by (rows, keys) in ONE pass.

    Packs the pair into a single integer key and argsorts once — the
    replacement for ``jnp.lexsort((keys, rows))``'s two stable passes. Rows
    may carry the padding sentinel ``m``; keys must lie in [0, key_bound).
    Ordering is exactly lexsort's: stable, by row then key.

    Width selection is static (m, key_bound are trace-time ints): int32
    packing when (m+1)*key_bound fits, int64 when x64 is enabled, otherwise a
    single fused ``lax.sort`` on (rows, keys, index) — still one sort pass,
    never two.
    ``key_bound=None`` means "unknown at trace time": use the fused sort.
    """
    span = None if key_bound is None else (m + 1) * key_bound  # rows pad to m
    if span is not None and span <= np.iinfo(np.int32).max:
        packed = rows.astype(jnp.int32) * jnp.int32(key_bound) + keys.astype(jnp.int32)
        return jnp.argsort(packed, stable=True).astype(jnp.int32)
    if span is not None and jax.config.jax_enable_x64 and span <= np.iinfo(np.int64).max:
        packed = rows.astype(jnp.int64) * jnp.int64(key_bound) + keys.astype(jnp.int64)
        return jnp.argsort(packed, stable=True).astype(jnp.int32)
    # the index as a third key makes every key unique, so an unstable sort
    # gives exactly the stable order; the TPU compiler builds an unstable
    # three-key sort in about half the time of a stable two-key one
    iota = jnp.arange(rows.shape[0], dtype=jnp.int32)
    _, _, order = jax.lax.sort(
        (rows.astype(jnp.int32), keys.astype(jnp.int32), iota),
        num_keys=3,
        is_stable=False,
    )
    return order


@partial(jax.jit, static_argnames=("fm_cap",))
def expand_products(a: CSR, b: CSR, fm_cap: int) -> ProductExpansion:
    """Enumerate all f_m multiplications with static capacity ``fm_cap``.

    For product t: binary-search the owning A-slot in the exclusive prefix of
    per-A-slot product counts, then offset into B's row. Fully vectorized.
    """
    _note_trace("expand_products")
    b_row_nnz = b.row_nnz()
    a_valid = a.valid_mask()
    per_slot = jnp.where(
        a_valid, b_row_nnz[jnp.minimum(a.indices, b.m - 1)], 0
    ).astype(jnp.int32)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(per_slot).astype(jnp.int32)]
    )  # (nnzA+1,)
    t = jnp.arange(fm_cap, dtype=jnp.int32)
    a_slot = (
        jnp.searchsorted(offsets, t, side="right").astype(jnp.int32) - 1
    ).clip(0, a.nnz_cap - 1)
    within = t - offsets[a_slot]
    valid = t < offsets[-1]
    j = a.indices[a_slot]
    b_slot = (b.indptr[jnp.minimum(j, b.m - 1)] + within).clip(0, b.nnz_cap - 1)
    rows = csr_row_ids(a.indptr, a.nnz_cap)[a_slot]
    col = b.indices[b_slot]
    return ProductExpansion(
        row=jnp.where(valid, rows, a.m),  # pad rows to m -> sorts to the end
        col=jnp.where(valid, col, 0),
        a_slot=a_slot,
        b_slot=b_slot,
        valid=valid,
    )


@partial(jax.jit, static_argnames=("fm_cap",))
def expand_and_sort(a: CSR, b: CSR, fm_cap: int) -> SortedExpansion:
    """The fused front half of a fresh multiply: ONE expansion, ONE sort.

    Returns sorted products plus per-row distinct-column counts — the
    symbolic phase's answer — so the driver never expands or sorts again for
    the numeric plan.
    """
    _note_trace("expand_and_sort")
    # the named scopes mark each phase's device ops in a profile (their
    # ``tf_op`` path); they change op metadata only, never the program
    with jax.named_scope("spgemm_expand"):
        ex = expand_products(a, b, fm_cap)
    with jax.named_scope("spgemm_sort"):
        order = _single_sort_order(ex.row, ex.col, a.m, b.k)
    with jax.named_scope("spgemm_permute"):
        rows_s = ex.row[order]
        cols_s = ex.col[order]
        valid_s = ex.valid[order]
        heads = jnp.concatenate(
            [
                jnp.ones((1,), jnp.bool_),
                (rows_s[1:] != rows_s[:-1]) | (cols_s[1:] != cols_s[:-1]),
            ]
        )
        heads = heads & valid_s  # padding (row==m) groups don't mint slots
        seg_ids = (jnp.cumsum(heads.astype(jnp.int32)) - 1).clip(0).astype(jnp.int32)
        row_sizes = jnp.zeros((a.m,), jnp.int32).at[jnp.minimum(rows_s, a.m - 1)].add(
            heads.astype(jnp.int32), mode="drop"
        )
    return SortedExpansion(
        order=order,
        rows_s=rows_s,
        cols_s=cols_s,
        valid_s=valid_s,
        heads=heads,
        seg_ids=seg_ids,
        a_slot=ex.a_slot,
        b_slot=ex.b_slot,
        valid=ex.valid,
        row_sizes=row_sizes,
    )


@partial(jax.jit, static_argnames=("k", "nnz_cap"))
def plan_from_sorted(sx: SortedExpansion, k: int, nnz_cap: int) -> SpgemmPlan:
    """Back half of a fresh multiply: C structure + reuse plan, no re-sort.

    Precomposes the sort permutation into the slot maps (plan v2): the one
    extra gather pair here is paid once per *structure*, saving one O(f_m)
    permutation gather on every numeric replay.
    """
    _note_trace("plan_from_sorted")
    m = sx.row_sizes.shape[0]
    c_indices = jnp.zeros((nnz_cap,), jnp.int32).at[sx.seg_ids].max(
        jnp.where(sx.heads, sx.cols_s, 0), mode="drop"
    )
    indptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(sx.row_sizes).astype(jnp.int32)]
    )
    return SpgemmPlan(
        indptr=indptr,
        indices=c_indices,
        seg_ids=jnp.where(sx.valid_s, sx.seg_ids, nnz_cap),  # padded -> dropped
        a_slot_s=sx.a_slot[sx.order],
        b_slot_s=sx.b_slot[sx.order],
        shape=(m, k),
    )


def host_fm_cap(a: CSR, b: CSR, pad_to: int = 8, fm: int | None = None) -> int:
    """Host-side f_m (total products) rounded up — the static expansion size.

    fm: precomputed product count, if the caller already paid the
    ``flops_stats`` pass (saves its device->host sync)."""
    if fm is None:
        fm = int(flops_stats(a, b.row_nnz())[0])
    return max(-(-fm // pad_to) * pad_to, pad_to)


# --------------------------------------------------------------------------
# Symbolic phase
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("fm_cap", "m", "key_bound"))
def _symbolic_sorted(rows, keys, payload, valid, m: int, fm_cap: int, key_bound: int):
    """Shared core: sort (row, key) pairs, OR payloads per group, count groups
    per row (plain symbolic: payload == popcount 1 per distinct column)."""
    _note_trace("_symbolic_sorted")
    order = _single_sort_order(rows, keys, m, key_bound)
    rows_s, keys_s, valid_s = rows[order], keys[order], valid[order]
    pay_s = payload[order]
    heads = jnp.concatenate(
        [
            jnp.ones((1,), jnp.bool_),
            (rows_s[1:] != rows_s[:-1]) | (keys_s[1:] != keys_s[:-1]),
        ]
    )
    or_scan = segmented_scan(pay_s, heads, jnp.bitwise_or)
    ends = segment_ends(heads) & valid_s
    contrib = jnp.where(ends, popcount(or_scan), 0).astype(jnp.int32)
    sizes = jnp.zeros((m,), jnp.int32).at[jnp.minimum(rows_s, m - 1)].add(
        jnp.where(valid_s, contrib, 0), mode="drop"
    )
    return sizes


@partial(jax.jit, static_argnames=("fm_cap", "m", "key_bound"))
def symbolic_compressed(a: CSR, bc: CompressedMatrix, m: int, fm_cap: int,
                        key_bound: int | None = None) -> jax.Array:
    """Symbolic phase on the compressed B (paper §3.2): expand (row, CSI, CS)
    products, OR the CS masks per (row, CSI), sum popcounts per row.

    key_bound: static bound on CSI values (ceil(k/32)) enabling the packed
    single-key sort; None falls back to the fused multi-key sort."""
    _note_trace("symbolic_compressed")
    bc_row_nnz = bc.row_nnz()
    a_valid = a.valid_mask()
    nb = bc.indptr.shape[0] - 1
    per_slot = jnp.where(
        a_valid, bc_row_nnz[jnp.minimum(a.indices, nb - 1)], 0
    ).astype(jnp.int32)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(per_slot).astype(jnp.int32)]
    )
    t = jnp.arange(fm_cap, dtype=jnp.int32)
    a_slot = (
        jnp.searchsorted(offsets, t, side="right").astype(jnp.int32) - 1
    ).clip(0, a.nnz_cap - 1)
    within = t - offsets[a_slot]
    valid = t < offsets[-1]
    j = jnp.minimum(a.indices[a_slot], nb - 1)
    cap = bc.csi.shape[0]
    b_slot = (bc.indptr[j] + within).clip(0, cap - 1)
    rows = jnp.where(valid, csr_row_ids(a.indptr, a.nnz_cap)[a_slot], m)
    keys = jnp.where(valid, bc.csi[b_slot], 0)
    cs = jnp.where(valid, bc.cs[b_slot], jnp.uint32(0))
    return _symbolic_sorted(rows, keys, cs, valid, m, fm_cap, key_bound=key_bound)


@partial(jax.jit, static_argnames=("fm_cap",))
def symbolic_plain(a: CSR, b: CSR, fm_cap: int) -> jax.Array:
    """Uncompressed symbolic: distinct-column count per row via sort."""
    _note_trace("symbolic_plain")
    ex = expand_products(a, b, fm_cap)
    ones = jnp.where(ex.valid, jnp.uint32(1), jnp.uint32(0))
    return _symbolic_sorted(
        ex.row, ex.col, ones, ex.valid, a.m, fm_cap, key_bound=max(b.k, 1)
    )


@partial(jax.jit, static_argnames=("block_rows",))
def symbolic_dense_bitmask(a_ell, b_bitmask: jax.Array, block_rows: int = 64) -> jax.Array:
    """KKDENSE symbolic: per row-block, gather B's bitmask rows and OR-reduce
    into a dense (block_rows, ceil(k/32)) accumulator — the dense-accumulator
    symbolic with 32x compression. Memory-bounded via lax.map over blocks."""
    m = a_ell.m
    k32 = b_bitmask.shape[1]
    r_pad = a_ell.r_pad
    n_blocks = -(-m // block_rows)
    pad_m = n_blocks * block_rows
    idx = jnp.pad(a_ell.indices, ((0, pad_m - m), (0, 0)))
    rnnz = jnp.pad(a_ell.row_nnz, (0, pad_m - m))
    idx = idx.reshape(n_blocks, block_rows, r_pad)
    rnnz = rnnz.reshape(n_blocks, block_rows)

    def block(args):
        bi, brn = args  # (block_rows, r_pad), (block_rows,)
        masks = b_bitmask[bi.clip(0, b_bitmask.shape[0] - 1)]  # (BR, r_pad, k32)
        live = (
            jnp.arange(r_pad, dtype=jnp.int32)[None, :, None] < brn[:, None, None]
        )
        masks = jnp.where(live, masks, jnp.uint32(0))
        acc = jax.lax.reduce(
            masks, jnp.uint32(0), jnp.bitwise_or, dimensions=(1,)
        )  # (BR, k32)
        return jnp.sum(popcount(acc), axis=-1).astype(jnp.int32)

    sizes = jax.lax.map(block, (idx, rnnz))
    return sizes.reshape(pad_m)[:m]


# --------------------------------------------------------------------------
# Numeric phase
# --------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("fm_cap", "nnz_cap"))
def numeric_fresh(a: CSR, b: CSR, fm_cap: int, nnz_cap: int):
    """First numeric run: discovers C's structure and the product->slot map,
    computes values. Returns (CSR C, SpgemmPlan). Jittable end-to-end (used
    inside shard_map); composes the single-expansion stages inline."""
    _note_trace("numeric_fresh")
    sx = expand_and_sort(a, b, fm_cap)
    plan = plan_from_sorted(sx, b.k, nnz_cap)
    values = numeric_reuse(plan, a.values, b.values)
    c = CSR(indptr=plan.indptr, indices=plan.indices, values=values, shape=(a.m, b.k))
    return c, plan


@jax.jit
def numeric_reuse(plan: SpgemmPlan, a_values: jax.Array, b_values: jax.Array) -> jax.Array:
    """The Reuse case: same structure, new values. Two gathers + one sorted
    segment-sum. No sort, no hash, no permutation pass, no recompile.

    The precomposed plan already orders the slot maps, so padding products
    need no mask: their sentinel ``seg_ids == nnz_cap`` fall off the scatter
    (``mode="drop"``). Accumulates in ``jnp.result_type(a_values, b_values)``
    so mixed-precision operands keep full product precision.
    """
    _note_trace("numeric_reuse")
    acc_dtype = jnp.result_type(a_values, b_values)
    with jax.named_scope("replay_gather"):
        prod = (a_values[plan.a_slot_s].astype(acc_dtype)
                * b_values[plan.b_slot_s].astype(acc_dtype))
    nnz_cap = plan.indices.shape[0]
    with jax.named_scope("replay_scatter"):
        return jnp.zeros((nnz_cap,), acc_dtype).at[plan.seg_ids].add(
            prod, mode="drop", indices_are_sorted=True
        )


def lp_replay_values(plan: SpgemmPlan, a_values: jax.Array,
                     b_values: jax.Array, interpret: bool | None = None):
    """The one LP-position replay dispatch: Pallas LP-hash kernel when the
    operand dtypes can accumulate in f32, the exact XLA ``numeric_reuse``
    otherwise (f64/int). Every LP entry point — ``spgemm(method="lp")``,
    ``numeric_lp``, ``ReuseExecutor(backend="pallas_lp")`` — routes through
    here so the fallback rule can never drift between them.

    interpret: None = interpret off-TPU (Pallas lowers only to TPU).
    Returns (values, backend) with backend in {"pallas", "xla"}.
    """
    from repro.core.meta import f32_accumulation_ok  # cycle-free late import

    if f32_accumulation_ok(a_values.dtype, b_values.dtype):
        from repro.kernels.spgemm_lp import lp_reuse  # cycle-free late import

        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        return lp_reuse(plan, a_values, b_values, interpret=interpret), "pallas"
    return numeric_reuse(plan, a_values, b_values), "xla"


@partial(jax.jit, static_argnames=("fm_cap", "nnz_cap", "interpret"))
def numeric_lp(a: CSR, b: CSR, fm_cap: int, nnz_cap: int,
               interpret: bool = False):
    """KKLP-position numeric phase: structure via the single-expansion
    pipeline, values through the Pallas LP-hash accumulator replay
    (``kernels.spgemm_lp.lp_reuse``; automatic XLA fallback for f64/int).
    Returns (CSR C, SpgemmPlan) — the same contract as ``numeric_fresh``,
    selected by ``choose_kernel``'s ``flat_lp`` branch for flop-heavy
    rows."""
    _note_trace("numeric_lp")
    sx = expand_and_sort(a, b, fm_cap)
    plan = plan_from_sorted(sx, b.k, nnz_cap)
    values, _ = lp_replay_values(plan, a.values, b.values, interpret=interpret)
    c = CSR(indptr=plan.indptr, indices=plan.indices, values=values,
            shape=(a.m, b.k))
    return c, plan


@partial(jax.jit, static_argnames=("fm_cap", "nnz_cap"))
def numeric_dense_acc(a: CSR, b: CSR, fm_cap: int, nnz_cap: int) -> CSR:
    """KKDENSE numeric: scatter all products into a dense (m, k) accumulator,
    then extract the CSR structure with a fixed-size nonzero scan. Chosen by
    the meta-algorithm when k is small (paper: k < 250k). O(m*k) memory —
    exactly the paper's dense-accumulator trade-off."""
    _note_trace("numeric_dense_acc")
    ex = expand_products(a, b, fm_cap)
    vals = jnp.where(ex.valid, a.values[ex.a_slot] * b.values[ex.b_slot], 0)
    dense = jnp.zeros((a.m, b.k), a.dtype)
    dense = dense.at[jnp.minimum(ex.row, a.m - 1), ex.col].add(
        jnp.where(ex.valid, vals, 0), mode="drop"
    )
    # structure mask must come from the *symbolic* structure, not value!=0
    # (cancellation must keep explicit zeros, like the paper's accumulators):
    occupied = jnp.zeros((a.m, b.k), jnp.int32)
    occupied = occupied.at[jnp.minimum(ex.row, a.m - 1), ex.col].max(
        ex.valid.astype(jnp.int32), mode="drop"
    )
    rr, cc = jnp.nonzero(occupied, size=nnz_cap, fill_value=0)
    got = jnp.arange(nnz_cap) < jnp.sum(occupied.astype(jnp.int32))
    values = jnp.where(got, dense[rr, cc], 0)
    indices = jnp.where(got, cc, 0).astype(jnp.int32)
    row_sizes = jnp.sum(occupied.astype(jnp.int32), axis=1)
    indptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(row_sizes).astype(jnp.int32)]
    )
    return CSR(indptr=indptr, indices=indices, values=values, shape=(a.m, b.k))


# --------------------------------------------------------------------------
# Host-level driver (the paper's Algorithm 2)
# --------------------------------------------------------------------------


class SpgemmResult(NamedTuple):
    c: CSR
    plan: SpgemmPlan | None
    stats: dict


def symbolic(a: CSR, b: CSR, compress: str = "auto",
             pad_policy: str = DEFAULT_PAD_POLICY):
    """Paper Alg. 2 lines 1-3. Returns (row_sizes, stats). Host-mediated:
    decides compression by the CF<=0.85 rule and sizes the expansion."""
    stats: dict = {}
    fm, maxrf = (int(x) for x in _fm_scalars(a, b))
    stats["fm"] = fm
    stats["maxrf"] = maxrf
    use_c = False
    cf = cmrf = 1.0
    bc = None
    if compress in ("auto", "always"):
        bc = compress_matrix(b)
        cf, cmrf, use_c = compression_decision(a, b, bc)
        if compress == "always":
            use_c = True
    stats["cf"], stats["cmrf"], stats["compressed"] = cf, cmrf, use_c
    if use_c and bc is not None:
        fm_c = max(int(jnp.sum(_per_slot(a, bc.row_nnz(), bc.indptr.shape[0] - 1))), 1)
        cap = round_capacity(fm_c, pad_policy)
        sizes = symbolic_compressed(a, bc, a.m, cap, key_bound=-(-b.k // 32))
    else:
        cap = round_capacity(fm, pad_policy)
        sizes = symbolic_plain(a, b, cap)
    return sizes, stats


def _repad_csr(a: CSR, nnz_cap: int) -> CSR:
    """Re-pad a CSR's buffer capacity to a bucketed cap (live prefix kept).

    Requires nnz(a) <= nnz_cap — only padding slots are ever dropped. Runs in
    numpy on purpose: eager jnp slicing here would compile per *exact* input
    capacity, defeating the bucketing (the host driver syncs for the
    structure hash anyway, so the device->host copy is already paid).
    """
    from repro.runtime.validate import CapacityOverflowError  # cycle-free

    if nnz_cap == a.nnz_cap:
        return a
    nnz = int(a.indptr[-1])
    if nnz > nnz_cap:
        raise CapacityOverflowError(
            f"cannot repad CSR to nnz_cap={nnz_cap}: {nnz} live entries would "
            f"be truncated (buffer cap {a.nnz_cap})"
        )
    keep = min(nnz_cap, a.nnz_cap)
    indices = np.zeros(nnz_cap, np.int32)
    values = np.zeros(nnz_cap, np.asarray(a.values).dtype)
    indices[:keep] = np.asarray(a.indices)[:keep]
    values[:keep] = np.asarray(a.values)[:keep]
    return CSR(indptr=a.indptr, indices=jnp.asarray(indices),
               values=jnp.asarray(values), shape=a.shape)


def prepare_sparse_inputs(a: CSR, b: CSR, policy: str):
    """Bucket the operand buffer caps and size the expansion: the shared
    preamble of every sparse-path entry point (``spgemm()`` and
    ``executor.spgemm_grouped``), so the inputs feeding ``structure_key``
    can never drift between them. Returns (a, b, fm, maxrf, fm_cap)."""
    a = _repad_csr(a, round_capacity(max(int(a.indptr[-1]), 1), policy))
    b = _repad_csr(b, round_capacity(max(int(b.indptr[-1]), 1), policy))
    fm, maxrf = (int(x) for x in _fm_scalars(a, b))
    return a, b, fm, maxrf, round_capacity(fm, policy)


def resolve_plan(a: CSR, b: CSR, fm_cap: int, policy: str, cache, key=None):
    """Get-or-build the numeric plan for (repadded) A, B.

    The single source of truth for plan resolution — both ``spgemm()`` and
    ``executor.spgemm_grouped`` go through here, so the structure key, the
    nnz_cap bucketing, and the cache put/get can never drift apart (a drift
    would silently replay a plan with the wrong capacities). ``key`` lets a
    caller that already hashed the structure (the grouping loop) skip the
    second O(nnz) digest.

    Returns (plan, cache_state, key) with cache_state in {"hit", "miss",
    "bypass"} — the key is returned so callers can attach per-entry
    metadata (e.g. the autotuner's measured winner) without re-hashing.
    """
    from repro.core.plan_cache import structure_key  # cycle-free late import

    if key is None:
        with span("plan.key"):
            key = structure_key(a, b, fm_cap, policy)
    if cache is not None:
        plan = cache.get(key)
        if plan is not None:
            return plan, "hit", key
    with span("plan.build", structure_key=key, fm_cap=fm_cap) as sp:
        sx = expand_and_sort(a, b, fm_cap)
        nnz_cap = round_capacity(int(jnp.sum(sx.row_sizes)), policy)
        sp.set("nnz_cap", nnz_cap)
        plan = plan_from_sorted(sx, b.k, nnz_cap)
    if cache is None:
        return plan, "bypass", key
    cache.put(key, plan)
    return plan, "miss", key


def _measured_replay(plan, a: CSR, b: CSR, cache, cache_key: str):
    """tune="measure" replay: dispatch the measured-fastest replay backend.

    Winner resolution order (each layer avoids re-tuning the next):
      1. the plan-cache entry's sidecar meta (dtype-qualified — the
         structure key excludes value dtypes on purpose),
      2. the autotuner's structure-stats bucket table,
      3. a first-sight micro-bench of the eligible replay backends on the
         real operands (recorded in the bucket table).
    The winner is written back to the plan-cache entry so later replays and
    ``spgemm_grouped`` re-dispatch it with zero re-tuning.
    """
    from repro.core import autotune
    from repro.core.executor import _apply, fitting_backend, replay_candidates

    interp = jax.default_backend() != "tpu"
    meta_key = ("tuned_backend", str(a.values.dtype), str(b.values.dtype))
    winner = cache.get_meta(cache_key, meta_key) if cache is not None else None
    if winner is not None:
        autotune.TUNE_COUNTS["plan_meta_hit"] += 1
    else:
        bkey = autotune.bucket_key(
            a.m, b.k, plan.seg_ids.shape[0], a.values.dtype, b.values.dtype,
            table="replay")
        winner = autotune.lookup_measured(bkey)
        if winner is None:
            winner, _ = autotune.measure_and_record(
                bkey, replay_candidates(plan, a.values, b.values, interp))
        if cache is not None:
            cache.set_meta(cache_key, meta_key, winner)
    winner = fitting_backend(winner, plan, a.values, b.values)
    values = _apply(plan, a.values, b.values, backend=winner,
                    interpret=interp)
    return values, winner


def spgemm(a: CSR, b: CSR, method: str = "auto", compress: str = "auto",
           pad_policy: str | None = None, plan_cache=None,
           tune: str | None = None,
           mesh=None, mesh_axis: str = "data",
           b_placement: str = "replicated",
           validate: str | None = None,
           trace: str | bool | None = None) -> SpgemmResult:
    """Full two-phase SpGEMM with the KKSPGEMM meta-algorithm's method choice
    (see core/meta.py for the heuristics).

    pad_policy: capacity bucketing for every static cap ("pow2" default;
        "exact8" restores tight per-size caps — see core.meta.round_capacity).
    plan_cache: None (default) uses the module-level LRU from
        core/plan_cache.py; pass a PlanCache for an isolated cache, or False
        to disable caching for this call. On a structure hit, the sparse path
        skips the expansion and sort entirely (stats["cache"] == "hit").
    compress: only affects the "dense" method's symbolic phase. The sparse
        path needs the plain expansion for its numeric plan anyway, so
        compression would add work, not save it — its stats (cf/cmrf/
        compressed) are therefore only present on the dense path; use
        ``symbolic()`` directly to inspect compression on any matrix.
    mesh: a JAX mesh routes the multiply through ``repro.dist``: C's rows
        are 1-D partitioned over ``mesh_axis``, the sharded plan comes from
        (and lands in) the mesh-aware plan cache, and the numeric phase runs
        under shard_map in one dispatch. ``b_placement`` picks "replicated"
        (B everywhere, zero communication) or "allgather" (B row-sharded,
        one values-only all-gather per call). Implies the sparse method.

    The dense method returns ``plan=None``: KKDENSE has no product->slot map
    and therefore no Reuse fast path. Callers that need structure reuse (or a
    ``ReuseExecutor``) must use ``method="sparse"``.

    method="lp" is the KKLP position made explicit: the same single-expansion
    sparse pipeline (plan, cache, Reuse path all intact) but the numeric
    values come from the Pallas LP-hash accumulator kernel
    (``kernels/spgemm_lp.py``; interpret mode off-TPU) — with an automatic
    XLA fallback for f64/int operand dtypes, which the f32-accumulating
    kernel must not touch. ``stats["kernel"]`` always records what
    ``choose_kernel`` would pick ('dense_acc' below the avg-row-flops
    cutoff, 'flat_lp' at or above); ``stats["lp_backend"]`` records which
    backend the lp method actually used ("pallas" or "xla").

    validate: "off" (default via None) | "host" | "device" — typed operand
        validation before any dispatch (``runtime/validate.py``): CSR
        invariant violations raise ``SpgemmInputError``, a claimed nnz past
        the buffer cap raises ``CapacityOverflowError``. "host" pulls the
        structure to numpy and reports exact violation indices; "device"
        runs one jitted bitmask sweep with a single scalar sync. ``None``
        defers to ``$REPRO_VALIDATE``. "off" is bit-for-bit the pre-existing
        dispatch path (no extra traces/hashes — telemetry-asserted in
        tests/test_validate.py).

    tune="measure" (sparse/auto-sparse only) switches the replay dispatch to
    the autotuner: on first sight of a structure-stats bucket the eligible
    replay backends are micro-benchmarked on the real operands and the
    winner is cached — in the autotuner's bucket table and in the plan-cache
    entry — so replays re-dispatch it with zero re-tuning
    (``stats["kernel_source"] == "measured"``, ``stats["replay_backend"]``
    records the winner). The dense method ignores tune (its choosers are
    advisory there, and KKDENSE has no replay to re-dispatch); method="lp"
    rejects it (lp *is* an explicit backend pin); mesh= rejects it (the
    sharded replay is XLA-only, see ROADMAP).

    trace: None (default) | bool | "off" | "on" | "xprof" — phase tracing for
        this call (``repro.obs``): "on" records nesting spans
        (``spgemm.prepare``, ``plan.key``, ``plan.build``,
        ``numeric.dispatch``, ...) for Chrome trace-event export;
        "xprof" additionally wraps each span in
        ``jax.profiler.TraceAnnotation``. ``None`` defers to the ambient mode
        (ultimately ``$REPRO_TRACE``, mirroring how ``validate=None`` defers
        to ``$REPRO_VALIDATE``). "off" pins tracing off for this call; the
        untraced path is dispatch-identical (telemetry-asserted in
        tests/test_obs.py).
    """
    from repro.core import autotune  # cycle-free
    from repro.core.meta import choose_kernel, choose_method  # cycle-free
    from repro.core.plan_cache import default_plan_cache

    from repro.runtime.validate import (SpgemmConfigError, check_csr,  # cycle-free
                                        resolve_mode)

    if trace is not None:
        # Pin the trace mode for this call's full extent, then re-enter with
        # trace=None so the body below runs unchanged under the pinned scope.
        with trace_scope(trace):
            return spgemm(a, b, method=method, compress=compress,
                          pad_policy=pad_policy, plan_cache=plan_cache,
                          tune=tune, mesh=mesh, mesh_axis=mesh_axis,
                          b_placement=b_placement, validate=validate,
                          trace=None)
    policy = DEFAULT_PAD_POLICY if pad_policy is None else pad_policy
    if method not in ("auto", "dense", "sparse", "lp"):
        raise SpgemmConfigError(
            f"unknown method {method!r}; expected 'auto', 'dense', 'sparse' "
            f"or 'lp'")
    autotune.validate_tune(tune)
    vmode = resolve_mode(validate)
    if vmode != "off":
        check_csr(a, vmode, name="A")
        check_csr(b, vmode, name="B")
    if tune == "measure" and method == "lp":
        raise SpgemmConfigError(
            "tune='measure' does not compose with method='lp': 'lp' pins "
            "the LP-hash kernel explicitly, while measure mode exists to "
            "pick the replay backend empirically — use method='sparse' (or "
            "'auto') with tune='measure'")
    if mesh is not None:
        if tune is not None:
            raise SpgemmConfigError(
                "tune= does not support mesh= yet: the sharded replay runs "
                "the XLA segment-sum only, so there are no per-shard "
                "candidates to measure (see ROADMAP)")
        if method == "dense":
            raise SpgemmConfigError(
                "mesh= requires the sparse method: KKDENSE has no "
                "product->slot map, so it cannot pin a sharded plan")
        if method == "lp":
            raise SpgemmConfigError(
                "mesh= does not support method='lp' yet: the sharded replay "
                "runs the XLA segment-sum only (see ROADMAP: Pallas path "
                "under shard_map); use method='sparse' on a mesh")
        from repro.dist import sharded_spgemm  # cycle-free late import

        return sharded_spgemm(a, b, mesh, axis=mesh_axis,
                              b_placement=b_placement, pad_policy=policy,
                              plan_cache=plan_cache)
    stats: dict = {"pad_policy": policy, "validate": vmode}
    if method == "auto":
        method = choose_method(a, b, stats)  # shape-only heuristics
    stats["method"] = method

    if method == "dense":
        with span("spgemm.symbolic", method="dense"):
            sizes, sym_stats = symbolic(a, b, compress=compress,
                                        pad_policy=policy)
        stats.update(sym_stats)
        stats["kernel"] = choose_kernel(a, b, stats)  # advisory telemetry
        fm_cap = round_capacity(sym_stats["fm"], policy)
        stats["fm_cap"] = fm_cap
        nnz = int(jnp.sum(sizes))
        nnz_cap = round_capacity(nnz, policy)
        stats["nnz_c"] = nnz
        stats["nnz_cap"] = nnz_cap
        stats["cache"] = "bypass"
        with span("numeric.dispatch", kernel="dense_acc", method="dense"):
            c = numeric_dense_acc(a, b, fm_cap, nnz_cap)
        return SpgemmResult(c=c, plan=None, stats=stats)

    # "sparse"/"lp": single-expansion pipeline through the plan cache. Bucket
    # the input buffer caps *before* any jitted work, so every array shape
    # the jitted stages (including the f_m scalars) see is a bucket size —
    # that's what lets same-bucket matrices share executables.
    if plan_cache is None:
        cache = default_plan_cache()
    elif plan_cache is False:
        cache = None
    else:
        cache = plan_cache
    with span("spgemm.prepare", pad_policy=policy):
        a, b, fm, maxrf, fm_cap = prepare_sparse_inputs(a, b, policy)
    stats["fm"] = fm
    stats["maxrf"] = maxrf
    stats["fm_cap"] = fm_cap
    stats["kernel"] = choose_kernel(a, b, stats)  # the paper's GPU rule

    plan, cache_state, skey = resolve_plan(a, b, fm_cap, policy, cache)
    stats["structure_key"] = skey
    if method == "lp":
        with span("numeric.dispatch", method="lp") as sp:
            values, stats["lp_backend"] = lp_replay_values(
                plan, a.values, b.values)
            sp.set("kernel", stats["lp_backend"])
        stats["replay_backend"] = stats["lp_backend"]
        if stats["lp_backend"] == "xla":
            # host-side bump (trace-time bumps are unreliable): the f32-
            # accumulation dtype guard rerouted the LP pin to exact XLA
            from repro.core.telemetry import FALLBACK_COUNTS

            FALLBACK_COUNTS["dtype:lp->xla"] += 1
    elif tune == "measure":
        with span("numeric.dispatch", method="measure") as sp:
            values, winner = _measured_replay(plan, a, b, cache, skey)
            sp.set("kernel", winner)
        stats["replay_backend"] = winner
        stats["kernel_source"] = "measured"  # overrides choose_kernel's
    else:
        with span("numeric.dispatch", kernel="xla", method=method):
            values = numeric_reuse(plan, a.values, b.values)
        stats["replay_backend"] = "xla"
    c = CSR(indptr=plan.indptr, indices=plan.indices, values=values,
            shape=(a.m, b.k))
    stats["cache"] = cache_state
    stats["nnz_c"] = int(plan.indptr[-1])
    stats["nnz_cap"] = plan.indices.shape[0]
    return SpgemmResult(c=c, plan=plan, stats=stats)


@jax.jit
def _fm_scalars(a: CSR, b: CSR):
    fm, _, maxrf = flops_stats(a, b.row_nnz())
    return fm, maxrf


@jax.jit
def _per_slot(a: CSR, row_nnz: jax.Array, nb: int):
    valid = a.valid_mask()
    return jnp.where(valid, row_nnz[jnp.minimum(a.indices, nb - 1)], 0)
