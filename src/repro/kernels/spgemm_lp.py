"""Pallas TPU kernels: the paper's §3.1.2 linear-probing hash accumulator.

KKLP position (``core.meta.choose_kernel`` -> "flat_lp"): for flop-heavy rows
the dense accumulator's O(k) zero/scan per row loses to a hash table sized by
the row's *output*, not the column space. Two kernels share the LP discipline:

``spgemm_lp``
    Gustavson numeric phase over ELL operands, one C row per outer grid step
    (grid ``(m, rA)`` — rows tiled over grid steps, exactly the partitioning
    of ``spgemm_numeric``). The accumulator is the paper's two-level scheme
    in VMEM scratch: an L1 linear-probing table with the 50% max-occupancy
    rule (new keys are rejected past the cutoff while existing keys still
    accumulate) and an L2 table sized to hold every spill (the MAXRF
    guarantee the memory pool gives the paper's CHUNKSIZE). The semantic
    oracle is ``core.accumulators.accumulate_row(kind="lp")``: the kernel
    replays the exact insert stream (row-major over A slots, then B slots)
    with the same occupancy cutoff and the same f32 adds, so its output is
    **bitwise** the oracle's merged L1+L2 extraction — including rows that
    spill.

``lp_reuse`` / ``lp_reuse_arrays``
    The Reuse-case replay (same contract as ``kernels.segsum_reuse``) with
    the in-tile reduction done through an LP table instead of the direct
    one-hot window matmul: products of an FM-tile hash their segment offsets
    into a scratch table, and the table is flushed into the tile's output
    window with one one-hot matmul. The table is sized at 2x the tile (the
    MAXRF bound of a tile), so the 50% rule never spills here — this variant
    exists to make the accumulator trade-off *measurable* on the replay hot
    loop (``benchmarks.run bench_accumulators``), not to win it everywhere.

Probe-loop totality: the probe is evaluated as a vectorized minimum over probe
distance (first empty-or-matching slot in cyclic order), so a full table
cannot hang the kernel — an unservable insert simply resolves to a rejected
candidate and spills, mirroring the clamped-cutoff fix in
``core.accumulators.lp_insert``.

Precision: tables accumulate in f32 and the result is cast to
``result_type(a, b)`` — f64/int operands belong on the XLA fallback, which is
what ``kernels.ops.numeric_values`` and ``ReuseExecutor`` route them to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.accumulators import MAX_OCCUPANCY
from repro.kernels.limits import ell_misfit, replay_misfit, require_fit
from repro.kernels.segsum_reuse import LANES, _gather_row, _pad_to
from repro.kernels.spgemm_numeric import _pad_width, _pick, _row_views

# products per grid step of the LP replay kernel (lane-aligned); its scratch
# table is 2x this, so in-tile occupancy can never exceed the 50% cutoff
LP_TILE = 128


def _next_pow2(x: int) -> int:
    # deliberately NOT core.meta.round_capacity("pow2"): table sizes are a
    # hash invariant (the & mask needs a power of two) and must not follow
    # the tunable capacity-bucketing policy, even though the numbers
    # coincide today
    return 1 << (max(int(x), 1) - 1).bit_length()


def default_l1_size(r_c: int) -> int:
    """Default L1 table size for an rC-wide output: next pow2 >= 2*rC, which
    the 50% max-occupancy rule can never spill. Exposed so tests build their
    oracle with the same size the kernel actually uses."""
    return _next_pow2(max(2 * r_c, 8))


def _lp_probe(ids: jax.Array, key: jax.Array):
    """First slot from hash(key), cyclically, that is empty (-1) or holds
    ``key`` — the linear probe, evaluated without a data-dependent loop.

    Probing order is increasing cyclic distance from the hash slot, and the
    probe stops at the first empty-or-match slot; that slot is exactly the
    minimum-distance candidate, so one vectorized min replaces the while
    loop (and is total even when the table has no candidate at all: the
    probe then resolves to slot 0, a non-candidate). The min is over
    distances, not an argmin, because Mosaic reduces indices of f32 only.
    Returns (slot, key_already_present).
    """
    size = ids.shape[0]
    mask = size - 1
    h = key & mask
    dist = (jax.lax.iota(jnp.int32, size) - h) & mask
    cand = (ids == -1) | (ids == key)
    d = jnp.min(jnp.where(cand, dist, size))
    p = jnp.where(d < size, (h + d) & mask, 0)
    id_at_p = jnp.sum(jnp.where(jax.lax.iota(jnp.int32, size) == p, ids, 0))
    return p, id_at_p == key


# --------------------------------------------------------------------------
# Gustavson numeric phase (the KKLP kernel proper)
# --------------------------------------------------------------------------


def _kernel(a_idx_ref, a_nnz_ref, b_nnz_ref, c_nnz_ref,  # scalar prefetch
            a_val_ref, b_idx_ref, b_val_ref, c_idx_ref,  # VMEM inputs
            out_ref,  # VMEM output (1, rC)
            l1_ids_ref, l1_val_ref, l2_ids_ref, l2_val_ref,  # VMEM scratch
            used_ref):  # SMEM scratch (1,) — L1 occupancy counter
    i = pl.program_id(0)
    r = pl.program_id(1)
    n_r = pl.num_programs(1)
    s1 = l1_ids_ref.shape[1]
    s2 = l2_ids_ref.shape[1]
    r_b = b_idx_ref.shape[1]
    r_c = out_ref.shape[1]
    # the paper's 50% rule, clamped so an empty sentinel always survives —
    # same formula as the (fixed) core.accumulators.lp_insert oracle
    cutoff = min(int(s1 * MAX_OCCUPANCY), s1 - 1)

    @pl.when(r == 0)
    def _reset():
        l1_ids_ref[...] = jnp.full_like(l1_ids_ref, -1)
        l1_val_ref[...] = jnp.zeros_like(l1_val_ref)
        l2_ids_ref[...] = jnp.full_like(l2_ids_ref, -1)
        l2_val_ref[...] = jnp.zeros_like(l2_val_ref)
        used_ref[0] = 0

    live_a = r < a_nnz_ref[i]
    n_live_b = jnp.where(live_a, b_nnz_ref[a_idx_ref[i * n_r + r]], 0)
    a_val = _pick(a_val_ref[0, :].astype(jnp.float32), r)
    cols = b_idx_ref[0, :]  # (rB,) — the B row steered by a_idx[i, r]
    prods = a_val * b_val_ref[0, :].astype(jnp.float32)  # (rB,)

    def insert(t, used):
        key = _pick(cols, t)
        val = _pick(prods, t)
        ok = t < n_live_b  # padded B slots must not mint phantom keys
        ids1 = l1_ids_ref[0, :]
        p1, found1 = _lp_probe(ids1, key)
        accept = found1 | (used < cutoff)
        upd1 = (jax.lax.iota(jnp.int32, s1) == p1) & ok & accept
        l1_ids_ref[0, :] = jnp.where(upd1, key, ids1)
        l1_val_ref[0, :] = l1_val_ref[0, :] + jnp.where(upd1, val, 0.0)
        # rejected new keys spill to L2 (sized for every spill: no cutoff)
        spill = ok & ~accept
        ids2 = l2_ids_ref[0, :]
        p2, _ = _lp_probe(ids2, key)
        upd2 = (jax.lax.iota(jnp.int32, s2) == p2) & spill
        l2_ids_ref[0, :] = jnp.where(upd2, key, ids2)
        l2_val_ref[0, :] = l2_val_ref[0, :] + jnp.where(upd2, val, 0.0)
        return used + (ok & accept & ~found1).astype(jnp.int32)

    used_ref[0] = jax.lax.fori_loop(0, r_b, insert, used_ref[0])

    @pl.when(r == n_r - 1)
    def _emit():
        c_cols = c_idx_ref[0, :]  # (rC,)
        eq1 = l1_ids_ref[0, :][:, None] == c_cols[None, :]  # (s1, rC)
        vals = jnp.sum(jnp.where(eq1, l1_val_ref[0, :][:, None], 0.0), axis=0)
        eq2 = l2_ids_ref[0, :][:, None] == c_cols[None, :]  # (s2, rC)
        vals = vals + jnp.sum(
            jnp.where(eq2, l2_val_ref[0, :][:, None], 0.0), axis=0
        )
        mask = jax.lax.iota(jnp.int32, r_c)[None, :] < c_nnz_ref[i]
        out_ref[...] = jnp.where(mask, vals[None, :], 0.0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("l1_size", "interpret"))
def spgemm_lp(a_idx, a_val, a_nnz, b_idx, b_val, b_nnz, c_idx, c_nnz, *,
              l1_size: int | None = None, interpret: bool = False) -> jax.Array:
    """LP-hash numeric phase: C values (ELL layout, (m, rC)) at the given
    structure, accumulated through the paper's two-level L1/L2 LP scheme.

    a_idx/a_val: (m, rA) ELL of A; a_nnz: (m,); b_idx/b_val: (n, rB) ELL of B;
    b_nnz: (n,) — live B widths (padded B slots are *masked*, not relied on
    to carry zero values: a phantom key would corrupt table occupancy);
    c_idx: (m, rC) symbolic structure of C; c_nnz: (m,).

    l1_size: L1 table size (power of two). The default sizes L1 at the next
    power of two >= 2*rC, which the 50% rule can never spill; pass a smaller
    size to exercise the spill path. L2 is always sized to hold every
    possible spill (next pow2 >= 2*rC), the MAXRF guarantee.
    """
    m, r_a = a_idx.shape
    n, r_b = b_idx.shape
    r_c = c_idx.shape[1]
    if l1_size is None:
        l1_size = default_l1_size(r_c)
    if l1_size & (l1_size - 1) or l1_size < 2:
        from repro.runtime.validate import SpgemmConfigError  # cycle-free
        raise SpgemmConfigError(
            f"l1_size must be a power of two >= 2; got {l1_size}")
    require_fit(ell_misfit("flat_lp", m=m, r_a=r_a, n=n, r_b=r_b, r_c=r_c,
                           l1_size=l1_size))
    s2 = default_l1_size(r_c)  # L2 holds every possible spill (MAXRF)
    out_dtype = jnp.result_type(a_val, b_val)

    grid = (m, r_a)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, 1, r_a),
                             lambda i, r, ai, an, bn, cn: (i, 0, 0)),
                pl.BlockSpec((None, 1, r_b),
                             lambda i, r, ai, an, bn, cn: (ai[i * r_a + r], 0, 0)),
                pl.BlockSpec((None, 1, r_b),
                             lambda i, r, ai, an, bn, cn: (ai[i * r_a + r], 0, 0)),
                pl.BlockSpec((None, 1, r_c),
                             lambda i, r, ai, an, bn, cn: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, 1, r_c),
                                   lambda i, r, ai, an, bn, cn: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((1, l1_size), jnp.int32),
                pltpu.VMEM((1, l1_size), jnp.float32),
                pltpu.VMEM((1, s2), jnp.int32),
                pltpu.VMEM((1, s2), jnp.float32),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((m, 1, r_c), out_dtype),
        interpret=interpret,
    )(a_idx.reshape(-1), a_nnz, b_nnz, c_nnz, *_row_views(a_val, b_idx, b_val, c_idx))
    return out[:, 0, :]


def spgemm_lp_bucketed(a_idx, a_val, a_nnz, b_idx, b_val, b_nnz, c_idx, c_nnz,
                       *, l1_size: int | None = None,
                       pad_policy: str | None = None,
                       interpret: bool = False) -> jax.Array:
    """``spgemm_lp`` with ELL widths rA/rB/rC padded to capacity buckets
    (same contract as ``spgemm_numeric_bucketed``); output sliced back to the
    caller's rC. Padded A slots are masked by ``a_nnz``, padded B slots by
    ``b_nnz``, padded C slots by ``c_nnz``."""
    from repro.core.meta import DEFAULT_PAD_POLICY, round_capacity

    policy = DEFAULT_PAD_POLICY if pad_policy is None else pad_policy
    r_c = c_idx.shape[1]
    a_idx = _pad_width(a_idx, round_capacity(a_idx.shape[1], policy))
    a_val = _pad_width(a_val, a_idx.shape[1])
    b_idx = _pad_width(b_idx, round_capacity(b_idx.shape[1], policy))
    b_val = _pad_width(b_val, b_idx.shape[1])
    c_idx_p = _pad_width(c_idx, round_capacity(r_c, policy))
    out = spgemm_lp(a_idx, a_val, a_nnz, b_idx, b_val, b_nnz, c_idx_p, c_nnz,
                    l1_size=l1_size, interpret=interpret)
    return out[:, :r_c]


# --------------------------------------------------------------------------
# Reuse-case replay through the LP accumulator
# --------------------------------------------------------------------------


def _reuse_kernel(a_val_ref, b_val_ref, a_slot_ref, b_slot_ref, seg_ref,
                  out_ref, ids_ref, val_ref):
    step = pl.program_id(0)
    fm_t = a_slot_ref.shape[1]
    s1 = ids_ref.shape[1]
    win = fm_t + LANES
    nnz_cap = out_ref.shape[1] - win  # wrapper pads the output by one window

    @pl.when(step == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    # fresh table per tile: the tile's segments are its whole key space
    ids_ref[...] = jnp.full_like(ids_ref, -1)
    val_ref[...] = jnp.zeros_like(val_ref)

    segs = seg_ref[0, :]  # (fm_t,) non-decreasing; sentinel nnz_cap on pad
    prod = _gather_row(a_val_ref, a_slot_ref[0, :]) * _gather_row(
        b_val_ref, b_slot_ref[0, :]
    )  # (1, fm_t)
    live = segs < nnz_cap
    # sortedness: live segments of a tile land in a contiguous window of
    # width <= fm_t; align its start down to a lane group (as segsum_reuse)
    base = (segs[0] // LANES) * LANES
    local = segs - base  # live keys in [0, win)
    prod_v = prod[0, :]

    def insert(t, _):
        key = _pick(local, t)
        val = _pick(prod_v, t)
        ok = _pick(live.astype(jnp.int32), t) > 0
        ids = ids_ref[0, :]
        p, _found = _lp_probe(ids, key)
        # table is 2x the tile: distinct keys <= fm_t == the 50% cutoff, so
        # every live insert is accepted (in-tile MAXRF bound)
        upd = (jax.lax.iota(jnp.int32, s1) == p) & ok
        ids_ref[0, :] = jnp.where(upd, key, ids)
        val_ref[0, :] = val_ref[0, :] + jnp.where(upd, val, 0.0)
        return 0

    jax.lax.fori_loop(0, fm_t, insert, 0)

    # flush the table into the tile's output window with one one-hot matmul
    eq = ids_ref[0, :][:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (s1, win), 1
    )  # (s1, win); empty slots (-1) match nothing
    window = jnp.sum(jnp.where(eq, val_ref[0, :][:, None], 0.0), axis=0)[None, :]

    out_ref[:, pl.ds(base, win)] += window.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("nnz_cap", "interpret"))
def lp_reuse_arrays(a_slot_s, b_slot_s, seg_ids, a_values, b_values, *,
                    nnz_cap: int, interpret: bool = False) -> jax.Array:
    """LP-accumulator replay on raw plan arrays. Returns (nnz_cap,) C values.

    Same contract as ``segsum_reuse_arrays`` (sorted product order, sentinel
    ``seg_ids == nnz_cap`` on padding, f32 accumulation cast to
    ``result_type(a, b)``) — only the in-tile reduction differs.
    """
    from repro.kernels.segsum_reuse import VAL_TILE

    out_dtype = jnp.result_type(a_values, b_values)
    fm_cap = a_slot_s.shape[0]
    fm_pad = -(-fm_cap // LP_TILE) * LP_TILE
    a_slot_s = _pad_to(a_slot_s.astype(jnp.int32), fm_pad)[None, :]
    b_slot_s = _pad_to(b_slot_s.astype(jnp.int32), fm_pad)[None, :]
    seg_ids = _pad_to(seg_ids.astype(jnp.int32), fm_pad, fill=nnz_cap)[None, :]
    na = -(-a_values.shape[0] // VAL_TILE) * VAL_TILE
    nb = -(-b_values.shape[0] // VAL_TILE) * VAL_TILE
    a_values = _pad_to(a_values, na)[None, :]
    b_values = _pad_to(b_values, nb)[None, :]
    require_fit(replay_misfit(na, nb, nnz_cap))

    s1 = _next_pow2(2 * LP_TILE)
    grid = (fm_pad // LP_TILE,)
    out = pl.pallas_call(
        _reuse_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, na), lambda t: (0, 0)),
            pl.BlockSpec((1, nb), lambda t: (0, 0)),
            pl.BlockSpec((1, LP_TILE), lambda t: (0, t)),
            pl.BlockSpec((1, LP_TILE), lambda t: (0, t)),
            pl.BlockSpec((1, LP_TILE), lambda t: (0, t)),
        ],
        out_specs=pl.BlockSpec((1, nnz_cap + LP_TILE + LANES), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, nnz_cap + LP_TILE + LANES), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1, s1), jnp.int32),
            pltpu.VMEM((1, s1), jnp.float32),
        ],
        interpret=interpret,
    )(a_values, b_values, a_slot_s, b_slot_s, seg_ids)
    return out[0, :nnz_cap].astype(out_dtype)


def lp_reuse(plan, a_values, b_values, *, interpret: bool = False) -> jax.Array:
    """Replay a ``SpgemmPlan`` numerically through the LP-hash accumulator.

    Same structure contract as ``core.spgemm.numeric_reuse`` / ``segsum_reuse``
    but with hash-table in-tile accumulation — select it through
    ``ReuseExecutor(..., backend="pallas_lp")`` or ``spgemm(method="lp")``.
    f32 accumulation: f64/int operands belong on the XLA path.
    """
    return lp_reuse_arrays(
        plan.a_slot_s, plan.b_slot_s, plan.seg_ids, a_values, b_values,
        nnz_cap=plan.indices.shape[0], interpret=interpret,
    )
