"""Pallas TPU kernel: the Reuse-case hot loop (gather-multiply-segment-sum).

Replays a precomposed ``SpgemmPlan`` (v2) numerically: for every product t in
sorted order, ``C[seg_ids[t]] += A_values[a_slot_s[t]] * B_values[b_slot_s[t]]``.
This is the paper's Thread-Flat-Parallel numeric variant mapped to the TPU's
regime (DESIGN.md §2): the flat multiplication space is tiled over the grid,
gathers become one-hot MXU matmuls (the same scatter==matmul trick as
``spgemm_numeric``), and the sorted-segment property replaces GPU atomics.

Why sortedness makes this a windowed kernel: consecutive sorted products have
segment ids differing by 0 or 1, so an FM_TILE-long product tile touches a
*contiguous* output window of width <= FM_TILE starting at its first segment
id. Each grid step reduces its tile into that window with one one-hot matmul
and accumulates read-modify-write — safe because the TPU grid is sequential.
The window's store offset is rounded down to a LANES (128) boundary and its
width widened by one lane group, so the dynamic store on the minor-most
dimension stays lane-aligned for Mosaic. Padding products carry the sentinel
``seg_ids == nnz_cap``; they are masked to zero before the reduction, so
they contribute nothing wherever their window rows land.

The output buffer is over-allocated by one window (``nnz_cap + FM_TILE +
LANES``) so a tail window still stores in bounds; the wrapper slices the
live prefix back off.

Precision: accumulation is f32 (the MXU regime), and the result is cast to
``result_type(a, b)`` — so unlike ``numeric_reuse`` this kernel does NOT
widen f64 operands. ``ReuseExecutor`` therefore routes f64 replays to the
XLA path, and keeps the kernel as an explicit ``backend="pallas"`` opt-in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.limits import replay_misfit, require_fit

# products per grid step (the f_m tile) and one-hot gather tile width along
# the value buffers — both MXU-friendly multiples of 128
FM_TILE = 512
VAL_TILE = 512
LANES = 128  # lane-group alignment for the windowed dynamic store
# f32 passes on the MXU: the one-hot operand is exact in bf16, the values are
# not, and the default single bf16 pass would round each one to 8 bits
EXACT = jax.lax.Precision.HIGHEST


def _gather_row(val_ref, slots):
    """Gather ``val_ref[0, slots]`` as (1, FM_TILE) f32 via tiled one-hot
    matmuls — the MXU replacement for an unsupported vector gather."""
    n = val_ref.shape[1]
    t = slots.shape[0]

    def body(c, acc):
        base = c * VAL_TILE
        chunk = val_ref[:, pl.ds(base, VAL_TILE)].astype(jnp.float32)  # (1, VAL_TILE)
        onehot = (
            base + jax.lax.broadcasted_iota(jnp.int32, (VAL_TILE, t), 0)
            == slots[None, :]
        ).astype(jnp.float32)  # (VAL_TILE, t)
        return acc + jnp.dot(chunk, onehot, precision=EXACT,
                             preferred_element_type=jnp.float32)

    return jax.lax.fori_loop(0, n // VAL_TILE, body, jnp.zeros((1, t), jnp.float32))


def _kernel(a_val_ref, b_val_ref, a_slot_ref, b_slot_ref, seg_ref, out_ref):
    step = pl.program_id(0)
    fm_t = a_slot_ref.shape[1]
    win = fm_t + LANES
    nnz_cap = out_ref.shape[1] - win  # wrapper pads the output by one window

    @pl.when(step == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    segs = seg_ref[0, :]  # (fm_t,) non-decreasing; sentinel nnz_cap at tail
    prod = _gather_row(a_val_ref, a_slot_ref[0, :]) * _gather_row(
        b_val_ref, b_slot_ref[0, :]
    )  # (1, fm_t)
    prod = jnp.where((segs < nnz_cap)[None, :], prod, 0.0)

    # in-tile sorted-segment reduction: ids step by <= 1 per product, so all
    # live segments land in [seg0, seg0 + fm_t); aligning the window start
    # down to a lane group keeps the dynamic store lane-aligned and one
    # one-hot matmul computes every window slot's partial sum at once
    base = (segs[0] // LANES) * LANES
    local = segs - base  # live products: in [0, fm_t + LANES)
    onehot = (
        local[:, None] == jax.lax.broadcasted_iota(jnp.int32, (fm_t, win), 1)
    ).astype(jnp.float32)  # (fm_t, win); masked rows contribute zero
    window = jnp.dot(prod, onehot, preferred_element_type=jnp.float32,
                     precision=EXACT)

    out_ref[:, pl.ds(base, win)] += window.astype(out_ref.dtype)


def _pad_to(x: jax.Array, size: int, fill=0) -> jax.Array:
    return x if x.shape[0] == size else jnp.pad(
        x, (0, size - x.shape[0]), constant_values=fill
    )


@functools.partial(jax.jit, static_argnames=("nnz_cap", "interpret"))
def segsum_reuse_arrays(a_slot_s, b_slot_s, seg_ids, a_values, b_values, *,
                        nnz_cap: int, interpret: bool = False) -> jax.Array:
    """Kernel entry on raw plan arrays. Returns (nnz_cap,) C values.

    a_slot_s/b_slot_s/seg_ids: (fm_cap,) int32, sorted product order with
    sentinel ``seg_ids == nnz_cap`` on padding; a_values/b_values: operand
    value buffers. Accumulates in f32 and casts to result_type(a, b) — f64
    operands lose precision here; use ``numeric_reuse`` for those.
    """
    out_dtype = jnp.result_type(a_values, b_values)
    fm_cap = a_slot_s.shape[0]
    fm_pad = -(-fm_cap // FM_TILE) * FM_TILE
    # grid padding: slots clip to 0 (any live value — masked), segs to sentinel
    a_slot_s = _pad_to(a_slot_s.astype(jnp.int32), fm_pad)[None, :]
    b_slot_s = _pad_to(b_slot_s.astype(jnp.int32), fm_pad)[None, :]
    seg_ids = _pad_to(seg_ids.astype(jnp.int32), fm_pad, fill=nnz_cap)[None, :]
    na = -(-a_values.shape[0] // VAL_TILE) * VAL_TILE
    nb = -(-b_values.shape[0] // VAL_TILE) * VAL_TILE
    a_values = _pad_to(a_values, na)[None, :]
    b_values = _pad_to(b_values, nb)[None, :]
    require_fit(replay_misfit(na, nb, nnz_cap))

    grid = (fm_pad // FM_TILE,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, na), lambda t: (0, 0)),
            pl.BlockSpec((1, nb), lambda t: (0, 0)),
            pl.BlockSpec((1, FM_TILE), lambda t: (0, t)),
            pl.BlockSpec((1, FM_TILE), lambda t: (0, t)),
            pl.BlockSpec((1, FM_TILE), lambda t: (0, t)),
        ],
        out_specs=pl.BlockSpec((1, nnz_cap + FM_TILE + LANES), lambda t: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, nnz_cap + FM_TILE + LANES), jnp.float32),
        interpret=interpret,
    )(a_values, b_values, a_slot_s, b_slot_s, seg_ids)
    return out[0, :nnz_cap].astype(out_dtype)


def segsum_reuse(plan, a_values, b_values, *, interpret: bool = False) -> jax.Array:
    """Replay a ``SpgemmPlan`` numerically with the Pallas kernel.

    Same structure contract as ``core.spgemm.numeric_reuse``, but f32
    accumulation (see module docstring — f64 operands belong on the XLA
    path). Select it through ``ReuseExecutor(..., backend="pallas")``. Pass
    ``interpret=True`` off-TPU.
    """
    return segsum_reuse_arrays(
        plan.a_slot_s, plan.b_slot_s, plan.seg_ids, a_values, b_values,
        nnz_cap=plan.indices.shape[0], interpret=interpret,
    )
