"""Pallas TPU kernel: SpGEMM numeric phase with a dense VMEM accumulator.

This is KKDENSE's numeric phase adapted to the MXU (DESIGN.md §2.1): the
per-row dense accumulator is a (1, k_pad) f32 VMEM tile; scatter of a B-row's
products is a one-hot matmul (vals @ onehot(cols)) and the final gather at
C's symbolic structure is the transposed one-hot matmul — both MXU ops,
replacing GPU per-lane atomics with associative matrix products.

Partitioning: Thread-Sequential (grid (m, rA)) — one C row per outer grid
step; lane parallelism covers B-row nonzeros; the B-row gather is steered by
the scalar-prefetched A structure via the BlockSpec index_map.

Two-phase contract: the kernel takes C's structure (from the symbolic
kernel) and writes values in ELL layout — reuse re-invokes only this kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.limits import ell_misfit, require_fit

# one-hot scatter tile width along the dense-accumulator (column) axis
K_TILE = 512
# the one-hot operand is exact in bf16 but the values are not: the MXU's
# default single bf16 pass would round every product to 8 mantissa bits
EXACT = jax.lax.Precision.HIGHEST


def _row_views(*arrays):
    """(rows, r) -> (rows, 1, r): Mosaic tiles the last two block dims, so a
    one-row block of an ELL array must be a whole (1, r) trailing slab."""
    return tuple(x[:, None, :] for x in arrays)


def _pick(vec: jax.Array, t) -> jax.Array:
    """``vec[t]`` for a dynamic ``t`` as a masked sum: Mosaic lowers neither
    a dynamic-lane scalar load nor ``dynamic_slice`` on a vector value."""
    hit = jax.lax.iota(jnp.int32, vec.shape[0]) == t
    return jnp.sum(jnp.where(hit, vec, jnp.zeros_like(vec)))


def _kernel(a_idx_ref, a_nnz_ref, c_nnz_ref,  # scalar prefetch
            a_val_ref, b_idx_ref, b_val_ref, c_idx_ref,  # VMEM inputs
            out_ref,  # VMEM output (1, rC)
            acc_ref):  # VMEM scratch (1, k_pad) f32
    i = pl.program_id(0)
    r = pl.program_id(1)
    n_r = pl.num_programs(1)
    k_pad = acc_ref.shape[1]
    r_c = out_ref.shape[1]

    @pl.when(r == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = r < a_nnz_ref[i]
    a_val = jnp.where(live, _pick(a_val_ref[0, :].astype(jnp.float32), r), 0.0)
    cols = b_idx_ref[0, :]  # (rB,)
    scaled = (a_val * b_val_ref[0, :].astype(jnp.float32))[None, :]  # (1, rB)

    def scatter_tile(t, _):
        base = t * K_TILE
        # one-hot (rB, K_TILE) on the MXU: scatter == matmul
        onehot = (
            cols[:, None] == base + jax.lax.iota(jnp.int32, K_TILE)[None, :]
        ).astype(jnp.float32)
        tile = jnp.dot(scaled, onehot, preferred_element_type=jnp.float32,
                       precision=EXACT)
        acc_ref[:, pl.ds(base, K_TILE)] += tile
        return 0

    jax.lax.fori_loop(0, k_pad // K_TILE, scatter_tile, 0)

    @pl.when(r == n_r - 1)
    def _emit():
        c_cols = c_idx_ref[0, :]  # (rC,)

        def gather_tile(t, out):
            base = t * K_TILE
            onehot = (
                base + jax.lax.iota(jnp.int32, K_TILE)[:, None] == c_cols[None, :]
            ).astype(jnp.float32)  # (K_TILE, rC)
            seg = acc_ref[:, pl.ds(base, K_TILE)]
            return out + jnp.dot(seg, onehot, precision=EXACT,
                                 preferred_element_type=jnp.float32)

        vals = jax.lax.fori_loop(
            0, k_pad // K_TILE, gather_tile, jnp.zeros((1, r_c), jnp.float32)
        )
        mask = jax.lax.iota(jnp.int32, r_c)[None, :] < c_nnz_ref[i]
        out_ref[...] = jnp.where(mask, vals, 0.0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def spgemm_numeric(a_idx, a_val, a_nnz, b_idx, b_val, c_idx, c_nnz, *,
                   k: int, interpret: bool = False) -> jax.Array:
    """Numeric phase: C values (ELL layout, (m, rC)) at the given structure.

    a_idx/a_val: (m, rA) ELL of A; a_nnz: (m,); b_idx/b_val: (n, rB) ELL of B
    (padded B slots must carry value 0); c_idx: (m, rC) symbolic structure of
    C; c_nnz: (m,); k: number of columns of B (static).
    """
    m, r_a = a_idx.shape
    n, r_b = b_idx.shape
    r_c = c_idx.shape[1]
    require_fit(ell_misfit("dense_acc", m=m, r_a=r_a, r_b=r_b, r_c=r_c, k=k))
    k_pad = -(-k // K_TILE) * K_TILE

    grid = (m, r_a)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, 1, r_a), lambda i, r, ai, an, cn: (i, 0, 0)),
                pl.BlockSpec((None, 1, r_b),
                             lambda i, r, ai, an, cn: (ai[i * r_a + r], 0, 0)),
                pl.BlockSpec((None, 1, r_b),
                             lambda i, r, ai, an, cn: (ai[i * r_a + r], 0, 0)),
                pl.BlockSpec((None, 1, r_c), lambda i, r, ai, an, cn: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, 1, r_c),
                                   lambda i, r, ai, an, cn: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((1, k_pad), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, 1, r_c), a_val.dtype),
        interpret=interpret,
    )(a_idx.reshape(-1), a_nnz, c_nnz, *_row_views(a_val, b_idx, b_val, c_idx))
    return out[:, 0, :]


def _pad_width(x: jax.Array, width: int) -> jax.Array:
    cur = x.shape[1]
    return x if cur == width else jnp.pad(x, ((0, 0), (0, width - cur)))


def spgemm_numeric_bucketed(a_idx, a_val, a_nnz, b_idx, b_val, c_idx, c_nnz, *,
                            k: int, pad_policy: str | None = None,
                            interpret: bool = False) -> jax.Array:
    """``spgemm_numeric`` with ELL widths rA/rB/rC padded to capacity buckets.

    Same bucketing contract as the host driver (core.meta.round_capacity):
    each width rounds up to its x2 band so similarly-shaped problems share
    one compiled kernel. Zero-padding preserves semantics — padded A slots
    are masked by ``a_nnz``, padded B slots carry value 0 (the kernel's
    contract), padded C slots are masked by ``c_nnz`` — and the output is
    sliced back to the caller's rC.
    """
    from repro.core.meta import DEFAULT_PAD_POLICY, round_capacity

    policy = DEFAULT_PAD_POLICY if pad_policy is None else pad_policy
    r_c = c_idx.shape[1]
    a_idx = _pad_width(a_idx, round_capacity(a_idx.shape[1], policy))
    a_val = _pad_width(a_val, a_idx.shape[1])
    b_idx = _pad_width(b_idx, round_capacity(b_idx.shape[1], policy))
    b_val = _pad_width(b_val, b_idx.shape[1])
    c_idx_p = _pad_width(c_idx, round_capacity(r_c, policy))
    out = spgemm_numeric(a_idx, a_val, a_nnz, b_idx, b_val, c_idx_p, c_nnz,
                         k=k, interpret=interpret)
    return out[:, :r_c]
