"""Pallas TPU kernel: SpGEMM symbolic phase over compressed (bitmask) B.

The paper's §3.2 compression is the most TPU-native piece of the algorithm:
B's structure packs 32 columns per uint32 lane, the symbolic row-union is a
VPU BITWISE-OR, and `population_count` recovers row sizes. The L1 accumulator
is a (1, k32) uint32 VMEM scratch tile — the dense-accumulator scheme in
compressed column space (32x smaller than an uncompressed dense accumulator,
which is why it stays in VMEM for k up to ~4M columns).

Partitioning (DESIGN.md §2.2 Thread-Sequential): grid = (m, rA); step (i, r)
DMAs B's bitmask row ``a_idx[i, r]`` — the gather is steered by the
scalar-prefetched A structure through the BlockSpec index_map, which is the
TPU idiom replacing the GPU's per-thread pointer chasing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.limits import ell_misfit, require_fit


def _kernel(a_idx_ref, a_nnz_ref, b_bm_ref, out_ref, acc_ref):
    i = pl.program_id(0)
    r = pl.program_id(1)
    n_r = pl.num_programs(1)

    @pl.when(r == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = r < a_nnz_ref[i]
    row = b_bm_ref[...]  # (1, k32) uint32, DMA'd by index_map gather
    acc_ref[...] |= jnp.where(live, row, jnp.uint32(0))

    @pl.when(r == n_r - 1)
    def _emit():
        counts = jax.lax.population_count(acc_ref[...])
        out_ref[...] = jnp.full(out_ref.shape,
                                jnp.sum(counts.astype(jnp.int32)), jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def spgemm_symbolic(a_idx: jax.Array, a_nnz: jax.Array, b_bitmask: jax.Array,
                    *, interpret: bool = False) -> jax.Array:
    """Row sizes of C = A*B from A's ELL structure and B's bitmask rows.

    a_idx: (m, rA) int32 — ELL column ids of A (padded slots masked via a_nnz)
    a_nnz: (m,) int32 — live width per row
    b_bitmask: (n, k32) uint32 — compressed structure of B (k32 % 128 == 0)
    returns: (m,) int32 row sizes.
    """
    m, r_a = a_idx.shape
    n, k32 = b_bitmask.shape
    if k32 % 128:
        from repro.runtime.validate import SpgemmInputError  # cycle-free
        raise SpgemmInputError(
            f"k32={k32} must be lane-aligned (multiple of 128)")
    require_fit(ell_misfit("symbolic", m=m, r_a=r_a, k=32 * k32))

    grid = (m, r_a)
    out = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec(
                    (None, 1, k32),
                    lambda i, r, a_idx, a_nnz: (a_idx[i * r_a + r], 0, 0),
                ),
            ],
            out_specs=pl.BlockSpec((None, 1, 1),
                                   lambda i, r, a_idx, a_nnz: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((1, k32), jnp.uint32)],
        ),
        out_shape=jax.ShapeDtypeStruct((m, 1, 1), jnp.int32),
        interpret=interpret,
    )(a_idx.reshape(-1), a_nnz, b_bitmask[:, None, :])
    return out[:, 0, 0]


def spgemm_symbolic_bucketed(a_idx: jax.Array, a_nnz: jax.Array,
                             b_bitmask: jax.Array, *,
                             pad_policy: str | None = None,
                             interpret: bool = False) -> jax.Array:
    """``spgemm_symbolic`` with the ELL width rA padded to a capacity bucket.

    Same bucketing contract as the host driver (core.meta.round_capacity):
    widths within a x2 band map to one grid shape, so similarly-sized
    matrices share a single compiled kernel instead of each recompiling.
    Padded slots sit beyond ``a_nnz`` and are masked inside the kernel.
    """
    from repro.core.meta import DEFAULT_PAD_POLICY, round_capacity

    policy = DEFAULT_PAD_POLICY if pad_policy is None else pad_policy
    r_a = a_idx.shape[1]
    r_cap = round_capacity(r_a, policy)
    if r_cap != r_a:
        a_idx = jnp.pad(a_idx, ((0, 0), (0, r_cap - r_a)))
    return spgemm_symbolic(a_idx, a_nnz, b_bitmask, interpret=interpret)
