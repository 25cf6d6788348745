"""ShardedPlan: the two-phase SpGEMM plan lifecycle lifted onto a JAX mesh.

A ``ShardedPlan`` is a stacked per-shard ``SpgemmPlan``: every array carries
a leading shard axis ``S`` and *uniform* capacities (the max over shards,
bucketed through ``core.meta.round_capacity`` so shards share capacity
buckets — and compiled executables — with the single-device path). Building
one costs:

  1. ONE sharded expand-and-sort pass (``shard_map`` over the ``data``
     axis): each shard enumerates and sorts its own products, returning the
     stacked ``SortedExpansion`` — the sharded analog of the single-device
     single-expansion contract (the expansion is never re-run for the plan);
  2. ONE host cap-sync: the per-shard nnz(C) maxima come back to the host
     and pick the uniform ``nnz_cap`` bucket (the same role as the paper's
     host-side allocation between the symbolic and numeric phases);
  3. a vmapped ``plan_from_sorted`` over the stacked expansion — pure
     composition, no second sort.

The plan also pins the *value routing* so replays never touch structure:

  * ``a_perm`` (S, a_cap): global A value slot feeding each shard slot —
    fresh A values are re-sharded with one gather;
  * ``b_shard_perm`` / ``b_perm`` (allgather placement only): how B values
    shard before the collective and how the flattened all-gather maps onto
    the concatenated global B layout the plan was built against. B's
    *structure* all-gather (``concat_csr_shards``) happens once, here —
    replays only all-gather values.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.core.distributed import (
    ShardedCSR,
    allgather_value_perm,
    concat_csr_shards,
    partition_rows,
    partition_value_map,
    shard_fm_cap,
)
from repro.core.meta import DEFAULT_PAD_POLICY, round_capacity
from repro.core.spgemm import (
    SortedExpansion,
    expand_and_sort,
    plan_from_sorted,
)
from repro.sparse.formats import CSR

B_PLACEMENTS = ("replicated", "allgather")


class ShardedPlan(NamedTuple):
    """Stacked per-shard numeric plan (leading axis S, uniform caps).

    ``indptr``/``indices`` describe each shard's rows of C; ``seg_ids`` /
    ``a_slot_s`` / ``b_slot_s`` are the per-shard precomposed v2 replay maps
    (see ``SpgemmPlan``); the perms route *values* between the global and
    sharded layouts. For the replicated placement the B perms are empty
    ``(0,)``-shaped placeholders.
    """

    indptr: jax.Array  # (S, m_loc+1) int32 — per-shard C row pointers
    indices: jax.Array  # (S, nnz_cap) int32 — per-shard C columns
    seg_ids: jax.Array  # (S, fm_cap) int32 — sorted product -> C slot
    a_slot_s: jax.Array  # (S, fm_cap) int32 — A slot per sorted product
    b_slot_s: jax.Array  # (S, fm_cap) int32 — B slot per sorted product
    a_perm: jax.Array  # (S, a_cap) int32 — global A value slot per shard slot
    b_shard_perm: jax.Array  # (S, b_cap) int32 (allgather) — B value sharding
    b_perm: jax.Array  # (S*b_cap,) int32 (allgather) — gathered -> concat slot
    shape: tuple  # global (m, k) of C

    @property
    def num_shards(self) -> int:
        return self.indptr.shape[0]

    @property
    def m_loc(self) -> int:
        return self.indptr.shape[1] - 1

    @property
    def nnz_cap(self) -> int:
        return self.indices.shape[1]

    @property
    def fm_cap(self) -> int:
        return self.seg_ids.shape[1]


def dist_expand_and_sort(a_sh: ShardedCSR, b: CSR | ShardedCSR, mesh,
                         axis: str, fm_cap: int) -> SortedExpansion:
    """ONE sharded expansion+sort: stacked ``SortedExpansion`` (leading S).

    ``row_sizes`` (S, m_loc) doubles as the sharded symbolic answer — the
    host reads its per-shard sums to pick the uniform ``nnz_cap`` bucket,
    then feeds the *same* expansion to the plan build (never re-expanded).
    """
    arrays, static = mesh_expand_args(a_sh, b, mesh, axis, fm_cap)
    return expand_on_mesh(*arrays, **static)


def mesh_expand_args(a_sh: ShardedCSR, b: CSR | ShardedCSR, mesh, axis: str,
                     fm_cap: int) -> tuple[tuple, dict]:
    """``expand_on_mesh``'s (arrays, static kwargs) for these operands."""
    return ((a_sh.indptr, a_sh.indices, a_sh.values, b.indptr, b.indices,
             b.values),
            dict(mesh=mesh, axis=axis, fm_cap=fm_cap,
                 a_shape=tuple(a_sh.shape), b_shape=tuple(b.shape)))


@partial(jax.jit, static_argnames=("mesh", "axis", "fm_cap", "a_shape",
                                   "b_shape"))
def expand_on_mesh(ip, ix, vl, b_ip, b_ix, b_vl, *, mesh, axis: str,
                   fm_cap: int, a_shape: tuple, b_shape: tuple):
    """The jitted program behind ``dist_expand_and_sort``, on raw arrays:
    A's stacked shards and B either whole (1-D arrays: replicated) or
    stacked shards (2-D: all-gathered inside). One named program, so a
    caller can compile it ahead (``expand_on_mesh.lower(...)``)."""
    m_loc = ip.shape[1] - 1
    k = b_shape[1]
    replicated = b_ip.ndim == 1

    def fn(ip, ix, vl, b_ip, b_ix, b_vl):
        a_loc = CSR(indptr=ip[0], indices=ix[0], values=vl[0],
                    shape=(m_loc, a_shape[1]))
        if replicated:
            b_loc = CSR(indptr=b_ip, indices=b_ix, values=b_vl, shape=b_shape)
        else:
            b_ips = jax.lax.all_gather(b_ip[0], axis)
            b_ixs = jax.lax.all_gather(b_ix[0], axis)
            b_vls = jax.lax.all_gather(b_vl[0], axis)
            b_loc = concat_csr_shards(b_ips, b_ixs, b_vls, k)
        sx = expand_and_sort(a_loc, b_loc, fm_cap)
        return jax.tree.map(lambda x: x[None], sx)

    b_specs = (P(), P(), P()) if replicated else (P(axis), P(axis), P(axis))
    out_specs = SortedExpansion(*([P(axis)] * len(SortedExpansion._fields)))
    return shard_map(
        fn, mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)) + b_specs,
        out_specs=out_specs,
    )(ip, ix, vl, b_ip, b_ix, b_vl)


def build_sharded_plan(a: CSR, b: CSR, mesh, *, axis: str = "data",
                       b_placement: str = "replicated",
                       pad_policy: str | None = None) -> ShardedPlan:
    """Pin the full sharded plan lifecycle: partition -> one sharded
    expand/sort -> one host cap-sync -> stacked plan composition.

    ``a`` and ``b`` are the *global* operands (callers that also feed the
    single-device path should pass them through ``prepare_sparse_inputs``
    first so both paths hash and bucket identically).
    """
    if b_placement not in B_PLACEMENTS:
        from repro.runtime.validate import SpgemmConfigError  # cycle-free
        raise SpgemmConfigError(
            f"unknown b_placement {b_placement!r}; expected one of {B_PLACEMENTS}")
    policy = DEFAULT_PAD_POLICY if pad_policy is None else pad_policy
    num = mesh.shape[axis]
    a_sh = partition_rows(a, num, policy)
    a_perm = partition_value_map(a, num, policy)
    if b_placement == "replicated":
        b_in: CSR | ShardedCSR = b
        b_shard_perm = np.zeros((num, 0), np.int32)
        b_perm = np.zeros((0,), np.int32)
    else:
        b_sh = partition_rows(b, num, policy)
        b_in = b_sh
        b_shard_perm = partition_value_map(b, num, policy)
        b_perm = allgather_value_perm(b_sh)

    fm_cap = shard_fm_cap(a_sh, b, policy)
    sx = dist_expand_and_sort(a_sh, b_in, mesh, axis, fm_cap)
    # the one host round-trip between phases: uniform nnz bucket over shards
    nnz_cap = round_capacity(int(jnp.max(jnp.sum(sx.row_sizes, axis=1))), policy)
    k = b.shape[1]

    def build(one: SortedExpansion):
        p = plan_from_sorted(one, k, nnz_cap)
        return p.indptr, p.indices, p.seg_ids, p.a_slot_s, p.b_slot_s

    ip, ix, seg, asl, bsl = jax.vmap(build)(sx)
    # pin every plan array on the mesh: per-shard stacks one slab per device,
    # the value-routing perms that act on whole buffers replicated
    sharded = NamedSharding(mesh, P(axis))
    replicated = NamedSharding(mesh, P())
    ip, ix, seg, asl, bsl, a_perm = jax.device_put(
        (ip, ix, seg, asl, bsl, a_perm), sharded)
    b_shard_perm, b_perm = jax.device_put((b_shard_perm, b_perm), replicated)
    return ShardedPlan(
        indptr=ip, indices=ix, seg_ids=seg, a_slot_s=asl, b_slot_s=bsl,
        a_perm=a_perm, b_shard_perm=b_shard_perm, b_perm=b_perm,
        shape=(a.m, k),
    )
