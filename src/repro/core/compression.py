"""Matrix compression for the symbolic phase (paper §3.2).

The graph of B is binary, so 32 columns pack into one uint32: a row's columns
become (CSI = col >> 5, CS = 1 << (col & 31)) pairs, merged per-CSI with
BITWISE-OR. Row unions in the symbolic phase then operate on the compressed
rows, cutting f_m by the compression factor CF. The paper's rule: compress
only when CF <= 0.85 (>= 15% flop reduction); we keep the constant verbatim.

This transfers to TPU unchanged — uint32 lanes OR on the VPU, and
``lax.population_count`` recovers set sizes.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.utils import segmented_scan, segment_ends
from repro.sparse.formats import CSR, csr_row_ids

COMPRESSION_CF_CUTOFF = 0.85  # paper §3.2: apply compression iff CF <= 0.85
BITS = 32


class CompressedMatrix(NamedTuple):
    """B_c: CSR over (row, CSI) with OR-merged CS bitmask payloads."""

    indptr: jax.Array  # (m+1,) int32
    csi: jax.Array  # (nnz_cap,) int32 — column-set index (col >> 5)
    cs: jax.Array  # (nnz_cap,) uint32 — column-set bitmask
    shape: tuple  # (m, k) of the *original* matrix

    @property
    def k_compressed(self) -> int:
        return -(-self.shape[1] // BITS)

    def row_nnz(self) -> jax.Array:
        return self.indptr[1:] - self.indptr[:-1]


@partial(jax.jit, static_argnames=("nnz_cap",))
def compress_matrix(b: CSR, nnz_cap: int | None = None) -> CompressedMatrix:
    """Build B_c. Output capacity defaults to B's (compression never grows).

    Entries within a CSR row are deduped by CSI via sort + segmented OR-scan;
    because column ids within a row are unique, bits within a (row, CSI) group
    are distinct.
    """
    cap = b.nnz_cap if nnz_cap is None else nnz_cap
    rows = csr_row_ids(b.indptr, b.nnz_cap)
    valid = b.valid_mask()
    csi = (b.indices >> 5).astype(jnp.int32)
    cs = (jnp.uint32(1) << (b.indices & 31).astype(jnp.uint32)).astype(jnp.uint32)
    # Sort by (valid desc implicitly handled by pushing invalid to the end
    # via a large key), then (row, csi).
    big = jnp.int32(b.shape[0] + 1)
    sort_rows = jnp.where(valid, rows, big)
    order = jnp.lexsort((csi, sort_rows))
    rows_s = sort_rows[order]  # invalid slots carry row=big -> own trailing group
    csi_s = csi[order]
    cs_s = cs[order]
    valid_s = valid[order]

    heads = jnp.concatenate(
        [
            jnp.ones((1,), jnp.bool_),
            (rows_s[1:] != rows_s[:-1]) | (csi_s[1:] != csi_s[:-1]),
        ]
    )
    or_scan = segmented_scan(cs_s, heads, jnp.bitwise_or)
    ends = segment_ends(heads) & valid_s

    # Compact the group representatives to the front (stable): order by
    # (not end) so ends come first in (row, csi) order.
    comp_order = jnp.lexsort((jnp.arange(cap, dtype=jnp.int32), ~ends))
    out_csi = jnp.where(ends, csi_s, 0)[comp_order]
    out_cs = jnp.where(ends, or_scan, jnp.uint32(0))[comp_order]
    out_rows = jnp.where(ends, rows_s, big)[comp_order]

    n_groups = jnp.sum(ends.astype(jnp.int32))
    m = b.shape[0]
    counts = jnp.zeros((m,), jnp.int32).at[jnp.minimum(out_rows, m - 1)].add(
        (jnp.arange(cap) < n_groups).astype(jnp.int32), mode="drop"
    )
    indptr = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts).astype(jnp.int32)]
    )
    return CompressedMatrix(indptr=indptr, csi=out_csi, cs=out_cs, shape=b.shape)


def per_slot_products(a: CSR, b_row_nnz: jax.Array) -> jax.Array:
    """Products each A slot makes: the size of the B row its column names
    (0 on padding slots). Sums to f_m; the expansion lays them out."""
    j = jnp.minimum(a.indices, b_row_nnz.shape[0] - 1)
    return jnp.where(a.valid_mask(), b_row_nnz[j], 0)


@jax.jit
def flops_stats(a: CSR, b_row_nnz: jax.Array):
    """(f_m total, per-row flops, MAXRF) for C = A*B given B's row sizes.

    f_m is the paper's multiplication count; MAXRF its max-row upper bound
    used to size L2 accumulator chunks (memory pool CHUNKSIZE).
    """
    rows = csr_row_ids(a.indptr, a.nnz_cap)
    contrib = per_slot_products(a, b_row_nnz)
    row_flops = jnp.zeros((a.m,), jnp.int64 if contrib.dtype == jnp.int64 else jnp.int32)
    row_flops = row_flops.at[rows].add(contrib, mode="drop")
    return jnp.sum(row_flops), row_flops, jnp.max(row_flops)


def compression_decision(a: CSR, b: CSR, bc: CompressedMatrix):
    """Host-facing: (CF, CMRF, use_compression). Mirrors the 15% rule."""
    fm, _, maxrf = flops_stats(a, b.row_nnz())
    fm_c, _, maxrf_c = flops_stats(a, bc.row_nnz())
    fm = max(int(fm), 1)
    maxrf = max(int(maxrf), 1)
    cf = float(int(fm_c)) / fm
    cmrf = float(int(maxrf_c)) / maxrf
    return cf, cmrf, cf <= COMPRESSION_CF_CUTOFF


def bitmask_rows(b: CSR) -> jax.Array:
    """(m, ceil(k/32)) uint32 dense bitmask of B's structure (KKDENSE symbolic
    feed). Distinct column bits per row ⇒ scatter-add == scatter-or."""
    k32 = -(-b.k // BITS)
    rows = csr_row_ids(b.indptr, b.nnz_cap)
    valid = b.valid_mask()
    csi = jnp.where(valid, (b.indices >> 5).astype(jnp.int32), 0)
    cs = jnp.where(
        valid, (jnp.uint32(1) << (b.indices & 31).astype(jnp.uint32)), jnp.uint32(0)
    )
    rows = jnp.where(valid, rows, 0)
    out = jnp.zeros((b.m, k32), jnp.uint32)
    return out.at[rows, csi].add(cs)
