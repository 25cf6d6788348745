"""Graph500 Kronecker (RMAT) graph: the structure of A.

The edges are the repository's ``rmat_csr`` draw, copied here so that a
change to ``src/`` cannot move the yardstick: the same numpy generator, the
same per-bit quadrant draws, duplicates merged. Then, as Graph500's
generator does, the vertex labels are permuted at random (from the same
structure seed), so that the heavy rows and columns do not all sit at low
indices. Only the structure is made here; the values come from the run's
seed.
"""
from __future__ import annotations

import numpy as np


def edges(scale: int, edge_factor: int, a: float, b: float, c: float,
          rng: np.random.Generator):
    """(rows, cols) of edge_factor * 2**scale edges drawn quadrant by
    quadrant, one bit of each endpoint per level."""
    count = (1 << scale) * edge_factor
    rows = np.zeros(count, np.int64)
    cols = np.zeros(count, np.int64)
    for bit in range(scale):
        r = rng.random(count)
        rows |= (r >= a + b).astype(np.int64) << bit
        cols |= ((r >= a) & (r < a + b) | (r >= a + b + c)).astype(np.int64) << bit
    return rows, cols


def structure(scale: int, edge_factor: int, a: float, b: float, c: float,
              structure_seed: int):
    """(indptr, indices, shape) of an RMAT graph with 2**scale vertices
    and edge_factor * 2**scale drawn edges, its vertex labels permuted,
    rows sorted, columns sorted within each row, duplicate edges merged."""
    rng = np.random.default_rng(structure_seed)
    n = 1 << scale
    rows, cols = edges(scale, edge_factor, a, b, c, rng)
    label = rng.permutation(n)
    key = np.unique(label[rows] * n + label[cols])
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(key // n, minlength=n), out=indptr[1:])
    return indptr.astype(np.int32), (key % n).astype(np.int32), (n, n)
