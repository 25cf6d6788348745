"""The 2-D 5-point Poisson operator on an nx x ny grid, Dirichlet boundary:
the structure of A (the model problem of algebraic multigrid).

Row ``i * ny + j`` couples to its four grid neighbours and itself; rows on
the boundary lose the neighbours outside the grid. Built row by row in
column order, so no sort is needed. Only the structure is made here; the
values come from the run's seed.
"""
from __future__ import annotations

import numpy as np


def structure(nx: int, ny: int):
    """(indptr, indices, shape) of the 5-point stencil, columns sorted."""
    n = nx * ny
    idx = np.arange(n, dtype=np.int64)
    i, j = idx // ny, idx % ny
    # candidate columns in ascending order: up, left, self, right, down
    cand = np.stack([idx - ny, idx - 1, idx, idx + 1, idx + ny], axis=1)
    keep = np.stack([i > 0, j > 0, np.ones(n, bool), j < ny - 1, i < nx - 1],
                    axis=1)
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(keep.sum(axis=1), out=indptr[1:])
    return indptr.astype(np.int32), cand[keep].astype(np.int32), (n, n)
