"""Replay traffic: the paper's Reuse case (AMG setup whose values change
while the structure stays).

Set-up pins ``ReuseExecutor.from_matrices(a, a)``; each timed call is
``ex.apply(v, v)`` with v cycling through the run's pool of value sets on
the device. A call returns once it is sent; the harness waits for C's
values, keeping the traffic's ``ahead_products`` of later calls queued on
the device meanwhile.
"""
from __future__ import annotations

import importlib


class Driver:
    def __init__(self, indptr, indices, shape, pool, params):
        from repro.sparse.formats import CSR

        executor = importlib.import_module("repro.core.executor")
        a = CSR(indptr, indices, pool[0], shape)
        self.ex = executor.ReuseExecutor.from_matrices(a, a)
        self.pool = pool

    def call(self, i: int):
        """Send one replay; returns (pool index, C's values, maybe not yet
        computed)."""
        k = i % len(self.pool)
        v = self.pool[k]
        return k, self.ex.apply(v, v)

    def to_host(self, values):
        """(indptr, indices, values) of one call's C, on the host: the pinned
        plan's structure with the call's values."""
        import numpy as np

        plan = self.ex.plan
        return (np.asarray(plan.indptr), np.asarray(plan.indices),
                np.asarray(values))
