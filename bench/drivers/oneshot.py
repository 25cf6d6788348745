"""One-shot traffic: a structure the program sees for the first time.

Each timed call is ``spgemm(a, a, plan_cache=False)`` on the operand as a
user hands it over (exact size, not pre-bucketed), its values cycling
through the run's pool, and ends when all of C is on the device. No plan
cache: a call that found a cached plan would be a replay, not a one-shot.
"""
from __future__ import annotations

import importlib


class Driver:
    def __init__(self, indptr, indices, shape, pool, params):
        from repro.sparse.formats import CSR

        self._spgemm = importlib.import_module("repro.core.spgemm")
        self.mats = [CSR(indptr, indices, v, shape) for v in pool]

    def call(self, i: int):
        """One multiply; returns (pool index, C)."""
        import jax

        k = i % len(self.mats)
        a = self.mats[k]
        res = self._spgemm.spgemm(a, a, plan_cache=False)
        if res.stats.get("cache") != "bypass":
            raise RuntimeError(f"one-shot call hit a plan cache: {res.stats}")
        jax.block_until_ready(res.c)
        return k, res.c

    @staticmethod
    def to_host(c):
        """(indptr, indices, values) of one call's C, on the host."""
        import numpy as np

        return np.asarray(c.indptr), np.asarray(c.indices), np.asarray(c.values)
