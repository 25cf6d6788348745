"""A run with the timed path broken underneath: the harness's whole run
(set-up, window, check) past its look for a chip, with each fault a cell of
one chip can have planted in the program's entry point, must come out not
correct."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

import jax.numpy as jnp  # noqa: E402

from bench import harness  # noqa: E402
from bench.tests import tinytree  # noqa: E402

SEED = 31337


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tinytree.make(tmp_path_factory.mktemp("tiny"))


def broken_c(indptr, indices, values, fault):
    """C with one fault planted: half the rows left out, one value or one
    column altered where it is produced."""
    m = indptr.shape[0] - 1
    if fault == "half_left_out":
        values = values.at[indptr[m // 2]:].set(0)
    elif fault == "value_altered":
        values = values.at[indptr[m // 2]].add(1.0)
    elif fault == "column_altered":
        slot = indptr[m // 2]
        indices = indices.at[slot].set((indices[slot] + 1) % m)
    return indices, values


def plant_replay(monkeypatch, fault):
    from repro.core.executor import ReuseExecutor

    real = ReuseExecutor.apply
    last = []

    def apply(self, a_values, b_values, **kw):
        out = real(self, a_values, b_values, **kw)
        if fault == "state_unchanged":  # returns the previous call's answer
            prev = last[-1] if last else out
            last.append(out)
            return prev
        return broken_c(self.plan.indptr, self.plan.indices, out, fault)[1]

    monkeypatch.setattr(ReuseExecutor, "apply", apply)


def plant_oneshot(monkeypatch, fault):
    import importlib

    from repro.sparse.formats import CSR

    mod = importlib.import_module("repro.core.spgemm")
    real = mod.spgemm
    last = []

    def spgemm(a, b, **kw):
        res = real(a, b, **kw)
        if fault == "state_unchanged":
            prev = last[-1] if last else res
            last.append(res)
            return prev
        c = res.c
        ix, vals = broken_c(c.indptr, c.indices, c.values, fault)
        return res._replace(c=CSR(c.indptr, ix, vals, c.shape))

    monkeypatch.setattr(mod, "spgemm", spgemm)


CASES = ([("stencil2d_1024.replay", f) for f in
          ("state_unchanged", "half_left_out", "value_altered")]
         + [("rmat_s14.oneshot", f) for f in
            ("state_unchanged", "half_left_out", "value_altered",
             "column_altered")])


@pytest.mark.parametrize("name,fault", CASES)
def test_planted_fault_is_not_correct(tiny, monkeypatch, name, fault):
    cell = harness.load_cell(tiny, name)
    plant = plant_oneshot if cell.traffic["driver"] == "oneshot" else plant_replay
    plant(monkeypatch, fault)
    _, res = harness.run_cell(cell, SEED, 0.2)
    assert not res["correct"], (fault, res["compared"])


def test_value_altered_shows_in_the_checksum_alone(tiny, monkeypatch):
    """An entry altered outside the sampled rows still fails the run."""
    monkeypatch.setattr(harness, "SAMPLE_ROWS", 0)
    monkeypatch.setattr(harness.reference, "HEAVY_ROWS", 1)
    plant_replay(monkeypatch, "value_altered")
    _, res = harness.run_cell(harness.load_cell(tiny, "stencil2d_1024.replay"),
                              SEED, 0.2)
    cmp = res["compared"]
    assert not res["correct"]
    assert cmp["checksum_err"]["value"] > cmp["checksum_err"]["limit"]
    assert jnp is not None
