"""The Pallas kernels' size bounds (``kernels.limits``) on the selection
paths: above a bound an explicit kernel raises ``SpgemmConfigError`` before
compiling, and "auto" / measured selection never picks it."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.executor import (ReuseExecutor, fitting_backend,
                                 replay_candidates)
from repro.core.spgemm import SpgemmPlan
from repro.kernels import limits
from repro.kernels.ops import numeric_values, resolve_numeric_kernel
from repro.runtime.validate import SpgemmConfigError
from repro.sparse import random_csr
from repro.sparse.generators import stencil2d_csr


def _rows_past_smem():
    # rA = 5 buckets to 8; dense_acc prefetches m * (8 + 2) words
    side = int(np.ceil(np.sqrt(limits.SMEM_WORDS / 10))) + 1
    return stencil2d_csr(side, side)


def test_ell_misfit_both_sides_of_each_bound():
    words = limits.SMEM_WORDS
    assert limits.ell_misfit("dense_acc", m=words // 10, r_a=8) is None
    assert "SMEM" in limits.ell_misfit("dense_acc", m=words // 10 + 1, r_a=8)
    assert limits.ell_misfit("symbolic", m=words // 9, r_a=8,
                             k=32 * limits.SYMBOLIC_MAX_K32) is None
    assert "VMEM" in limits.ell_misfit("symbolic", m=8, r_a=8,
                                       k=32 * limits.SYMBOLIC_MAX_K32 + 32)
    assert limits.ell_misfit("dense_acc", m=8, r_a=8,
                             k=limits.DENSE_ACC_MAX_K_PAD,
                             r_b=limits.DENSE_ACC_MAX_WIDTH,
                             r_c=limits.DENSE_ACC_MAX_WIDTH) is None
    assert limits.ell_misfit("dense_acc", m=8, r_a=8, k=4096,
                             r_b=limits.DENSE_ACC_MAX_WIDTH + 1) is not None
    assert limits.ell_misfit("dense_acc", m=8, r_a=8,
                             k=limits.DENSE_ACC_MAX_K_PAD + 1) is not None
    assert limits.ell_misfit("flat_lp", m=8, r_a=8, n=8, r_c=2048) is None
    assert limits.ell_misfit("flat_lp", m=8, r_a=8, n=8, r_c=2049) is not None
    assert limits.replay_misfit(1 << 21, 1 << 20, 1 << 24) is None
    assert limits.replay_misfit((1 << 21) + 512, 1 << 20, 1 << 24)
    assert limits.replay_misfit(8, 8, (1 << 24) + 8)


def test_auto_resolves_to_xla_past_the_smem_bound():
    small = stencil2d_csr(20, 20)
    assert resolve_numeric_kernel(small, small) == "dense_acc"
    big = _rows_past_smem()
    assert resolve_numeric_kernel(big, big) == "xla"


def test_auto_respects_the_flat_lp_width_bound():
    a = random_csr(64, 64, 40.0, seed=3)  # avg row flops >= 256 -> flat_lp
    assert resolve_numeric_kernel(a, a, widths=(64, 64, 2048)) == "flat_lp"
    assert resolve_numeric_kernel(a, a, widths=(64, 64, 4096)) == "xla"


def test_explicit_kernel_past_its_bound_raises_before_compiling():
    big = _rows_past_smem()
    c_idx = jnp.zeros((big.m, 16), jnp.int32)
    c_nnz = jnp.zeros((big.m,), jnp.int32)
    for kname in ("dense_acc", "flat_lp"):
        with pytest.raises(SpgemmConfigError, match="SMEM"):
            numeric_values(big, big, c_idx, c_nnz, kernel=kname)


def _plan(nnz_cap: int, fm_cap: int = 1024) -> SpgemmPlan:
    z = jnp.zeros((fm_cap,), jnp.int32)
    return SpgemmPlan(indptr=jnp.zeros((9,), jnp.int32),
                      indices=jnp.zeros((nnz_cap,), jnp.int32),
                      seg_ids=z, a_slot_s=z, b_slot_s=z, shape=(8, 8))


def test_replay_selection_skips_kernels_past_the_bound():
    vals = jnp.ones((64,), jnp.float32)
    small, big = _plan(64), _plan(limits.REPLAY_MAX_NNZ + 8)
    assert set(replay_candidates(small, vals, vals, True)) == {
        "xla", "pallas", "pallas_lp"}
    assert set(replay_candidates(big, vals, vals, True)) == {"xla"}
    assert fitting_backend("pallas_lp", small, vals, vals) == "pallas_lp"
    assert fitting_backend("pallas_lp", big, vals, vals) == "xla"


@pytest.mark.parametrize("backend", ["pallas", "pallas_lp"])
def test_explicit_replay_kernel_past_its_bound_raises(backend):
    vals = jnp.ones((64,), jnp.float32)
    ex = ReuseExecutor(_plan(limits.REPLAY_MAX_NNZ + 8), backend=backend,
                       interpret=True)
    with pytest.raises(SpgemmConfigError, match="bound"):
        ex.apply(vals, vals)
