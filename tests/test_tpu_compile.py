"""Compiles for a described TPU v5e chip: the XLA main path at the chip smoke
sizes and every Pallas SpGEMM kernel at its size bound.

Nothing runs: the TPU compiler, installed here, compiles for one chip of a
``v5e:2x2`` topology it is only told about. That finds what interpret mode
cannot — block shapes Mosaic refuses, kernels past SMEM/VMEM, programs past
the chip's 16 GB. The topology is described inside a fixture, never at import
or collection, so every test worker collects the same tests.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.executor import _apply_batched
from repro.core.spgemm import (SpgemmPlan, expand_and_sort, expand_products,
                               numeric_reuse, plan_from_sorted)
from repro.kernels import limits
from repro.kernels.segsum_reuse import segsum_reuse_arrays
from repro.kernels.spgemm_lp import lp_reuse_arrays, spgemm_lp
from repro.kernels.spgemm_numeric import spgemm_numeric
from repro.kernels.spgemm_symbolic import spgemm_symbolic
from repro.runtime.validate import SpgemmConfigError
from repro.sparse.formats import CSR

HBM_BYTES = 16 * 10**9  # one v5e chip
F32, I32 = jnp.float32, jnp.int32

# chip_smoke.py's deployments after bucketing (round_capacity "pow2"):
# (m = k, A's nnz cap, fm_cap, nnz(C) cap). stencil2d_csr(2048, 2048)^2:
# nnz 20963328, f_m 104783880, nnz(C) 54484996 — (m+1)*k > 2^31, so the
# expansion takes the multi-key sort. rmat_csr(15, 16)^2: nnz 467722,
# f_m 146324174, nnz(C) 57597840 — one packed int32 key.
SMOKE_SIZES = {
    "stencil2d_2048": (2048 * 2048, 1 << 25, 1 << 27, 1 << 26),
    "rmat_s15_ef16": (1 << 15, 1 << 19, 1 << 28, 1 << 26),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def shape(one_chip):
    def make(dims, dtype=I32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def _hbm_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def _csr(shape, m, cap):
    return CSR(indptr=shape((m + 1,)), indices=shape((cap,)),
               values=shape((cap,), F32), shape=(m, m))


def _plan(shape, m, fm_cap, nnz_cap):
    return SpgemmPlan(indptr=shape((m + 1,)), indices=shape((nnz_cap,)),
                      seg_ids=shape((fm_cap,)), a_slot_s=shape((fm_cap,)),
                      b_slot_s=shape((fm_cap,)), shape=(m, m))


# --------------------------------------------------------------------------
# XLA main path at the smoke sizes
# --------------------------------------------------------------------------


@pytest.mark.parametrize("deployment", sorted(SMOKE_SIZES))
def test_plan_build_fits_one_chip(shape, deployment):
    m, a_cap, fm_cap, nnz_cap = SMOKE_SIZES[deployment]
    a = _csr(shape, m, a_cap)
    expand = expand_and_sort.lower(a, a, fm_cap=fm_cap).compile()
    assert _hbm_bytes(expand) <= HBM_BYTES
    sx = jax.eval_shape(lambda x: expand_and_sort(x, x, fm_cap), a)
    sx = jax.tree.map(lambda s: shape(s.shape, s.dtype), sx)
    build = plan_from_sorted.lower(sx, k=m, nnz_cap=nnz_cap).compile()
    # the whole expansion stays alive while the plan is built from it
    peak = (expand.memory_analysis().output_size_in_bytes
            + build.memory_analysis().output_size_in_bytes
            + build.memory_analysis().temp_size_in_bytes)
    assert peak <= HBM_BYTES


@pytest.mark.parametrize("deployment", sorted(SMOKE_SIZES))
def test_expansion_compiles_without_a_loop(shape, deployment):
    """The expansion finds each product's A slot by scatter and prefix sum:
    the chip's program holds no ``while`` (a binary search over the
    products would be one, a gather over all of them per bisection step)."""
    m, a_cap, fm_cap, _ = SMOKE_SIZES[deployment]
    a = _csr(shape, m, a_cap)
    text = expand_products.lower(a, a, fm_cap=fm_cap).compile().as_text()
    assert not re.search(r"[\]})] while\(", text)
    assert re.search(r"[\]})] scatter\(", text)


@pytest.mark.parametrize("deployment", sorted(SMOKE_SIZES))
def test_replay_fits_one_chip(shape, deployment):
    m, a_cap, fm_cap, nnz_cap = SMOKE_SIZES[deployment]
    plan = _plan(shape, m, fm_cap, nnz_cap)
    one = numeric_reuse.lower(plan, shape((a_cap,), F32),
                              shape((a_cap,), F32)).compile()
    assert _hbm_bytes(one) <= HBM_BYTES
    batched = _apply_batched.lower(plan, shape((4, a_cap), F32),
                                   shape((4, a_cap), F32), a_axis=0,
                                   b_axis=0).compile()
    assert _hbm_bytes(batched) <= HBM_BYTES


# --------------------------------------------------------------------------
# Pallas kernels at their bounds (kernels.limits) and one step past them
# --------------------------------------------------------------------------


def _ell(shape, m, r_a, n, r_b, r_c):
    """(m, rA) A, (n, rB) B and (m, rC) C in ELL layout, for dense_acc."""
    return (shape((m, r_a)), shape((m, r_a), F32), shape((m,)),
            shape((n, r_b)), shape((n, r_b), F32), shape((m, r_c)),
            shape((m,)))


def _ell_lp(shape, m, r_a, n, r_b, r_c):
    """The same operands in flat_lp's order (B's row counts after B)."""
    return (shape((m, r_a)), shape((m, r_a), F32), shape((m,)),
            shape((n, r_b)), shape((n, r_b), F32), shape((n,)),
            shape((m, r_c)), shape((m,)))


def _sym(shape, m, r_a, k32):
    return shape((m, r_a)), shape((m,)), shape((256, k32), jnp.uint32)


def _replay(shape, fm, na, nb):
    return (shape((fm,)), shape((fm,)), shape((fm,)), shape((na,), F32),
            shape((nb,), F32))


SMEM_ROWS_RA8 = limits.SMEM_WORDS // (8 + 2)  # dense_acc rows at rA=8
LP_ROWS = limits.SMEM_WORDS // (256 + 3)  # flat_lp rows at rA=256, n=m
SYM_ROWS = limits.SMEM_WORDS // (8 + 1)
STENCIL_K32 = (2048 * 2048) // 32

# name -> (kernel, shapes(shape), static kwargs): each at its bound, at the
# widths of a smoke deployment (stencil rA=rB=8, rC=16; RMAT rB=4096)
AT_BOUND = {
    "symbolic_smem": (spgemm_symbolic,
                      lambda s: _sym(s, SYM_ROWS, 8, STENCIL_K32), {}),
    "symbolic_vmem": (spgemm_symbolic,
                      lambda s: _sym(s, 1024, 8, limits.SYMBOLIC_MAX_K32), {}),
    "dense_acc_smem": (spgemm_numeric,
                       lambda s: _ell(s, SMEM_ROWS_RA8, 8, 4096, 8, 16),
                       {"k": limits.DENSE_ACC_MAX_K_PAD}),
    "dense_acc_vmem": (spgemm_numeric,
                       lambda s: _ell(s, 1024, 8, 4096,
                                      limits.DENSE_ACC_MAX_WIDTH,
                                      limits.DENSE_ACC_MAX_WIDTH),
                       {"k": limits.DENSE_ACC_MAX_K_PAD}),
    "flat_lp": (spgemm_lp,
                lambda s: _ell_lp(s, LP_ROWS, 256, LP_ROWS, 4096, 2048), {}),
    "segsum_reuse": (segsum_reuse_arrays,
                     lambda s: _replay(s, 1 << 28, 1 << 21, 1 << 20),
                     {"nnz_cap": limits.REPLAY_MAX_NNZ}),
    "lp_reuse": (lp_reuse_arrays,
                 lambda s: _replay(s, 1 << 28, 1 << 21, 1 << 20),
                 {"nnz_cap": limits.REPLAY_MAX_NNZ}),
}

PAST_BOUND = {
    "symbolic_smem": (spgemm_symbolic,
                      lambda s: _sym(s, SYM_ROWS + 1, 8, STENCIL_K32), {}),
    "symbolic_vmem": (spgemm_symbolic,
                      lambda s: _sym(s, 1024, 8,
                                     limits.SYMBOLIC_MAX_K32 + 128), {}),
    "dense_acc_smem": (spgemm_numeric,
                       lambda s: _ell(s, SMEM_ROWS_RA8 + 1, 8, 4096, 8, 16),
                       {"k": 4096}),
    "dense_acc_stencil_k": (spgemm_numeric,
                            lambda s: _ell(s, 1024, 8, 4096, 8, 16),
                            {"k": 2048 * 2048}),
    "dense_acc_width": (spgemm_numeric,
                        lambda s: _ell(s, 1024, 8, 4096,
                                       2 * limits.DENSE_ACC_MAX_WIDTH, 16),
                        {"k": 4096}),
    "flat_lp_smem": (spgemm_lp,
                     lambda s: _ell_lp(s, LP_ROWS + 1, 256, LP_ROWS + 1,
                                       4096, 2048), {}),
    "flat_lp_rc": (spgemm_lp,
                   lambda s: _ell_lp(s, 1024, 8, 1024, 8, 4096), {}),
    "segsum_reuse_values": (segsum_reuse_arrays,
                            lambda s: _replay(s, 1 << 20, (1 << 21) + 512,
                                              1 << 20),
                            {"nnz_cap": 1 << 20}),
    "lp_reuse_nnz": (lp_reuse_arrays,
                     lambda s: _replay(s, 1 << 20, 1 << 12, 1 << 12),
                     {"nnz_cap": limits.REPLAY_MAX_NNZ + 8}),
}


@pytest.mark.parametrize("name", sorted(AT_BOUND))
def test_kernel_compiles_at_its_bound(shape, name):
    kernel, shapes, static = AT_BOUND[name]
    compiled = kernel.lower(*shapes(shape), **static).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", sorted(PAST_BOUND))
def test_kernel_refuses_past_its_bound(shape, name):
    kernel, shapes, static = PAST_BOUND[name]
    with pytest.raises(SpgemmConfigError, match="bound"):
        kernel.lower(*shapes(shape), **static)
