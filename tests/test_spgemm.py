"""Core SpGEMM tests: two-phase vs Gustavson oracle, compression rules,
reuse semantics, meta-algorithm — including hypothesis property tests."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.extend.core import Var
from hypothesis import given, settings, strategies as st

from repro.core import (
    COMPRESSION_CF_CUTOFF,
    compress_matrix,
    compression_decision,
    flops_stats,
    numeric_dense_acc,
    numeric_fresh,
    numeric_reuse,
    spgemm,
    symbolic,
    symbolic_dense_bitmask,
    bitmask_rows,
    choose_method,
    expand_and_sort,
    expand_products,
    plan_from_sorted,
)
from repro.core.meta import DENSE_K_CUTOFF
from repro.core.spgemm import _repad_csr
from repro.sparse import (
    CSR,
    banded_csr,
    dense_spgemm_oracle,
    galerkin_triple,
    gustavson_numpy,
    random_csr,
    rmat_csr,
    stencil2d_csr,
)
from repro.sparse.formats import csr_to_ell


CASES = [
    (random_csr(40, 50, 3.0, 1), random_csr(50, 45, 2.5, 2)),
    (rmat_csr(5, 5, 3), rmat_csr(5, 5, 4)),
    (banded_csr(48, 2, 5), banded_csr(48, 3, 6)),
    (stencil2d_csr(7, 7), stencil2d_csr(7, 7)),
]


@pytest.mark.parametrize("a,b", CASES)
@pytest.mark.parametrize("method", ["sparse", "dense"])
def test_spgemm_matches_oracle(a, b, method):
    res = spgemm(a, b, method=method)
    np.testing.assert_allclose(
        np.asarray(res.c.to_dense()), dense_spgemm_oracle(a, b),
        rtol=1e-4, atol=1e-4,
    )
    # structure: sorted per row, identical to Gustavson's
    ip, ind, _, _ = gustavson_numpy(a, b)
    np.testing.assert_array_equal(np.asarray(res.c.indptr), ip)
    np.testing.assert_array_equal(np.asarray(res.c.indices)[: ip[-1]], ind)


@pytest.mark.parametrize("a,b", CASES)
def test_symbolic_row_sizes(a, b):
    ip, _, _, _ = gustavson_numpy(a, b)
    for compress in ("auto", "always", "never"):
        sizes, stats = symbolic(a, b, compress=compress)
        np.testing.assert_array_equal(np.asarray(sizes), np.diff(ip))


def test_two_phase_reuse_equals_fresh():
    """The paper's Reuse case: same structure, new values, no recompute of
    the symbolic phase — results must equal a fresh run."""
    a = random_csr(30, 40, 3.0, 11)
    b = random_csr(40, 35, 2.0, 12)
    res = spgemm(a, b, method="sparse")
    rng = np.random.default_rng(0)
    new_avals = jnp.asarray(rng.standard_normal(a.nnz_cap), jnp.float32)
    new_bvals = jnp.asarray(rng.standard_normal(b.nnz_cap), jnp.float32)
    a2 = CSR(a.indptr, a.indices, new_avals, a.shape)
    b2 = CSR(b.indptr, b.indices, new_bvals, b.shape)
    reused = numeric_reuse(res.plan, a2.values, b2.values)
    fresh = spgemm(a2, b2, method="sparse")
    nnz = int(fresh.c.nnz())
    np.testing.assert_allclose(
        np.asarray(reused)[:nnz], np.asarray(fresh.c.values)[:nnz],
        rtol=1e-4, atol=1e-5,
    )


def test_explicit_zeros_kept():
    """Numerical cancellation must keep the symbolic structure (the paper's
    accumulators track occupancy, not value != 0)."""
    a = CSR.from_dense(np.array([[1.0, 1.0]], np.float32))
    b = CSR.from_dense(np.array([[1.0], [-1.0]], np.float32))
    res = spgemm(a, b, method="sparse")
    assert int(res.c.nnz()) == 1  # structurally present
    assert abs(float(res.c.values[0])) < 1e-6  # numerically zero


def test_compression_rules():
    # banded matrices compress well (packed columns)
    a = banded_csr(64, 4, 1)
    bc = compress_matrix(a)
    cf, cmrf, use = compression_decision(a, a, bc)
    assert cf < COMPRESSION_CF_CUTOFF and use
    # 1-nnz-per-row matrices cannot compress
    p = CSR.from_dense(np.eye(32, 8, dtype=np.float32).repeat(1, axis=0))
    r, A, p = galerkin_triple(6, 6, 4)
    bcp = compress_matrix(p)
    cf_p, _, use_p = compression_decision(A, p, bcp)
    assert cf_p == 1.0 and not use_p


def test_compressed_sizes_match_bitmask_rows():
    b = random_csr(30, 100, 4.0, 3)
    bc = compress_matrix(b)
    bm = np.asarray(bitmask_rows(b))
    popc = np.unpackbits(bm.view(np.uint8), axis=1).sum(1)
    rn = np.asarray(bc.row_nnz())
    # compressed row sizes == #distinct CSI per row
    ip = np.asarray(b.indptr)
    ix = np.asarray(b.indices)
    for i in range(b.m):
        csis = set(int(c) >> 5 for c in ix[ip[i]: ip[i + 1]])
        assert rn[i] == len(csis)


def test_dense_bitmask_symbolic():
    a = stencil2d_csr(8, 8)
    b = stencil2d_csr(8, 8)
    ell = csr_to_ell(a)
    bm = bitmask_rows(b)
    sizes = symbolic_dense_bitmask(ell, bm, block_rows=16)
    ip, _, _, _ = gustavson_numpy(a, b)
    np.testing.assert_array_equal(np.asarray(sizes), np.diff(ip))


def test_meta_algorithm_cutoffs():
    small_b = random_csr(10, 100, 2.0, 1)
    big_b = CSR(
        indptr=small_b.indptr, indices=small_b.indices,
        values=small_b.values, shape=(10, DENSE_K_CUTOFF + 1),
    )
    a = random_csr(10, 10, 2.0, 2)
    assert choose_method(a, small_b, {}) == "dense"
    assert choose_method(a, big_b, {}) == "sparse"


# NOTE: the meta-algorithm regression tests (choose_method memory guard,
# choose_kernel KeyError, unknown-method validation, lp_insert clamp) live in
# tests/test_lp_kernel.py — this module is collection-skipped when hypothesis
# is absent (conftest.py), and those guards must run everywhere.


def test_triple_product_galerkin():
    """R*A*P multigrid product (24 of the paper's 83 cases are R*A*P)."""
    r, a, p = galerkin_triple(8, 8, 4)
    ap = spgemm(a, p).c
    rap = spgemm(r, ap).c
    want = (np.asarray(r.to_dense()) @ np.asarray(a.to_dense())
            @ np.asarray(p.to_dense()))
    np.testing.assert_allclose(np.asarray(rap.to_dense()), want, rtol=1e-4,
                               atol=1e-4)


@settings(max_examples=20, deadline=None)
@given(
    m=st.integers(2, 24), n=st.integers(2, 24), k=st.integers(2, 24),
    da=st.floats(0.5, 4.0), db=st.floats(0.5, 4.0),
    seed=st.integers(0, 99999),
)
def test_spgemm_property(m, n, k, da, db, seed):
    """For arbitrary random CSR pairs: dense(spgemm(A,B)) == dense(A)@dense(B)
    and symbolic sizes == structural product row sizes."""
    a = random_csr(m, n, da, seed)
    b = random_csr(n, k, db, seed + 1)
    res = spgemm(a, b)
    np.testing.assert_allclose(
        np.asarray(res.c.to_dense()), dense_spgemm_oracle(a, b),
        rtol=1e-3, atol=1e-3,
    )
    sizes, _ = symbolic(a, b)
    mask = (np.asarray(a.to_dense()) != 0) @ (np.asarray(b.to_dense()) != 0)
    np.testing.assert_array_equal(np.asarray(sizes), (mask > 0).sum(1))


def test_flops_stats():
    a = random_csr(20, 30, 2.0, 4)
    b = random_csr(30, 25, 3.0, 5)
    fm, row_flops, maxrf = flops_stats(a, b.row_nnz())
    _, _, _, rf = gustavson_numpy(a, b)
    np.testing.assert_array_equal(np.asarray(row_flops), rf)
    assert int(fm) == rf.sum() and int(maxrf) == rf.max()


# --------------------------------------------------------------------------
# The expansion against a binary search over the product offsets
# --------------------------------------------------------------------------


def _expand_by_search(a, b, fm_cap):
    """Product t -> (row, col, A slot, B slot, valid) by a binary search of
    t in the offsets of each A slot's products, in numpy: the reference the
    scatter-and-prefix-sum expansion must match bit for bit."""
    a_ip, a_ix = np.asarray(a.indptr), np.asarray(a.indices)
    b_ip, b_ix = np.asarray(b.indptr), np.asarray(b.indices)
    live = np.arange(a.nnz_cap) < a_ip[-1]
    j = np.minimum(a_ix, b.m - 1)
    per_slot = np.where(live, np.diff(b_ip)[j], 0)
    offsets = np.concatenate([[0], np.cumsum(per_slot)])
    t = np.arange(fm_cap)
    a_slot = (np.searchsorted(offsets, t, side="right") - 1).clip(
        0, a.nnz_cap - 1)
    valid = t < offsets[-1]
    b_slot = (b_ip[j[a_slot]] + t - offsets[a_slot]).clip(0, b.nnz_cap - 1)
    a_row = np.minimum(np.searchsorted(a_ip[1:], np.arange(a.nnz_cap),
                                       side="right"), a.m - 1)
    return dict(row=np.where(valid, a_row[a_slot], a.m),
                col=np.where(valid, b_ix[b_slot], 0),
                a_slot=a_slot, b_slot=b_slot, valid=valid)


def _empty_first_b_row():
    """A's first slot reads B's row 0, which is empty; A's row 2 is empty."""
    a = np.zeros((5, 5), np.float32)
    a[0, [0, 2]] = 1.0
    a[1, [0, 1]] = 2.0
    a[3, 4] = 3.0
    a[4, [0, 3]] = 4.0
    b = np.zeros((5, 5), np.float32)
    b[1, [0, 3]] = 1.0
    b[2, 1] = 2.0
    b[3, [0, 2, 4]] = 3.0
    b[4, 4] = 4.0
    return CSR.from_dense(a), CSR.from_dense(b)


EXPANSION_CASES = {
    "stencil16": lambda: (stencil2d_csr(16, 16), stencil2d_csr(16, 16)),
    "rmat_s8_ef16": lambda: (rmat_csr(8, 16, 0), rmat_csr(8, 16, 0)),
    "random64_empty_rows": lambda: (random_csr(64, 64, 1.0, 21),
                                    random_csr(64, 64, 1.0, 22)),
    "empty_first_b_row": _empty_first_b_row,
}


@pytest.mark.parametrize("case", sorted(EXPANSION_CASES))
@pytest.mark.parametrize("a_padding", [0, 13])
@pytest.mark.parametrize("fm_extra", [0, 29])
def test_expansion_matches_binary_search(case, a_padding, fm_extra):
    """``expand_products`` equals the binary-search expansion field by field,
    bitwise, padding products included: with A exact or carrying padding
    slots, and with fm_cap equal to f_m or above it."""
    a, b = EXPANSION_CASES[case]()
    if case == "random64_empty_rows":
        assert (np.diff(np.asarray(a.indptr)) == 0).any()
        assert (np.diff(np.asarray(b.indptr)) == 0).any()
    if case == "empty_first_b_row":
        assert int(b.indptr[1]) == 0 and int(a.indices[0]) == 0
    nnz = int(a.indptr[-1])
    assert a.nnz_cap == nnz
    a = _repad_csr(a, nnz + a_padding)
    fm = int(flops_stats(a, b.row_nnz())[0])
    fm_cap = fm + fm_extra
    got = expand_products(a, b, fm_cap)
    want = _expand_by_search(a, b, fm_cap)
    for field, ref in want.items():
        out = np.asarray(getattr(got, field))
        assert out.dtype == (np.bool_ if field == "valid" else np.int32), field
        np.testing.assert_array_equal(out, ref, err_msg=field)


def test_plan_build_reads_every_field_of_the_expansion():
    """``expand_and_sort`` returns only what ``plan_from_sorted`` reads: the
    plan build holds the whole expansion beside the plan, so a field it
    does not read is device memory held for nothing at the peak."""
    a = stencil2d_csr(8, 8)
    fm_cap = int(flops_stats(a, a.row_nnz())[0])
    sx = expand_and_sort(a, a, fm_cap)
    nnz_cap = int(np.asarray(sx.row_sizes).sum())
    outer = jax.make_jaxpr(
        lambda sx: plan_from_sorted(sx, a.k, nnz_cap))(sx).jaxpr
    (call,) = outer.eqns
    inner = call.params["jaxpr"].jaxpr
    read = {v for e in inner.eqns for v in e.invars
            if isinstance(v, Var)}
    read |= {v for v in inner.outvars if isinstance(v, Var)}
    unread = [f for f, v in zip(sx._fields, inner.invars) if v not in read]
    assert unread == []
