"""Pallas kernel sweeps (interpret=True) vs the pure-jnp ref.py oracles.

Shapes/dtypes swept per kernel; SpGEMM kernels additionally cross-checked
against the Gustavson numpy oracle.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import bitmask_rows
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.grouped_matmul import TM, grouped_matmul
from repro.kernels.spgemm_numeric import spgemm_numeric
from repro.kernels.spgemm_symbolic import spgemm_symbolic
from repro.kernels.ops import pallas_spgemm
from repro.sparse import (
    gustavson_ell_structure,
    gustavson_numpy,
    random_csr,
    stencil2d_csr,
)
from repro.sparse.formats import csr_to_ell

RNG = np.random.default_rng(0)


def _pad_bitmask(bm):
    pad = (-bm.shape[1]) % 128
    return jnp.pad(bm, ((0, 0), (0, pad))) if pad else bm


@pytest.mark.parametrize("m,n,k,da,db", [
    (16, 24, 150, 3.0, 4.0),
    (32, 32, 700, 2.0, 6.0),
    (8, 64, 4096, 4.0, 2.0),
])
def test_spgemm_symbolic_sweep(m, n, k, da, db):
    a = random_csr(m, n, da, int(da * 10))
    b = random_csr(n, k, db, int(db * 10))
    ell = csr_to_ell(a)
    bm = _pad_bitmask(bitmask_rows(b))
    got = spgemm_symbolic(ell.indices, ell.row_nnz, bm, interpret=True)
    want = ref.spgemm_symbolic_ref(ell.indices, ell.row_nnz, bm)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    ip, _, _, _ = gustavson_numpy(a, b)
    np.testing.assert_array_equal(np.asarray(got), np.diff(ip))


@pytest.mark.parametrize("m,n,k", [(12, 20, 300), (24, 16, 600), (8, 32, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_spgemm_numeric_sweep(m, n, k, dtype):
    a = random_csr(m, n, 3.0, m)
    b = random_csr(n, k, 4.0, n)
    ea, eb = csr_to_ell(a), csr_to_ell(b)
    c_idx, c_nnz = gustavson_ell_structure(a, b)
    got = spgemm_numeric(
        ea.indices, ea.values.astype(dtype), ea.row_nnz, eb.indices,
        eb.values.astype(dtype), jnp.asarray(c_idx), jnp.asarray(c_nnz),
        k=k, interpret=True,
    )
    want = ref.spgemm_numeric_ref(
        ea.indices, ea.values.astype(dtype), eb.indices,
        eb.values.astype(dtype), jnp.asarray(c_idx), jnp.asarray(c_nnz), k,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                               atol=1e-4)


def test_pallas_spgemm_pipeline():
    a = stencil2d_csr(6, 6)
    b = stencil2d_csr(6, 6)
    c_nnz, c_idx, c_val = pallas_spgemm(a, b)
    ip, ind, val, _ = gustavson_numpy(a, b)
    for i in range(a.m):
        n_i = int(c_nnz[i])
        assert n_i == ip[i + 1] - ip[i]
        np.testing.assert_array_equal(np.asarray(c_idx)[i, :n_i], ind[ip[i]: ip[i + 1]])
        np.testing.assert_allclose(
            np.asarray(c_val)[i, :n_i], val[ip[i]: ip[i + 1]], rtol=1e-4,
            atol=1e-5,
        )


def test_bucketed_kernel_wrappers_match_plain():
    """Width-bucketed wrappers (x2 ELL capacity padding) must be semantically
    identical to the unbucketed kernels — padding is masked, output sliced."""
    from repro.kernels.spgemm_numeric import spgemm_numeric_bucketed
    from repro.kernels.spgemm_symbolic import spgemm_symbolic_bucketed

    a = random_csr(14, 18, 3.0, 5)
    b = random_csr(18, 200, 2.5, 6)
    ell = csr_to_ell(a)
    bm = _pad_bitmask(bitmask_rows(b))
    got = spgemm_symbolic_bucketed(ell.indices, ell.row_nnz, bm,
                                   interpret=True)
    ip, ind, val, _ = gustavson_numpy(a, b)
    np.testing.assert_array_equal(np.asarray(got), np.diff(ip))

    eb = csr_to_ell(b)
    c_idx, c_nnz = gustavson_ell_structure(a, b)
    r_c = c_idx.shape[1]
    got_v = spgemm_numeric_bucketed(
        ell.indices, ell.values, ell.row_nnz, eb.indices, eb.values,
        jnp.asarray(c_idx), jnp.asarray(c_nnz), k=b.k, interpret=True,
    )
    assert got_v.shape == (a.m, r_c)  # sliced back to the caller's width
    want_v = ref.spgemm_numeric_ref(
        ell.indices, ell.values, eb.indices, eb.values,
        jnp.asarray(c_idx), jnp.asarray(c_nnz), b.k,
    )
    np.testing.assert_allclose(np.asarray(got_v), np.asarray(want_v),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("e,d,f,blocks", [(4, 256, 256, 6), (8, 128, 384, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_matmul_sweep(e, d, f, blocks, dtype):
    t = blocks * TM
    be = jnp.asarray(np.sort(RNG.integers(0, e, blocks)).astype(np.int32))
    x = jnp.asarray(RNG.standard_normal((t, d)), dtype)
    w = jnp.asarray(RNG.standard_normal((e, d, f)) * 0.1, dtype)
    got = grouped_matmul(x, w, be, interpret=True)
    want = ref.grouped_matmul_ref(x, w, jnp.repeat(be, TM))
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


@pytest.mark.parametrize("hq,hkv,t,d", [(4, 2, 256, 64), (8, 8, 128, 32),
                                        (4, 1, 256, 64)])
@pytest.mark.parametrize("kwargs", [
    dict(causal=True),
    dict(causal=True, window=64),
    dict(causal=True, softcap=30.0),
    dict(causal=False),
])
def test_flash_attention_sweep(hq, hkv, t, d, kwargs):
    q = jnp.asarray(RNG.standard_normal((hq, t, d)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((hkv, t, d)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((hkv, t, d)), jnp.float32)
    got = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True,
                          **kwargs)
    want = ref.flash_attention_ref(q, k, v, **kwargs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3,
                               atol=2e-3)


def test_flash_attention_bf16():
    q = jnp.asarray(RNG.standard_normal((4, 128, 64)), jnp.bfloat16)
    k = jnp.asarray(RNG.standard_normal((2, 128, 64)), jnp.bfloat16)
    v = jnp.asarray(RNG.standard_normal((2, 128, 64)), jnp.bfloat16)
    got = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=5e-2, atol=5e-2,
    )


def _dot_precisions(jaxpr):
    """Precision of every dot_general in ``jaxpr`` and its sub-jaxprs (the
    pallas_call kernel body and its loops included)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            out.append(eqn.params["precision"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_dot_precisions(sub))
    return out


def _ell_operands(m=8, r_a=4, n=8, r_b=8, r_c=16):
    i32, f32 = jnp.int32, jnp.float32
    return (jnp.zeros((m, r_a), i32), jnp.zeros((m, r_a), f32),
            jnp.zeros((m,), i32), jnp.zeros((n, r_b), i32),
            jnp.zeros((n, r_b), f32), jnp.zeros((m, r_c), i32),
            jnp.zeros((m,), i32))


def _replay_operands(fm=1024, na=512, nb=512):
    i32, f32 = jnp.int32, jnp.float32
    return (jnp.zeros((fm,), i32), jnp.zeros((fm,), i32),
            jnp.zeros((fm,), i32), jnp.zeros((na,), f32),
            jnp.zeros((nb,), f32))


@pytest.mark.parametrize("name", ["dense_acc", "segsum_reuse", "lp_reuse"])
def test_onehot_matmuls_keep_f32_values(name):
    """The one-hot scatter/gather matmuls carry f32 values: on the MXU the
    default precision is one bf16 pass, which rounds each value to 8
    mantissa bits, so every such dot must ask for HIGHEST."""
    from repro.kernels.segsum_reuse import segsum_reuse_arrays
    from repro.kernels.spgemm_lp import lp_reuse_arrays

    fn, args, static = {
        "dense_acc": (spgemm_numeric, _ell_operands(), {"k": 1024}),
        "segsum_reuse": (segsum_reuse_arrays, _replay_operands(),
                         {"nnz_cap": 256}),
        "lp_reuse": (lp_reuse_arrays, _replay_operands(), {"nnz_cap": 256}),
    }[name]
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, interpret=True, **static))(*args)
    found = _dot_precisions(jaxpr.jaxpr)
    assert found, "no one-hot matmul found"
    highest = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    assert all(p == highest for p in found), found
