"""The float64 reference: it agrees with a dense product, and each number
it compares catches the fault it is there for."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import reference  # noqa: E402
from bench.generators import rmat, stencil2d  # noqa: E402


def dense(ip, ix, v, shape):
    out = np.zeros(shape)
    rows = np.repeat(np.arange(shape[0]), np.diff(ip))
    np.add.at(out, (rows, ix[: len(rows)]), v[: len(rows)])
    return out


def exact_c(a):
    """C = A @ A as CSR (indptr, indices, values) from the dense product,
    with the structure of the symbolic product (explicit zeros kept)."""
    ip, ix, v, shape = a
    d = dense(ip, ix, v, shape)
    pattern = dense(ip, ix, np.ones(len(ix)), shape)
    struct = (pattern @ pattern) != 0
    prod = d @ d
    c_ip = np.concatenate([[0], np.cumsum(struct.sum(1))]).astype(np.int32)
    r, c = np.nonzero(struct)
    return c_ip, c.astype(np.int32), prod[r, c].astype(np.float32)


@pytest.fixture(params=["rmat", "stencil"])
def problem(request):
    if request.param == "rmat":
        ip, ix, shape = rmat.structure(7, 8, 0.57, 0.19, 0.19, 3)
    else:
        ip, ix, shape = stencil2d.structure(9, 7)
    v = np.random.default_rng(0).standard_normal(len(ix)).astype(np.float32)
    a = (ip, ix, v, shape)
    rng = np.random.default_rng(1)
    return a, exact_c(a), rng.standard_normal(shape[0]), rng.standard_normal(shape[1])


def test_rows_match_the_dense_product(problem):
    a, (c_ip, c_ix, c_v), _, _ = problem
    rows = np.arange(a[3][0])
    sizes, cols, vals, mags = reference.product_rows(a, a, rows)
    assert np.array_equal(sizes, np.diff(c_ip))
    assert np.array_equal(cols, c_ix)
    np.testing.assert_allclose(vals, c_v, rtol=1e-5, atol=1e-5)
    assert (mags >= np.abs(vals) - 1e-12).all()


def test_checksum_matches_the_dense_product(problem):
    a, _, u, w = problem
    d = dense(*a)
    want, scale = reference.checksum(a, a, u, w)
    assert want == pytest.approx(u @ (d @ d) @ w, rel=1e-10, abs=1e-10)
    terms = np.abs(u)[:, None] ** 2 * (d ** 2) @ (d ** 2) * np.abs(w)[None, :] ** 2
    assert scale == pytest.approx(np.sqrt(terms.sum()), rel=1e-10)


def numbers(problem, c):
    a, _, u, w = problem
    rows = np.arange(a[3][0])
    return reference.compare(a, a, c, rows, u, w, nnz_c=len(problem[1][1]))


def test_sound_output_reads_rounding_only(problem):
    got = numbers(problem, problem[1])
    assert got["nnz_c_diff"] == 0 and got["rows_wrong"] == 0
    assert got["value_err"] < 1e-6 and got["checksum_err"] < 1e-6


def test_corrupted_column_is_caught(problem):
    c_ip, c_ix, c_v = (x.copy() for x in problem[1])
    slot = c_ip[len(c_ip) // 2]
    c_ix[slot] = (c_ix[slot] + 1) % problem[0][3][1]
    got = numbers(problem, (c_ip, c_ix, c_v))
    assert got["rows_wrong"] >= 1
    assert got["checksum_err"] > 1e-4


def test_corrupted_value_is_caught(problem):
    c_ip, c_ix, c_v = (x.copy() for x in problem[1])
    c_v[c_ip[len(c_ip) // 2]] += 0.5
    got = numbers(problem, (c_ip, c_ix, c_v))
    assert got["rows_wrong"] == 0
    assert got["value_err"] > 1e-3 and got["checksum_err"] > 1e-4


def test_missing_entry_is_caught(problem):
    c_ip, c_ix, c_v = problem[1]
    r = len(c_ip) // 2 + int(np.flatnonzero(np.diff(c_ip)[len(c_ip) // 2:])[0])
    slot = c_ip[r]
    short = c_ip.copy()
    short[r + 1:] -= 1
    got = numbers(problem, (short, np.delete(c_ix, slot), np.delete(c_v, slot)))
    assert got["nnz_c_diff"] == 1 and got["rows_wrong"] == 1


def test_row_pointers_that_are_no_csr_fail_every_number(problem):
    c_ip, c_ix, c_v = (x.copy() for x in problem[1])
    c_ip[len(c_ip) // 2] = c_ip[-1] + 5
    got = numbers(problem, (c_ip, c_ix, c_v))
    assert got["rows_wrong"] == len(c_ip) - 1
    assert got["value_err"] == got["checksum_err"] == 1.0


def test_sample_always_holds_the_heaviest_rows():
    ip, ix, _ = rmat.structure(8, 16, 0.57, 0.19, 0.19, 0)
    flops = reference.row_products(ip, ix, ip)
    rows = reference.sample_rows(ip, ix, ip, 5, np.random.default_rng(4))
    heavy = np.argsort(flops, kind="stable")[-reference.HEAVY_ROWS:]
    assert set(heavy) <= set(rows) and len(rows) <= 5 + reference.HEAVY_ROWS
