"""The plain reference that decides ``correct``: C = A @ B in float64 on the
host, by Gustavson's row-by-row definition, for a sample of C's rows, and a
weighted checksum of all of C.

It imports nothing of the program and takes nothing the program made: the
structures come from ``generators/`` and the values from the run's seed.

Numbers compared (each against its limit in the configuration's file):

- ``nnz_c_diff``: |nnz(C) read from C's row pointers - the configuration's
  stated nnz(C)|, exact.
- ``rows_wrong``: sampled rows whose column list differs from the
  reference's, exact.
- ``value_err``: over the sampled rows' entries, the largest
  |c - c64| / sum_k |a_ik b_kj|, the error as a share of the entry's
  magnitude sum, so cancellation cannot inflate it.
- ``checksum_err``: |u' C w - (u' A)(B w)| over
  sqrt((u*u)' (A.A)(B.B) (w*w)) for seeded vectors u and w: every entry of
  C enters it, so an entry altered or misplaced anywhere shows; the
  denominator is the 2-norm of all f_m weighted products.
"""
from __future__ import annotations

import numpy as np

HEAVY_ROWS = 4  # the heaviest rows of C (by products) are always sampled
TINY = np.finfo(np.float32).tiny


def row_products(a_ip, a_ix, b_ip) -> np.ndarray:
    """Products per row of A @ B (int64)."""
    per_slot = np.diff(b_ip).astype(np.int64)[a_ix]
    cs = np.concatenate([[0], np.cumsum(per_slot)])
    return cs[a_ip[1:]] - cs[a_ip[:-1]]


def sample_rows(a_ip, a_ix, b_ip, n_rows: int, rng) -> np.ndarray:
    """``n_rows`` rows drawn from ``rng`` plus the heaviest ones, sorted."""
    m = len(a_ip) - 1
    flops = row_products(a_ip, a_ix, b_ip)
    heavy = np.argsort(flops, kind="stable")[-HEAVY_ROWS:]
    rand = rng.choice(m, size=min(n_rows, m), replace=False)
    return np.unique(np.concatenate([heavy, rand]))


def _slots(indptr, rows) -> np.ndarray:
    lens = (indptr[rows + 1] - indptr[rows]).astype(np.int64)
    starts = np.repeat(indptr[rows].astype(np.int64) - np.concatenate(
        [[0], np.cumsum(lens)[:-1]]), lens)
    return starts + np.arange(lens.sum())


def product_rows(a, b, rows):
    """Reference rows of C = A @ B.

    ``a``, ``b``: (indptr, indices, values, (m, k)) on the host. Returns
    (row_sizes, cols, vals, mags) for ``rows`` in order: per row its column
    count, and per entry its column, float64 value and magnitude sum
    sum_k |a_ik b_kj|.
    """
    a_ip, a_ix, a_v, _ = a
    b_ip, b_ix, b_v, (_, k) = b
    sel = _slots(a_ip, rows)
    row_of = np.repeat(np.arange(len(rows)), a_ip[rows + 1] - a_ip[rows])
    j = a_ix[sel].astype(np.int64)
    cnt = (b_ip[j + 1] - b_ip[j]).astype(np.int64)
    offs = np.concatenate([[0], np.cumsum(cnt)])
    pos = np.repeat(b_ip[j].astype(np.int64) - offs[:-1], cnt) + np.arange(offs[-1])
    prow = np.repeat(row_of, cnt)
    col = b_ix[pos].astype(np.int64)
    term = np.repeat(a_v[sel].astype(np.float64), cnt) * b_v[pos].astype(np.float64)
    key = prow * k + col
    order = np.argsort(key, kind="stable")
    key, term = key[order], term[order]
    head = np.ones(len(key), bool)
    head[1:] = key[1:] != key[:-1]
    starts = np.flatnonzero(head)
    vals = np.add.reduceat(term, starts) if len(starts) else term[:0]
    mags = np.add.reduceat(np.abs(term), starts) if len(starts) else term[:0]
    ukey = key[starts]
    sizes = np.bincount(ukey // k, minlength=len(rows))
    return sizes, ukey % k, vals, mags


def checksum(a, b, u, w):
    """((u' A)(B w), sqrt((u*u)' (A.A)(B.B)(w*w))) in float64, O(nnz)."""
    a_ip, a_ix, a_v, (m, _) = a
    b_ip, b_ix, b_v, (kb, _) = b
    a_rows = np.repeat(np.arange(m), np.diff(a_ip))
    b_rows = np.repeat(np.arange(kb), np.diff(b_ip))
    a_v = a_v[: len(a_ix)].astype(np.float64)
    b_v = b_v[: len(b_ix)].astype(np.float64)
    bw = np.bincount(b_rows, b_v * w[b_ix], minlength=kb)
    bw2 = np.bincount(b_rows, (b_v * w[b_ix]) ** 2, minlength=kb)
    want = np.dot(u[a_rows] * a_v, bw[a_ix])
    scale = np.sqrt(np.dot((u[a_rows] * a_v) ** 2, bw2[a_ix]))
    return want, scale


def compare(a, b, c, rows, u, w, nnz_c: int) -> dict:
    """The numbers compared for one output ``c`` = (indptr, indices, values)
    of A @ B against the reference (see the module docstring)."""
    c_ip, c_ix, c_v = c
    c_ip = c_ip.astype(np.int64)
    out = {"nnz_c_diff": abs(int(c_ip[-1]) - nnz_c)}
    if c_ip[0] != 0 or (np.diff(c_ip) < 0).any() or c_ip[-1] > min(
            len(c_ix), len(c_v)):
        # row pointers that describe no CSR: nothing in C can be read
        return {**out, "rows_wrong": len(rows), "value_err": 1.0,
                "checksum_err": 1.0}
    sizes, cols, vals, mags = product_rows(a, b, rows)
    got_sizes = c_ip[rows + 1] - c_ip[rows]
    ref_off = np.concatenate([[0], np.cumsum(sizes)])
    got = _slots(c_ip, rows)
    got_cols = c_ix[got].astype(np.int64)
    got_vals = c_v[got].astype(np.float64)
    wrong, err = 0, 0.0
    pos = 0
    for r in range(len(rows)):
        n = int(got_sizes[r])
        ref = slice(ref_off[r], ref_off[r + 1])
        mine = slice(pos, pos + n)
        pos += n
        if n != sizes[r] or not np.array_equal(got_cols[mine], cols[ref]):
            wrong += 1
            continue
        if n:
            e = np.abs(got_vals[mine] - vals[ref]) / (mags[ref] + TINY)
            err = max(err, float(e.max()))
    out["rows_wrong"] = wrong
    out["value_err"] = err
    nnz = int(c_ip[-1])
    c_rows = np.repeat(np.arange(len(c_ip) - 1), np.diff(c_ip))
    got_sum = np.dot(u[c_rows] * c_v[:nnz].astype(np.float64),
                     w[c_ix[:nnz].clip(0, len(w) - 1)])
    want, scale = checksum(a, b, u, w)
    out["checksum_err"] = abs(got_sum - want) / max(scale, TINY)
    return out
