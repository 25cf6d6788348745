"""The benchmark's own copies of the structure generators reproduce the
program's generators at reduced sizes and the statistics that
``chip_smoke.py`` printed at full size (m, nnz, f_m and nnz(C)), and each
configuration's file states what its generator makes."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness, reference  # noqa: E402
from bench.generators import rmat, stencil2d  # noqa: E402
from bench.tests import tinytree  # noqa: E402

CONFIGS = harness.BENCH_DIR / "configs"
GRAPH500 = dict(a=0.57, b=0.19, c=0.19)


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def edge_keys(ip, ix, label=None):
    rows = np.repeat(np.arange(len(ip) - 1), np.diff(ip))
    cols = ix[: ip[-1]].astype(np.int64)
    if label is not None:
        rows, cols = label[rows], label[cols]
    return np.sort(rows * (len(ip) - 1) + cols)


def graph500_labels(scale, seed):
    """The vertex permutation, drawn from the structure seed after the
    edges, as the generator draws it."""
    rng = np.random.default_rng(seed)
    rmat.edges(scale, 16, rng=rng, **GRAPH500)
    return rng.permutation(1 << scale)


@pytest.mark.parametrize("scale,seed", [(6, 0), (9, 0), (10, 5)])
def test_rmat_copy_is_the_programs_draw(scale, seed):
    """The configuration's graph is the program's draw with every vertex
    label i replaced by label[i] (Graph500 relabels the vertices)."""
    from repro.sparse.generators import rmat_csr

    want = rmat_csr(scale, 16, seed=seed)
    ip, ix, shape = rmat.structure(scale, 16, structure_seed=seed, **GRAPH500)
    assert shape == want.shape
    assert np.array_equal(edge_keys(ip, ix), edge_keys(
        np.asarray(want.indptr), np.asarray(want.indices),
        graph500_labels(scale, seed)))


@pytest.mark.parametrize("scale,seed", [(6, 0), (9, 0), (10, 5)])
def test_rmat_permutes_the_vertex_labels(scale, seed):
    """The heavy rows no longer all sit at low indices; the row lengths
    are the same, in another order."""
    from repro.sparse.generators import rmat_csr

    want = np.diff(np.asarray(rmat_csr(scale, 16, seed=seed).indptr))
    got = np.diff(rmat.structure(scale, 16, structure_seed=seed,
                                 **GRAPH500)[0])
    assert (got != want).any()
    assert np.array_equal(np.sort(got), np.sort(want))
    assert np.argmax(got) == graph500_labels(scale, seed)[np.argmax(want)]


@pytest.mark.parametrize("nx,ny", [(1, 1), (2, 5), (33, 17), (64, 64)])
def test_stencil_copy_is_the_programs_operator(nx, ny):
    from repro.sparse.generators import stencil2d_csr

    want = stencil2d_csr(nx, ny)
    ip, ix, shape = stencil2d.structure(nx, ny)
    assert shape == want.shape
    assert np.array_equal(ip, np.asarray(want.indptr))
    assert np.array_equal(ix, np.asarray(want.indices))


# chip_smoke.py printed these for its deployments (rmat_csr(15, 16)
# and stencil2d_csr(2048, 2048), each squared)
SMOKE = {"rmat": ({"scale": 15, "edge_factor": 16, "structure_seed": 0, **GRAPH500},
                 {"m": 32768, "nnz": 467722, "f_m": 146324174, "nnz_c": 57597840}),
        "stencil2d": ({"nx": 2048, "ny": 2048},
                      {"m": 4194304, "nnz": 20963328, "f_m": 104783880,
                       "nnz_c": 54484996})}


def host_counts(generator, params):
    gen = harness.load_module(harness.BENCH_DIR, "generators", generator)
    ip, ix, shape = gen.structure(**params)
    return ip, ix, {"m": shape[0], "nnz": int(ip[-1]),
                    "f_m": int(reference.row_products(ip, ix, ip).sum())}


@pytest.mark.parametrize("generator", sorted(SMOKE))
def test_smoke_deployments_host_counts(generator):
    params, stats = SMOKE[generator]
    assert host_counts(generator, params)[2] == {
        k: stats[k] for k in ("m", "nnz", "f_m")}


@pytest.mark.parametrize("name", ["rmat_s14", "stencil2d_1024"])
def test_configurations_host_counts(name):
    """m, nnz and f_m of each configuration as run, against its file."""
    cfg = config(name)
    got = host_counts(cfg["generator"], cfg["params"])[2]
    assert got == {k: cfg["stats"][k] for k in got}


def rmat_nnz_c(ip, ix, n):
    """nnz(C) of A @ A counted on the host with one bit per column of C
    (C's row is the OR of B's rows)."""
    bits = np.zeros((n, n // 64), np.uint64)
    rows = np.repeat(np.arange(n), np.diff(ip))
    np.bitwise_or.at(bits, (rows, ix // 64),
                     np.left_shift(np.uint64(1), (ix % 64).astype(np.uint64)))
    total = 0
    for r0 in range(0, n, 1024):
        r1 = min(n, r0 + 1024)
        lens = np.diff(ip[r0:r1 + 1])
        starts = (ip[r0:r1] - ip[r0])[lens > 0]
        acc = np.bitwise_or.reduceat(bits[ix[ip[r0]:ip[r1]]], starts, axis=0)
        total += int(np.bitwise_count(acc).sum())
    return total


def test_rmat_nnz_c_at_full_size():
    """Scale 15 as the chip smoke printed it, and the configuration as run."""
    params, stats = SMOKE["rmat"]
    ip, ix, _ = rmat.structure(**params)
    assert rmat_nnz_c(ip, ix, 1 << params["scale"]) == stats["nnz_c"]
    cfg = config("rmat_s14")
    ip, ix, _ = rmat.structure(**cfg["params"])
    assert rmat_nnz_c(ip, ix, 1 << cfg["params"]["scale"]) == cfg["stats"]["nnz_c"]


def test_rmat_bit_count_matches_the_reference():
    ip, ix, shape = rmat.structure(9, 16, structure_seed=0, **GRAPH500)
    cfg = {"generator": "rmat", "params": {"scale": 9, "edge_factor": 16,
                                           "structure_seed": 0, **GRAPH500}}
    assert rmat_nnz_c(ip, ix, shape[0]) == tinytree.stats(cfg)["nnz_c"]


def stencil_nnz_c(nx, ny):
    """nnz of the 5-point stencil squared: every offset reachable in two
    steps (13 of them) whose target lies in the grid; each path stays in
    the grid's box, so none is cut."""
    offsets = [(dx, dy) for dx in range(-2, 3) for dy in range(-2, 3)
               if abs(dx) + abs(dy) <= 2]
    return sum(max(nx - abs(dx), 0) * max(ny - abs(dy), 0) for dx, dy in offsets)


@pytest.mark.parametrize("nx,ny", [(1, 1), (2, 3), (5, 4), (24, 20)])
def test_stencil_nnz_c_formula_matches_the_reference(nx, ny):
    cfg = {"generator": "stencil2d", "params": {"nx": nx, "ny": ny}}
    assert tinytree.stats(cfg)["nnz_c"] == stencil_nnz_c(nx, ny)


def test_stencil_nnz_c_at_full_size():
    assert stencil_nnz_c(**SMOKE["stencil2d"][0]) == SMOKE["stencil2d"][1]["nnz_c"]
    cfg = config("stencil2d_1024")
    assert stencil_nnz_c(**cfg["params"]) == cfg["stats"]["nnz_c"]


def test_harness_refuses_a_generator_that_misses_its_statistics(tmp_path):
    bench = tinytree.make(tmp_path)
    path = bench / "configs" / "rmat_s14.json"
    cfg = json.loads(path.read_text())
    cfg["stats"]["f_m"] += 1
    path.write_text(json.dumps(cfg))
    with pytest.raises(harness.BenchError, match="f_m"):
        harness.Structure(harness.load_cell(bench, "rmat_s14.oneshot"),
                          harness.Clock())
