"""A copy of the benchmark's tree with its configurations cut to a size the
CPU tests can run: the same generators, drivers, readers and limits, and
each configuration's stated statistics recomputed by the reference."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import numpy as np

from bench import harness, reference

BENCH = Path(harness.__file__).resolve().parent
TINY = {"rmat_s14": {"scale": 8}, "stencil2d_1024": {"nx": 24, "ny": 20}}


def stats(config: dict, bench_dir: Path = BENCH) -> dict:
    """m, nnz, f_m and nnz(C) of a configuration's A @ A, by the reference."""
    gen = harness.load_module(bench_dir, "generators", config["generator"])
    ip, ix, shape = gen.structure(**config["params"])
    a = (ip, ix, np.ones(len(ix)), shape)
    sizes = reference.product_rows(a, a, np.arange(shape[0]))[0]
    return {"m": shape[0], "nnz": int(ip[-1]),
            "f_m": int(reference.row_products(ip, ix, ip).sum()),
            "nnz_c": int(sizes.sum())}


def make(root: Path, sizes: dict = TINY) -> Path:
    """``root``/BENCHMARK.json and ``root``/bench with the configurations'
    parameters replaced by ``sizes``; returns ``root / "bench"``."""
    root = Path(root)
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".traces", "__pycache__",
                                                  "tests"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root)
    for name, params in sizes.items():
        path = root / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg["params"].update(params)
        cfg["stats"] = stats(cfg)
        path.write_text(json.dumps(cfg))
    return root / "bench"
