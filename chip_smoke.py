"""Smoke run of the SpGEMM engine on a TPU, through its normal entry points.

    python chip_smoke.py [--seed N]        # one chip: every phase below
    python chip_smoke.py --chips 4         # four chips: the sharded replay

Both first compile the deployments' expansions concurrently into the
persistent compilation cache (the TPU compiler spends minutes on each sort).
One chip, per deployment (AMG-style 5-point stencil on a 2048x2048 grid,
squared; Graph500-parameter RMAT, scale 15, edge factor 16, squared):
a one-shot ``spgemm()``, a ``ReuseExecutor.from_matrices`` pin, 3 replays
with fresh values and one batch-4 ``apply_batched``. Then a served phase
(``SparseService`` with its defaults answers 8 requests over 2 structures)
and a kernel phase (each Pallas kernel at a size it compiles for, compared
with XLA). Every product is checked against ``sparse.oracle.gustavson_numpy``
on a seeded sample of rows that includes the heaviest rows: row structure
exactly, values within f32 rounding of a float64 product.

Four chips: ``ShardedReuseExecutor`` on a 4-device data mesh for both
deployments and both B placements; replays and batched replays must equal
the single-device ``ReuseExecutor`` bitwise, every plan array must span the
four devices, and every device must hold bytes.

A phase fails the run on any oracle mismatch, any degradation-ladder
fallback, any opened circuit breaker or any fallback kernel source. Each
phase prints one line with its sizes, smoke timings (compile and wall
seconds of this one run, not benchmark numbers), the device's peak bytes and
the counters. The last line is ``{"ok": true, "device": {...}}``. With no
TPU the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
ROW_SAMPLE, HEAVY_ROWS = 28, 4  # oracle rows per check: random + heaviest
VALUE_RTOL = 1e-4  # |C - C64| <= VALUE_RTOL * (|A| @ |B|) per entry
N_REPLAYS, BATCH = 3, 4


class SmokeFailure(RuntimeError):
    """A phase produced a wrong answer or took a degraded path."""


class Smoke:
    """Run state the phases share: seed, interpret flag (tests only; the
    chip path never interprets), compile seconds seen so far, and the
    device whose memory statistics the phase lines report."""

    def __init__(self, seed: int, interpret: bool = False, device=None):
        import jax

        self.seed = seed
        self.interpret = interpret
        self.device = jax.devices()[0] if device is None else device
        self.compile_s = 0.0
        self.lines: list[dict] = []

    def on_event(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compile_s += secs

    def rng(self, salt: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, salt])

    def phase(self, name: str, fn, *args, **kwargs) -> dict:
        """Run one phase, fail it on any degraded path, print its line."""
        from repro.core import telemetry

        telemetry.reset_all()
        telemetry.reset_fallback_counts()
        telemetry.reset_breaker_counts()
        c0, t0 = self.compile_s, time.perf_counter()
        info = fn(self, *args, **kwargs)
        wall = time.perf_counter() - t0
        if telemetry.FALLBACK_COUNTS:
            raise SmokeFailure(f"{name}: fallbacks {dict(telemetry.FALLBACK_COUNTS)}")
        opened = [k for k in telemetry.BREAKER_COUNTS if k.endswith("open")]
        if opened:
            raise SmokeFailure(f"{name}: breakers opened {opened}")
        if "fallback" in info.pop("kernel_sources", ()):
            raise SmokeFailure(f"{name}: a kernel source is 'fallback'")
        stats = self.device.memory_stats() or {}
        line = {"phase": name, **info,
                "smoke_compile_s": self.compile_s - c0, "smoke_wall_s": wall,
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "counters": {k: v for k, v in telemetry.snapshot().items() if v}}
        self.lines.append(line)
        print(json.dumps(line), flush=True)
        return line


# --------------------------------------------------------------------------
# host-side oracle checks
# --------------------------------------------------------------------------


def host_csr(m):
    """(indptr, indices, values, shape) of a CSR's live entries, on the
    host."""
    ip = np.asarray(m.indptr)
    nnz = int(ip[-1])
    return ip, np.asarray(m.indices)[:nnz], np.asarray(m.values)[:nnz], m.shape


def sample_rows(a_ip, a_ix, b_ip, rng) -> np.ndarray:
    """A seeded sample of C's rows that always holds the heaviest ones (by
    multiplications)."""
    per_slot = np.diff(b_ip)[a_ix].astype(np.int64)
    cs = np.concatenate([[0], np.cumsum(per_slot)])
    flops = cs[a_ip[1:]] - cs[a_ip[:-1]]
    m = len(a_ip) - 1
    heavy = np.argsort(flops, kind="stable")[-HEAVY_ROWS:]
    rand = rng.choice(m, size=min(ROW_SAMPLE, m), replace=False)
    return np.unique(np.concatenate([heavy, rand]))


def _slots(indptr: np.ndarray, rows: np.ndarray) -> np.ndarray:
    return np.concatenate([np.arange(indptr[r], indptr[r + 1]) for r in rows])


def check_rows(label, a_host, b_host, a_vals, b_vals, c_indptr, c_indices,
               c_values, rows) -> None:
    """C's ``rows`` against Gustavson's algorithm (``gustavson_numpy``) on
    the same operands in float64: columns exactly, values within
    ``VALUE_RTOL`` of the per-entry magnitude sum |A| @ |B|."""
    import jax.numpy as jnp

    from repro.sparse.formats import CSR
    from repro.sparse.oracle import gustavson_numpy

    a_ip, a_ix, _, _ = a_host
    b_ip, b_ix, _, b_shape = b_host
    a_vals = np.asarray(a_vals)[: len(a_ix)].astype(np.float64)
    b_vals = np.asarray(b_vals)[: len(b_ix)].astype(np.float64)
    sel = _slots(a_ip, rows)
    sub_ip = np.concatenate([[0], np.cumsum(np.diff(a_ip)[rows])])
    shape_a = (len(rows), b_shape[0])
    want = gustavson_numpy(CSR(sub_ip, a_ix[sel], a_vals[sel], shape_a),
                           CSR(b_ip, b_ix, b_vals, b_shape))
    mag = gustavson_numpy(CSR(sub_ip, a_ix[sel], np.abs(a_vals[sel]), shape_a),
                          CSR(b_ip, b_ix, np.abs(b_vals), b_shape))
    c_sel = jnp.asarray(_slots(c_indptr, rows))
    got_ix = np.asarray(c_indices[c_sel])
    got_val = np.asarray(c_values[c_sel]).astype(np.float64)
    if not np.array_equal(np.diff(c_indptr)[rows], np.diff(want[0])):
        raise SmokeFailure(f"{label}: row sizes differ from the oracle")
    if not np.array_equal(got_ix, want[1]):
        raise SmokeFailure(f"{label}: column structure differs from the oracle")
    err = np.abs(got_val - want[2])
    bound = VALUE_RTOL * mag[2] + np.finfo(np.float32).tiny
    if not np.all(err <= bound):
        worst = int(np.argmax(err / bound))
        raise SmokeFailure(
            f"{label}: value {got_val[worst]} vs oracle {want[2][worst]} "
            f"(bound {bound[worst]})")


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------


def deployment_phase(smoke: Smoke, name: str, a, b) -> dict:
    """One-shot multiply, pinned executor, fresh-value replays and a batched
    replay of ``a @ b``, each checked against the oracle."""
    import jax
    import jax.numpy as jnp

    from repro.core.executor import ReuseExecutor
    from repro.core.spgemm import spgemm

    a_host, b_host = host_csr(a), host_csr(b)
    rows = sample_rows(a_host[0], a_host[1], b_host[0], smoke.rng(1))
    res = spgemm(a, b)
    jax.block_until_ready(res.c.values)
    c_ip = np.asarray(res.c.indptr)
    check_rows(f"{name} one-shot", a_host, b_host, a_host[2], b_host[2],
               c_ip, res.c.indices, res.c.values, rows)
    ex = ReuseExecutor.from_matrices(a, b, interpret=smoke.interpret)
    p_ip = np.asarray(ex.plan.indptr)
    rng = smoke.rng(2)

    def fresh(n, *lead):
        return rng.standard_normal((*lead, n)).astype(np.float32)

    for i in range(N_REPLAYS):
        av, bv = fresh(a.nnz_cap), fresh(b.nnz_cap)
        out = ex.apply(jnp.asarray(av), jnp.asarray(bv))
        check_rows(f"{name} replay {i}", a_host, b_host, av, bv, p_ip,
                   ex.plan.indices, out, rows)
    av, bv = fresh(a.nnz_cap, BATCH), fresh(b.nnz_cap, BATCH)
    outs = ex.apply_batched(jnp.asarray(av), jnp.asarray(bv))
    for j in range(BATCH):
        check_rows(f"{name} batched[{j}]", a_host, b_host, av[j], bv[j], p_ip,
                   ex.plan.indices, outs[j], rows)
    st = res.stats
    return {"deployment": name, "m": a.m, "nnz": len(a_host[1]),
            "f_m": st["fm"], "fm_cap": st["fm_cap"], "nnz_c": st["nnz_c"],
            "nnz_cap": st["nnz_cap"], "method": st["method"],
            "rows_checked": len(rows),
            "kernel_sources": (st.get("kernel_source"), ex.kernel_source)}


def served_phase(smoke: Smoke, structures) -> dict:
    """``SparseService`` with its defaults answers 4 fresh-value requests
    for each structure; every answer is checked against the oracle."""
    import jax.numpy as jnp

    from repro.serve import SparseService
    from repro.sparse.formats import CSR

    svc = SparseService(interpret=smoke.interpret)
    rng = smoke.rng(3)
    asked = []
    for i in range(8):
        a, b = structures[i % len(structures)]
        a = CSR(a.indptr, a.indices,
                jnp.asarray(rng.standard_normal(a.nnz_cap), jnp.float32),
                a.shape)
        b = CSR(b.indptr, b.indices,
                jnp.asarray(rng.standard_normal(b.nnz_cap), jnp.float32),
                b.shape)
        asked.append((a, b, svc.submit(a, b)))
    svc.drain()
    for i, (a, b, resp) in enumerate(asked):
        if not resp.ok:
            raise SmokeFailure(f"served request {i}: {resp.error!r}")
        a_host, b_host = host_csr(a), host_csr(b)
        rows = sample_rows(a_host[0], a_host[1], b_host[0], smoke.rng(4 + i))
        c = resp.value
        check_rows(f"served request {i}", a_host, b_host, a_host[2],
                   b_host[2], np.asarray(c.indptr), c.indices, c.values, rows)
    st = svc.stats()
    if st["breakers"] or st["degraded_dispatches"] or st["failed"]:
        raise SmokeFailure(f"served phase degraded: {st}")
    return {"requests": 8, "structures": len(structures),
            "completed": st["completed"],
            "group_dispatches": st["group_dispatches"],
            "m": [a.m for a, _ in structures],
            "nnz": [int(a.indptr[-1]) for a, _ in structures]}


def kernel_phase(smoke: Smoke, name: str, a, b) -> dict:
    """Every Pallas SpGEMM kernel on ``a @ b`` (a size each compiles for),
    on_kernel_failure="raise", compared with XLA."""
    import jax.numpy as jnp

    from repro.core.executor import ReuseExecutor
    from repro.kernels.ops import numeric_values, symbolic_rowsizes
    from repro.sparse.formats import csr_to_ell

    ex_xla = ReuseExecutor.from_matrices(a, b, interpret=smoke.interpret)
    plan = ex_xla.plan
    sizes = np.diff(np.asarray(plan.indptr))
    if not np.array_equal(np.asarray(symbolic_rowsizes(a, b)), sizes):
        raise SmokeFailure(f"{name}: symbolic kernel row sizes differ")
    c_ell = csr_to_ell(ex_xla.to_csr(jnp.zeros(plan.indices.shape, a.dtype)))
    ref = np.asarray(numeric_values(a, b, c_ell.indices, c_ell.row_nnz,
                                    kernel="xla", on_kernel_failure="raise"))
    scale = float(np.abs(ref).max(initial=1.0))
    for kname in ("dense_acc", "flat_lp"):
        got = numeric_values(a, b, c_ell.indices, c_ell.row_nnz, kernel=kname,
                             on_kernel_failure="raise")
        np.testing.assert_allclose(np.asarray(got), ref, rtol=VALUE_RTOL,
                                   atol=VALUE_RTOL * scale, err_msg=kname)
    want = np.asarray(ex_xla.apply(a.values, b.values))
    sources = []
    for backend in ("pallas", "pallas_lp"):
        ex = ReuseExecutor(plan, backend=backend, interpret=smoke.interpret,
                           on_kernel_failure="raise")
        got = ex.apply(a.values, b.values)
        np.testing.assert_allclose(np.asarray(got), want, rtol=VALUE_RTOL,
                                   atol=VALUE_RTOL * scale, err_msg=backend)
        sources.append(ex.kernel_source)
    return {"problem": name, "m": a.m, "nnz": int(a.indptr[-1]),
            "r_a": int(np.diff(np.asarray(a.indptr)).max()),
            "r_c": int(c_ell.indices.shape[1]), "fm_cap": ex_xla.fm_cap,
            "kernels": ["symbolic", "dense_acc", "flat_lp", "pallas",
                        "pallas_lp"],
            "kernel_sources": sources}


def warm_expansions(problems, mesh=None) -> int:
    """Compile the expansion of every ``(a, b)`` in ``problems`` at once, in
    threads, into the persistent compilation cache: single-device
    (``expand_and_sort``) and, given a ``mesh``, also sharded under both B
    placements (``dist.plan.expand_on_mesh``).

    The TPU compiler takes minutes for the expansion's sort and works on one
    core per program; the phases then load these programs from the cache
    instead of compiling them one after another. Each program is lowered on
    the operands the phase will hand it (capacity-bucketed as ``spgemm``
    and ``ShardedReuseExecutor`` bucket them), so it is the same program.
    Returns the number of programs compiled.
    """
    from concurrent.futures import ThreadPoolExecutor

    from repro.core.distributed import partition_rows, shard_fm_cap
    from repro.core.meta import DEFAULT_PAD_POLICY as POLICY
    from repro.core.spgemm import expand_and_sort, prepare_sparse_inputs
    from repro.dist.plan import expand_on_mesh, mesh_expand_args

    lowered = {}
    for a, b in problems:
        a, b, _, _, fm_cap = prepare_sparse_inputs(a, b, POLICY)
        key = (a.shape, a.nnz_cap, b.shape, b.nnz_cap, fm_cap)
        lowered[key] = expand_and_sort.lower(a, b, fm_cap=fm_cap)
        if mesh is None:
            continue
        num = mesh.devices.size
        a_sh = partition_rows(a, num, POLICY)
        fm_cap = shard_fm_cap(a_sh, b, POLICY)
        for b_in in (b, partition_rows(b, num, POLICY)):
            arrays, static = mesh_expand_args(a_sh, b_in, mesh,
                                              mesh.axis_names[0], fm_cap)
            key = tuple(x.shape for x in arrays) + (fm_cap,)
            lowered[key] = expand_on_mesh.lower(*arrays, **static)
    with ThreadPoolExecutor(len(lowered)) as pool:
        for f in [pool.submit(lo.compile) for lo in lowered.values()]:
            f.result()
    return len(lowered)


def free_device_state() -> None:
    """Drop the plan caches and collect, so the next deployment starts with
    the device memory of the last one released."""
    from repro.core.plan_cache import default_plan_cache
    from repro.dist.plan_cache import default_dist_plan_cache

    default_plan_cache().clear()
    default_dist_plan_cache().clear()
    gc.collect()


def deployments(seed: int) -> list:
    """(name, A, B) of the two deployments, made from ``seed``."""
    from repro.sparse.generators import rmat_csr, stencil2d_csr

    def stencil():
        a = stencil2d_csr(2048, 2048)
        return a, a

    def rmat():
        a = rmat_csr(15, 16, seed=seed)  # Graph500 a/b/c, edge factor 16
        return a, a

    return [("stencil2d_2048x2048", stencil), ("rmat_s15_ef16", rmat)]


def run_one_chip(smoke: Smoke) -> None:
    from repro.sparse.generators import rmat_csr

    made = [(name, *make()) for name, make in deployments(smoke.seed)]
    served = [(rmat_csr(12, 16, seed=smoke.seed + s),) * 2 for s in (1, 2)]
    kern = rmat_csr(9, 16, seed=smoke.seed)
    smoke.phase("warm", lambda _: {"programs": warm_expansions(
        [(a, b) for _, a, b in made] + served + [(kern, kern)])})
    while made:
        name, a, b = made.pop(0)
        smoke.phase(f"deployment:{name}", deployment_phase, name, a, b)
        del a, b
        free_device_state()
    smoke.phase("served", served_phase, served)
    free_device_state()
    smoke.phase("kernels:rmat_s9_ef16", kernel_phase, "rmat_s9_ef16", kern, kern)


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------


def sharded_phase(smoke: Smoke, name: str, a, b, mesh) -> dict:
    """Sharded replays of ``a @ b`` under both B placements, bitwise equal
    to the single-device executor's (computed first and kept on the host,
    so the two plans never share device memory)."""
    import jax
    import jax.numpy as jnp

    from repro.core.executor import ReuseExecutor
    from repro.dist import ShardedReuseExecutor

    rng = smoke.rng(5)
    av = rng.standard_normal(a.nnz_cap).astype(np.float32)
    bv = rng.standard_normal(b.nnz_cap).astype(np.float32)
    avb = rng.standard_normal((BATCH, a.nnz_cap)).astype(np.float32)
    bvb = rng.standard_normal((BATCH, b.nnz_cap)).astype(np.float32)
    ex = ReuseExecutor.from_matrices(a, b)
    nnz = int(np.asarray(ex.plan.indptr)[-1])
    want = np.asarray(ex.apply(jnp.asarray(av), jnp.asarray(bv)))[:nnz]
    want_b = np.asarray(
        ex.apply_batched(jnp.asarray(avb), jnp.asarray(bvb)))[:, :nnz]
    del ex
    free_device_state()
    info = {"deployment": name, "nnz_c": nnz, "placements": {}}
    for placement in ("replicated", "allgather"):
        sx = ShardedReuseExecutor.from_matrices(a, b, mesh,
                                                b_placement=placement)
        for field, arr in zip(sx.plan._fields, sx.plan):
            if hasattr(arr, "sharding") and len(arr.sharding.device_set) != 4:
                raise SmokeFailure(f"{name}/{placement}: plan.{field} spans "
                                   f"{len(arr.sharding.device_set)} devices")
        got = np.asarray(sx.merge_values(
            sx.apply(jnp.asarray(av), jnp.asarray(bv))))
        got_b = sx.apply_batched(jnp.asarray(avb), jnp.asarray(bvb))
        got_b = np.stack([np.asarray(sx.merge_values(got_b[j]))
                          for j in range(BATCH)])
        if not (np.array_equal(got, want) and np.array_equal(got_b, want_b)):
            raise SmokeFailure(f"{name}/{placement}: sharded replay is not "
                               f"bitwise the single-device replay")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in mesh.devices.flat]
        if smoke.device.platform == "tpu" and not all(in_use):
            raise SmokeFailure(f"{name}/{placement}: bytes in use {in_use}")
        info["placements"][placement] = {
            "fm_cap_per_shard": sx.plan.fm_cap,
            "nnz_cap_per_shard": sx.nnz_cap, "bytes_in_use": in_use}
        del sx, got_b
        free_device_state()
    return info


def run_four_chips(smoke: Smoke) -> None:
    from repro.launch.mesh import make_data_mesh

    mesh = make_data_mesh(4)
    made = [(name, *make()) for name, make in deployments(smoke.seed)]
    pairs = [(a, b) for _, a, b in made]
    smoke.phase("warm", lambda _: {"programs": warm_expansions(pairs, mesh)})
    del pairs
    while made:
        name, a, b = made.pop(0)
        smoke.phase(f"sharded:{name}", sharded_phase, name, a, b, mesh)
        del a, b
        free_device_state()


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax

    from repro.compile_cache import place_compilation_cache

    place_compilation_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform}); "
              f"this smoke runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    smoke = Smoke(args.seed)
    jax.monitoring.register_event_duration_secs_listener(smoke.on_event)
    if args.chips == 4:
        run_four_chips(smoke)
    else:
        run_one_chip(smoke)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
