"""The benchmark harness: one cell, one seed, one process.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

- ``configs/<config>.json``: the generator (``generators/<generator>.py``)
  and its parameters, the structure statistics it must reproduce, and the
  limits of the numbers compared;
- ``traffic/<mix>.json``: its parameters and the driver that reads them
  (``drivers/<driver>.py``);
- ``metrics/<metric>.json``: the reader (``readers/<reader>.py``) that takes
  the metric from the trace or the spans, with its parameters; its layer,
  unit, source and what it moves are in ``BENCHMARK.json`` alone.

A run builds the cell (set-up: structure, values from the seed on the
device, the driver's own set-up, warm-up calls), runs whole timed calls for
``seconds``, reads the device's peak memory, frees the program's state and
compares what the window produced with the plain float64 reference
(``reference.py``).
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import shutil
import sys
import time
from collections import deque
from dataclasses import dataclass
from functools import cache
from pathlib import Path

import numpy as np

from bench import reference, tracing

BENCH_DIR = Path(__file__).resolve().parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CALL_SPAN = tracing.CALL_SPAN
WINDOW_SPAN = tracing.WINDOW_SPAN
KEEP = 2  # window outputs kept (a seeded reservoir sample) for the check
SAMPLE_ROWS = 252  # random rows compared per kept output, plus the heaviest
CONTROLS = ("bf16",)  # the program fed values one precision below float32
METRIC_FILE_KEYS = {"reader", "params", "reads"}


class BenchError(RuntimeError):
    """The benchmark's own files or the machine do not fit the request."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(bench_dir: Path, kind: str, name: str):
    """``<bench_dir>/<kind>/<name>.py`` as a module."""
    path = bench_dir / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind[:-1]} named {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list  # BENCHMARK.json entries this cell reports
    per_layer: list  # (BENCHMARK.json entry, metric file) this cell reports
    bench_dir: Path


def load_cell(bench_dir: Path, workload: str) -> Cell:
    """Find a workload's configuration, traffic mix and metrics by name."""
    bench_dir = Path(bench_dir)
    spec = load_json(bench_dir.parent / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(bench_dir.parent / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = []
    for m in spec["per_layer"]:
        if workload in m.get("workloads", [workload] if m["moves"] in reported
                             else []):
            mfile = load_json(bench_dir / "metrics" / f"{m['name']}.json")
            extra = set(mfile) - METRIC_FILE_KEYS
            if extra:  # layer, unit, source, moves: BENCHMARK.json alone
                raise BenchError(f"metrics/{m['name']}.json: {sorted(extra)} "
                                 f"belong in BENCHMARK.json, not here")
            per_layer.append((m, mfile))
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                bench_dir=bench_dir)


def peak_table() -> dict:
    """Peaks by ``device_kind`` (``peaks.json``, with their source)."""
    return load_json(BENCH_DIR / "peaks.json")


def place_cache(root: Path) -> None:
    """JAX's persistent compilation cache in ``<root>/.jax_cache``: inside
    the checkout, at a fixed path, every program cached so that only a
    checkout's first run compiles. The program keeps a directory already
    set (``repro.compile_cache``)."""
    import jax

    from repro.compile_cache import place_compilation_cache

    jax.config.update("jax_compilation_cache_dir", str(Path(root) / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    place_compilation_cache()


class Clock:
    """Named set-up phases on the host clock, and the compiles JAX reports
    (``backend_compile_duration`` events) as they happen."""

    def __init__(self):
        self.phases: dict = {}
        self.compiles = 0
        self.compile_s = 0.0

    def on_event(self, event: str, secs: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.compiles += 1
            self.compile_s += secs

    def phase(self, name: str, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        secs = time.perf_counter() - t0
        self.phases[name] = self.phases.get(name, 0.0) + secs
        print(f"set-up {name} {secs:.3f} (compiles so far {self.compiles}, "
              f"{self.compile_s:.1f} s)", file=sys.stderr, flush=True)
        return out


def seed_key(seed: int):
    """A JAX key from any non-negative seed (more than 32 bits allowed)."""
    import jax

    lo, hi = (int(x) for x in np.random.SeedSequence(seed).generate_state(2))
    return jax.random.fold_in(jax.random.key(lo & 0x7FFFFFFF), hi)


@cache
def _draw(n: int, count: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def draw(key):
        return tuple(jax.random.normal(k, (n,), jnp.float32)
                     for k in jax.random.split(key, count))

    return draw


def value_pool(seed: int, n: int, count: int) -> list:
    """``count`` float32 value sets of length ``n``, standard normal, made
    on the device from the seed in one jitted call."""
    import jax

    pool = list(_draw(n, count)(seed_key(seed)))
    jax.block_until_ready(pool)
    return pool


def program_values(pool: list, control: str | None) -> list:
    """What the program is fed: the pool, or under the bf16 control the
    pool rounded to bfloat16 (the program then computes in bfloat16)."""
    if control is None:
        return pool
    if control not in CONTROLS:
        raise BenchError(f"unknown control {control!r}; known: {CONTROLS}")
    import jax
    import jax.numpy as jnp

    out = [v.astype(jnp.bfloat16) for v in pool]
    jax.block_until_ready(out)
    return out


class Structure:
    """A configuration's structure: on the host for the reference, on the
    device for the program."""

    def __init__(self, cell: Cell, clock: Clock):
        cfg = cell.config
        gen = load_module(cell.bench_dir, "generators", cfg["generator"])
        self.indptr, self.indices, self.shape = clock.phase(
            "generate_s", lambda: gen.structure(**cfg["params"]))
        self.nnz = int(self.indptr[-1])
        self.stats = cfg["stats"]
        f_m = int(reference.row_products(self.indptr, self.indices,
                                         self.indptr).sum())
        found = {"m": self.shape[0], "nnz": self.nnz, "f_m": f_m}
        wrong = {k: (v, self.stats[k]) for k, v in found.items()
                 if v != self.stats[k]}
        if wrong:
            raise BenchError(f"generator does not reproduce the stated "
                             f"statistics (found, stated): {wrong}")
        self.device = clock.phase("device_put_s", self._put)

    def _put(self):
        import jax
        import jax.numpy as jnp

        out = (jnp.asarray(self.indptr), jnp.asarray(self.indices))
        jax.block_until_ready(out)
        return out


def prepare(cell: Cell, structure: Structure, seed: int,
            control: str | None, clock: Clock, warm: bool = True):
    """The seed's value pool, and the traffic's driver set up on it (fed
    the pool, or what the control feeds) and, unless ``warm`` is false,
    warmed up."""
    import jax

    pool = clock.phase("values_s", value_pool, seed, structure.nnz,
                       int(cell.traffic["pool"]))
    fed = clock.phase("values_s", program_values, pool, control)
    drv = load_module(cell.bench_dir, "drivers", cell.traffic["driver"])
    indptr, indices = structure.device
    driver = clock.phase("driver_setup_s", drv.Driver, indptr, indices,
                         structure.shape, fed, cell.traffic)
    for i in range(int(cell.traffic["warm_calls"]) if warm else 0):
        clock.phase("warm_calls_s", lambda i: jax.block_until_ready(
            driver.call(i)), i)
    return pool, driver


def host_outputs(driver, kept: list, pool: list) -> list:
    """The kept outputs on the host, each with the float32 values its call
    was made from: [(values, (indptr, indices, values of C))]."""
    return [(np.asarray(pool[k]), driver.to_host(out)) for _, (k, out) in kept]


def run_window(driver, first: int, seconds: float, seed: int,
               annotate=None, ahead: int = 0) -> dict:
    """Whole timed calls until ``seconds`` have passed. Up to ``ahead``
    calls wait on the device behind the one the host waits for, so that a
    host that stands still does not leave the device idle. When the time
    is up nothing more is sent, every call sent is waited for, and the
    clock is read after that wait: all of that work over all of that time.

    Keeps a reservoir sample (drawn from the seed) of ``KEEP`` calls'
    outputs for the check, and the milliseconds between successive calls'
    completions (``call_ms``), so that a slow run shows whether one call or
    all were slow; ``dispatch_ms_max`` and ``gc_ms`` say whether the host
    stood still while sending or in Python's collector."""
    import jax

    rng = np.random.default_rng([seed, 3])
    kept: list = []
    in_flight: deque = deque()
    failed, errors, call_ms, dispatch_ms = 0, [], [], []
    gc_ms = [0.0, None]

    def on_gc(phase, _info):
        if phase == "start":
            gc_ms[1] = time.perf_counter()
        elif gc_ms[1] is not None:
            gc_ms[0] += (time.perf_counter() - gc_ms[1]) * 1e3

    def fail(e):
        nonlocal failed
        failed += 1
        errors.append(repr(e)[:500])

    def wait_oldest():
        nonlocal kept, t_done
        m, done = in_flight.popleft()
        try:
            jax.block_until_ready(done)
        except Exception as e:  # an error the device reported
            fail(e)
            kept = [x for x in kept if x[0] != m]
        t = time.perf_counter()
        call_ms.append((t - t_done) * 1e3)
        t_done = t

    span = annotate or (lambda _name: contextlib.nullcontext())
    n = 0
    gc.callbacks.append(on_gc)
    try:
        with span(WINDOW_SPAN):
            t0 = t_done = time.perf_counter()
            while True:
                t_call = time.perf_counter()
                try:
                    with span(CALL_SPAN):
                        item = driver.call(first + n)
                except Exception as e:  # counted against the attempts
                    fail(e)
                    item = None
                dispatch_ms.append((time.perf_counter() - t_call) * 1e3)
                n += 1
                if item is not None:
                    in_flight.append((n, item))
                    if len(kept) < KEEP:
                        kept.append((n, item))
                    else:
                        j = int(rng.integers(n))
                        if j < KEEP:
                            kept[j] = (n, item)
                # dropped once waited for: at most KEEP outputs outlive
                # their wait, whichever the seed keeps, so the peak does
                # not hang on the seed
                item = None
                while len(in_flight) > ahead:
                    wait_oldest()
                if time.perf_counter() - t0 >= seconds:
                    break
            while in_flight:
                wait_oldest()
            elapsed = time.perf_counter() - t0
    finally:
        gc.callbacks.remove(on_gc)
    return {"elapsed_s": elapsed, "attempted": n, "failed": failed,
            "errors": errors[:3], "kept": kept, "call_ms": call_ms,
            "dispatch_ms_max": max(dispatch_ms), "gc_ms": gc_ms[0]}


def ahead_calls(cell: Cell) -> int:
    """Calls the host keeps queued on the device behind the one it waits
    for: the traffic's ``ahead_products`` in whole calls of this
    configuration (``f_m`` products each), fixed by the files alone so that
    the memory they hold is the same in every run."""
    return int(cell.traffic.get("ahead_products", 0)) // int(cell.config["stats"]["f_m"])


def peak_bytes(devices) -> int | None:
    """``peak_bytes_in_use`` of the fullest device (None where the backend
    keeps no statistics)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def check(structure: Structure, outputs: list, limits: dict, seed: int) -> dict:
    """Compare each kept output with the float64 reference. ``outputs``:
    [(float32 values the call was given, (indptr, indices, values) of C)].
    Returns {name: (worst value over the outputs, limit)}."""
    ip, ix = structure.indptr, structure.indices
    rows = reference.sample_rows(ip, ix, ip, SAMPLE_ROWS,
                                 np.random.default_rng([seed, 1]))
    rng = np.random.default_rng([seed, 2])
    u = rng.standard_normal(structure.shape[0])
    w = rng.standard_normal(structure.shape[1])
    worst: dict = {}
    for vals, c in outputs:
        a = (ip, ix, vals, structure.shape)
        got = reference.compare(a, a, c, rows, u, w, structure.stats["nnz_c"])
        for k, v in got.items():
            worst[k] = max(worst.get(k, 0), v)
    return {k: (worst[k], limits[k]) for k in limits if k in worst}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool = False,
             control: str | None = None, t_start: float | None = None,
             peak: dict | None = None):
    """One run of a cell. Returns (earlier lines, result) where result has
    the contract's keys (``correct``, ``attempted``, ``failed``,
    ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last
    ``compared``)."""
    import jax

    from repro.core import telemetry
    from repro.obs import trace as obs_trace

    t_start = time.perf_counter() if t_start is None else t_start
    clock = Clock()
    jax.monitoring.register_event_duration_secs_listener(clock.on_event)
    obs_trace.set_tracing("off")
    devices = jax.devices()[:cell.chips]

    structure = Structure(cell, clock)
    pool, driver = prepare(cell, structure, seed, control, clock)
    setup_s = time.perf_counter() - t_start
    setup = {"setup_s": setup_s, "phases": clock.phases,
             "compiles": clock.compiles, "compile_s": clock.compile_s}

    telemetry.reset_fallback_counts()
    c0, cs0 = clock.compiles, clock.compile_s
    warm = int(cell.traffic["warm_calls"])
    annotate = None
    if trace:
        trace_dir = cell.bench_dir / ".traces" / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
        obs_trace.set_tracing("xprof")
        annotate = jax.profiler.TraceAnnotation
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # host spans only, no Python calls
        jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    try:
        win = run_window(driver, warm, seconds, seed, annotate,
                         ahead_calls(cell))
    finally:
        if trace:
            jax.profiler.stop_trace()
            obs_trace.set_tracing("off")
    window = {"calls": win["attempted"], "failed": win["failed"],
              "elapsed_s": win["elapsed_s"],
              "compiles": clock.compiles - c0,
              "compile_s": clock.compile_s - cs0,
              "fallbacks": dict(telemetry.FALLBACK_COUNTS),
              "errors": win["errors"],
              "kept_calls": [n for n, _ in win["kept"]],
              "ahead": ahead_calls(cell),
              "dispatch_ms_max": win["dispatch_ms_max"],
              "gc_ms": win["gc_ms"], "call_ms": win["call_ms"]}
    mem = peak_bytes(devices)

    outputs = host_outputs(driver, win["kept"], pool)
    del driver, pool, win
    gc.collect()
    compared = check(structure, outputs, cell.config["limits"], seed)
    correct = (window["failed"] == 0 and len(outputs) > 0
               and all(v <= lim for v, lim in compared.values()))

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    result: dict = {"correct": bool(correct), "attempted": window["calls"],
                    "failed": window["failed"], "metrics": {},
                    "device": device}
    if trace:
        view = tracing.load(tracing.latest_xplane(trace_dir),
                            set(obs_trace.SPAN_NAMES))
        ctx = {"stats": structure.stats, "peak": peak}
        for entry, mfile in cell.per_layer:
            reader = load_module(cell.bench_dir, "readers", mfile["reader"])
            value = reader.read(view, ctx, **mfile.get("params", {}))
            if value is not None:
                result["metrics"][entry["name"]] = {"value": float(value),
                                                    "unit": entry["unit"]}
        device["busy_s"] = view.busy_s
        device["window_s"] = view.window_s
        result["breakdown"] = tracing.breakdown(view)
    else:
        measured = {cell.traffic["reports"]:
                    win_ms(window["elapsed_s"], window["calls"]),
                    "setup_s": setup_s}
        if mem is not None:  # a TPU always keeps memory statistics
            measured["peak_hbm_gib"] = mem / 2**30
        for entry in cell.end_to_end:
            value = measured.get(entry["name"])
            if value is None:
                if entry["name"] == "peak_hbm_gib":
                    continue
                raise BenchError(f"{cell.name}: nothing measures "
                                 f"{entry['name']!r}")
            result["metrics"][entry["name"]] = {"value": float(value),
                                                "unit": entry["unit"]}
    result["compared"] = {k: {"value": float(v), "limit": float(lim)}
                          for k, (v, lim) in compared.items()}
    return [{"setup": setup}, {"window": window}], result


def win_ms(elapsed_s: float, calls: int) -> float:
    return elapsed_s * 1e3 / max(calls, 1)
