"""Benchmark of the SpGEMM engine on a TPU: one cell, one seed, one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``src/`` and ``BENCHMARK.json``.
The cell (``BENCHMARK.json`` ``workloads``) names its configuration and
traffic mix; ``bench/harness.py`` finds their files by name, builds the
cell, warms up its shapes, runs whole timed calls for ``--seconds`` and
checks what they produced against the float64 reference. With ``--trace
1`` the window runs under the profiler and the per-layer metrics are read
from its trace.

Standard output: one JSON line of set-up phases, one of the window's
counters (calls, compiles, fallbacks), then the result line (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``compared``: each number compared with its
limit). Standard error ends with the same comparisons, one per line.

Exits 2, printing no result, off a TPU, with another number of chips than
the cell asks for, on a device kind missing from ``bench/peaks.json``, or without
the program's ``src/``. The control that the limits of ``correct`` were set
against runs through ``bench/calibrate.py``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return 2


def refusal(devices, cell, peaks: dict) -> str | None:
    """Why the run cannot go on these devices, or None: only a TPU, with
    as many chips as the cell asks for, of a kind in the table of peaks."""
    if devices[0].platform != "tpu":
        return (f"no TPU: JAX found {devices[0].platform}; the benchmark "
                f"runs only on the chip")
    if len(devices) != cell.chips:
        return (f"{cell.name} asks for {cell.chips} chips, JAX found "
                f"{len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        return f"device kind {kind!r} is not in bench/peaks.json"
    return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        return fail(f"--seed must be non-negative, got {args.seed}")
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no program under {ROOT / 'src'}: run from a checkout")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    try:
        cell = harness.load_cell(ROOT / "bench", args.workload)
        peaks = harness.peak_table()
    except (harness.BenchError, OSError, KeyError, ValueError) as e:
        return fail(f"cannot load the cell: {e!r}")

    import jax

    harness.place_cache(ROOT)
    devices = jax.devices()
    refused = refusal(devices, cell, peaks)
    if refused:
        return fail(refused)
    lines, result = harness.run_cell(cell, args.seed, args.seconds,
                                     trace=bool(args.trace), t_start=T_START,
                                     peak=peaks[devices[0].device_kind])
    for line in lines:
        print(json.dumps(line), flush=True)
    for name, c in result["compared"].items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
