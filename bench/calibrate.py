"""Readings behind the limits of ``correct``: whole runs of one cell, for
many seeds of sound runs and for the bf16 control, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1-12 \\
        --control-seeds 101-103 --seconds 3

Each seed is one ``harness.run_cell``, the path a run of ``run.py`` takes:
structure, values, set-up and warm-up (programs from the compile cache
after the first seed), a window of ``--seconds`` (whole calls, at least
one) and the check. Prints one JSON line per seed: the run's ``correct``,
the compared numbers with their limits, calls and milliseconds per call.
Runs only on the chip, like ``run.py``.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def seeds(text: str) -> list:
    out = []
    for part in filter(None, text.split(",")):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench import harness

    harness.place_cache(ROOT)
    if jax.devices()[0].platform != "tpu":
        print("bench/calibrate.py: no TPU", file=sys.stderr)
        return 2
    cell = harness.load_cell(ROOT / "bench", args.workload)
    runs = ([(s, None) for s in args.seeds]
            + [(s, "bf16") for s in args.control_seeds])
    for seed, control in runs:
        lines, result = harness.run_cell(cell, seed, args.seconds,
                                         control=control)
        window = lines[1]["window"]
        print(json.dumps({
            "workload": cell.name, "seed": seed, "control": control,
            "correct": result["correct"], "compared": result["compared"],
            "calls": window["calls"], "failed": window["failed"],
            "ms_per_call": harness.win_ms(window["elapsed_s"],
                                          window["calls"]),
            "setup_s": lines[0]["setup"]["setup_s"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
