"""The command refuses every platform but the TPU, another number of chips
than the cell asks for, and a directory without the program, exiting
non-zero with no result line."""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run as bench_run  # noqa: E402
ARGS = ["--workload", "rmat_s14.oneshot", "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def run(cwd: Path, **env):
    return subprocess.run(
        [sys.executable, "bench/run.py", *ARGS], cwd=cwd, capture_output=True,
        text=True, timeout=120, env={**os.environ, **env})


def test_refuses_the_cpu():
    out = run(ROOT, JAX_PLATFORMS="cpu")
    assert out.returncode == 2
    assert out.stdout == ""
    assert "no TPU" in out.stderr and "cpu" in out.stderr


def test_refuses_a_directory_without_the_program(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".traces", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    out = run(tmp_path, JAX_PLATFORMS="cpu")
    assert out.returncode == 2
    assert out.stdout == ""


def test_refuses_an_unknown_workload():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nope", "--seed", "1",
         "--seconds", "1"], cwd=ROOT, capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 2 and out.stdout == ""
    assert "no workload" in out.stderr


class FakeDevice:
    def __init__(self, platform="tpu", device_kind="TPU v5 lite"):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("count,chips,refused", [
    (1, 1, False), (4, 4, False), (4, 1, True), (1, 4, True)])
def test_refuses_another_number_of_chips_than_the_cell_asks_for(
        count, chips, refused):
    cell = type("Cell", (), {"name": "c", "chips": chips})()
    why = bench_run.refusal([FakeDevice()] * count, cell,
                            {"TPU v5 lite": {}})
    assert bool(why) == refused
    if refused:
        assert f"asks for {chips} chips, JAX found {count}" in why


def test_refuses_a_device_kind_missing_from_the_peaks():
    cell = type("Cell", (), {"name": "c", "chips": 1})()
    why = bench_run.refusal([FakeDevice(device_kind="TPU v9")], cell,
                            {"TPU v5 lite": {}})
    assert "TPU v9" in why
