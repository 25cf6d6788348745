"""The harness finds every configuration, traffic mix and per-layer metric
by name, as files of their own: a new one is a new file and needs no edit
to a file that exists. And ``BENCHMARK.json`` agrees with those files."""
import json
import re
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import harness  # noqa: E402
from bench.tests import tinytree  # noqa: E402

ROOT = harness.BENCH_DIR.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def add_files(bench: Path):
    """A new configuration, traffic mix (with its driver) and metric (with
    its reader), and a cell that uses them, all as new files."""
    root = bench.parent
    cfg = json.loads((bench / "configs" / "stencil2d_1024.json").read_text())
    cfg.update(name="stencil2d_wide", params={"nx": 6, "ny": 40})
    cfg["stats"] = tinytree.stats(cfg)
    (bench / "configs" / "stencil2d_wide.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "replay_twice.json").write_text(json.dumps(
        {"driver": "replay_twice", "reports": "replay_ms", "pool": 2,
         "warm_calls": 1}))
    (bench / "drivers" / "replay_twice.py").write_text(
        (bench / "drivers" / "replay.py").read_text())
    (bench / "readers" / "calls_in_window.py").write_text(
        "def read(view, ctx, scale):\n    return scale * view.n_calls\n")
    (bench / "metrics" / "calls.replay.json").write_text(json.dumps(
        {"reader": "calls_in_window", "params": {"scale": 1}}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "stencil2d_wide", "source": "test",
                            "file": "bench/configs/stencil2d_wide.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "stencil2d_wide.replay_twice",
                              "config": "stencil2d_wide",
                              "traffic": "replay_twice", "chips": 1,
                              "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "replay_ms":
            m["workloads"].append("stencil2d_wide.replay_twice")
    spec["per_layer"].append({"name": "calls.replay", "unit": "calls",
                              "better": "higher", "source": "device_trace",
                              "layer": "replay", "moves": "replay_ms"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))


def test_new_files_are_found_by_name(tmp_path):
    bench = tinytree.make(tmp_path)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    add_files(bench)
    for path, data in before.items():  # no file of the benchmark was edited
        assert path.read_bytes() == data, path
    cell = harness.load_cell(bench, "stencil2d_wide.replay_twice")
    assert cell.config["params"] == {"nx": 6, "ny": 40}
    assert cell.traffic["driver"] == "replay_twice"
    assert [m["name"] for m, _ in cell.per_layer] == ["calls.replay"]
    assert [m["name"] for m in cell.end_to_end] == [
        "replay_ms", "peak_hbm_gib", "setup_s"]
    lines, res = harness.run_cell(cell, 5, 0.1)
    assert res["correct"], res["compared"]
    reader = harness.load_module(bench, "readers", "calls_in_window")
    assert reader.read(type("V", (), {"n_calls": 3})(), {}, scale=2) == 6


def test_a_missing_file_is_a_loud_error(tmp_path):
    bench = tinytree.make(tmp_path)
    with pytest.raises(harness.BenchError, match="no workload"):
        harness.load_cell(bench, "nope.replay")
    with pytest.raises(harness.BenchError, match="no driver"):
        harness.load_module(bench, "drivers", "nope")


def test_metric_files_agree_with_benchmark_json():
    """A metric's file names its reader; what BENCHMARK.json says of the
    metric (layer, unit, source, moves) is said there alone."""
    readers = harness.BENCH_DIR / "readers"
    for m in SPEC["per_layer"]:
        f = json.loads((harness.BENCH_DIR / "metrics" / f"{m['name']}.json")
                       .read_text())
        assert set(f) <= harness.METRIC_FILE_KEYS, m["name"]
        assert (readers / f"{f['reader']}.py").is_file()


def test_a_metric_file_that_repeats_benchmark_json_is_refused(tmp_path):
    bench = tinytree.make(tmp_path)
    path = bench / "metrics" / "idle_pct.replay.json"
    f = json.loads(path.read_text())
    path.write_text(json.dumps({**f, "unit": "%"}))
    with pytest.raises(harness.BenchError, match="unit"):
        harness.load_cell(bench, "stencil2d_1024.replay")


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for w in SPEC["workloads"]:
        cell = harness.load_cell(harness.BENCH_DIR, w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.traffic["reports"] in names
        assert cell.per_layer, w["name"]
        assert set(cell.config["limits"]) == {
            "nnz_c_diff", "rows_wrong", "value_err", "checksum_err"}


def test_benchmark_json_keeps_the_contracts_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][1] == "bench/run.py" and SPEC["paths"] == ["bench"]
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    cells = {w["name"] for w in SPEC["workloads"]}
    for entry in SPEC["configs"] + SPEC["workloads"] + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["reduced"] == json.loads((ROOT / c["file"]).read_text())["reduced"]
    assert 1 <= SPEC["run_seconds"] <= 51
    runs = 2 + 14 * 24  # a full check of 24 cells
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
