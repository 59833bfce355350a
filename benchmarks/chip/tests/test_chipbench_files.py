"""Every name in BENCHMARK.json finds its file, and the files agree."""

import json
import sys
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
sys.path.insert(0, str(CHIP))

import harness  # noqa: E402

SPEC = harness.Spec(ROOT)
CELLS = [c["name"] for c in SPEC.data["workloads"]]
CONFIGS = [c["name"] for c in SPEC.data["configs"]]
PER_LAYER = [m["name"] for m in SPEC.data["per_layer"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_finds_config_mix_and_job(cell):
    entry = SPEC.cell(cell)
    config = SPEC.config(entry["config"])
    mix = SPEC.mix(entry["traffic"])
    assert config["name"] == entry["config"]
    assert callable(SPEC.job(mix).run)
    assert set(mix["limits"]) and all(v > 0 for v in mix["limits"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    e2e = {m["name"] for m in SPEC.metrics_for("end_to_end", cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert SPEC.metrics_for("per_layer", cell)


@pytest.mark.parametrize("name", CONFIGS)
def test_config_file_lists_its_cuts(name):
    entry = next(c for c in SPEC.data["configs"] if c["name"] == name)
    config = SPEC.config(name)
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    for key in entry["reduced"]:
        assert config[key] != config["published"][key]
    for key, value in config["published"].items():
        if key not in entry["reduced"]:
            assert config[key] == value
    assert config["precision"] == "float32"
    assert config["assumed"]["data"]


@pytest.mark.parametrize("name", PER_LAYER)
def test_per_layer_metric_has_a_reader_and_reporting_cells(name):
    entry = next(m for m in SPEC.data["per_layer"] if m["name"] == name)
    assert callable(SPEC.reader(name).read)
    moves = entry["moves"]
    for cell in entry.get("workloads", CELLS):
        assert cell in CELLS
        reported = {m["name"] for m in SPEC.metrics_for("end_to_end", cell)}
        assert moves in reported


def test_peak_table_names_its_source_and_the_v5e():
    peaks = harness.load_json(CHIP / "peaks.json")
    assert "TPU v5e" in peaks["source"]
    assert peaks["devices"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_names_and_paths_keep_to_the_contract():
    data = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert data["paths"] == ["benchmarks/chip"]
    assert data["command"] == ["python3", "benchmarks/chip/run.py"]
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in data[k]]
    assert len(names) == len(set(names))
    for c in data["configs"]:
        assert c["file"].startswith("benchmarks/chip/")
