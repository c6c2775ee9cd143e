"""BENCHMARK.json and the files it names."""
import json
import re
from pathlib import Path

import pytest

from fleetbench import harness
from fleetbench.peaks import peaks
from fleetbench.tests.tiny import FILE_CELLS, file_cell

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["fleetbench"]
    assert BENCH["command"] == ["python3", "fleetbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_to_its_files(workload):
    cell = harness.resolve(ROOT, workload)
    assert cell.config["policy"] in ("flude", "mifa")
    assert cell.traffic["dynamics"]
    assert "mismatches" in cell.limits["limits"]
    assert [m["name"] for m in cell.end_to_end] == [
        "round_ms", "setup_s", "peak_hbm_gb"]
    for m in cell.per_layer:
        assert callable(harness.load_reader(m["name"]))
    assert all(callable(getattr(cell.model_code, fn))
               for fn in harness.MODEL_API)


@pytest.mark.parametrize("files", FILE_CELLS, ids=".".join)
def test_config_builds_the_program_configs(files):
    cell = file_cell(*files)
    sim, fl = harness.program_configs(harness.spec_of(cell), seed=3)
    assert sim.num_clients == fl.num_clients == cell.config["data"][
        "num_clients"]
    assert fl.cohort_size == cell.config["fl"]["cohort_size"]


def test_names_units_and_bounds():
    names = [c["name"] for c in BENCH["configs"]] \
        + [w["name"] for w in BENCH["workloads"]] \
        + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    assert {m["name"] for m in BENCH["end_to_end"]} >= {"setup_s"}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])


def test_peaks_table():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks("TPU v9 imaginary")
