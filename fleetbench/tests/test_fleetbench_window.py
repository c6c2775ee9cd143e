"""The harness's set-up, window and check, end to end on the CPU at a
tiny size (the CLI itself refuses to run without a TPU)."""
import json
import shutil

import pytest

from fleetbench import harness
from fleetbench.tests.tiny import (BENCHMARKED, ROOT, file_cell, run,
                                   tiny)


@pytest.mark.parametrize("cell", [
    lambda: harness.resolve(ROOT, BENCHMARKED),
    lambda: file_cell("selectall-mifa", "bernoulli")],
    ids=["benchmarked", "mifa-files"])
def test_window_runs_and_checks(cell, tmp_path):
    res = run(tiny(cell()), tmp_path)
    assert res["correct"] is True
    assert res["attempted"] >= harness.NUMERIC_ROUNDS
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"round_ms", "setup_s", "peak_hbm_gb"}
    assert res["metrics"]["round_ms"]["value"] > 0
    assert res["checked"]["mismatches"] == {"value": 0.0, "limit": 0.0}
    assert res["checked"]["in_window_compiles"]["value"] == 0
    assert list(res["checked"]) == list(json.loads(json.dumps(
        res))["checked"])


def test_cell_added_by_files_only(tmp_path, monkeypatch):
    """A configuration, a traffic mix, a limits file and a per-layer
    metric added as new files run without an edit to any existing one."""
    from fleetbench import peaks
    table = json.loads(peaks.TABLE.read_text())
    table["devices"]["cpu"] = dict(table["devices"]["TPU v5 lite"])
    (tmp_path / "peaks.json").write_text(json.dumps(table))
    monkeypatch.setattr(peaks, "TABLE", tmp_path / "peaks.json")
    base = tmp_path / "fleetbench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(ROOT / "fleetbench" / sub, base / sub)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((base / "configs" / "xdevice-flude.json").read_text())
    cfg["name"] = "dummy-flude"
    for block in ("sim", "fl", "data"):
        cfg[block]["num_clients"] = 48
    cfg["fl"]["clients_per_round"] = cfg["fl"]["cohort_size"] = 8
    (base / "configs" / "dummy-flude.json").write_text(json.dumps(cfg))
    traffic = json.loads((base / "traffic" / "diurnal.json").read_text())
    traffic["dynamics_params"]["amp"] = 0.3
    (base / "traffic" / "dummy-sun.json").write_text(json.dumps(traffic))
    (base / "limits" / "dummy-flude.dummy-sun.json").write_text(
        (base / "limits" / "xdevice-flude.diurnal.json").read_text())
    (base / "metrics" / "dummy_selected.py").write_text(
        "def read(ctx):\n"
        "    return ctx.counters['selected'] / ctx.rounds\n")
    bench["configs"].append({"name": "dummy-flude", "source": "x",
                             "file": "fleetbench/configs/dummy-flude.json",
                             "reduced": ["num_clients"], "why": "test"})
    bench["workloads"].append({"name": "dummy-flude.dummy-sun",
                               "config": "dummy-flude",
                               "traffic": "dummy-sun", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "dummy_selected", "unit": "clients",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "plan", "moves": "round_ms",
                               "workloads": ["dummy-flude.dummy-sun"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.resolve(tmp_path, "dummy-flude.dummy-sun", base=base)
    res = run(cell, tmp_path / "out", trace=True, base=base)
    assert res["correct"] is True
    assert res["metrics"]["dummy_selected"]["value"] == pytest.approx(8.0)
    assert set(res["metrics"]) <= {m["name"] for m in bench["per_layer"]}
    assert "breakdown" in res and "busy_s" in res["device"]
