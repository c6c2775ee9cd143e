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
    for sub in ("configs", "traffic", "limits", "metrics", "models"):
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


def test_model_added_by_files_only(tmp_path, monkeypatch):
    """A model kind added as one new file, ``models/<kind>.py`` (here the
    MLP's functions under a new name, so that the program can run it),
    runs through a cell without an edit to any existing file; the
    per-layer readers count its work from the new file."""
    from fleetbench import counts, peaks, tracing
    table = json.loads(peaks.TABLE.read_text())
    table["devices"]["cpu"] = dict(table["devices"]["TPU v5 lite"])
    (tmp_path / "peaks.json").write_text(json.dumps(table))
    monkeypatch.setattr(peaks, "TABLE", tmp_path / "peaks.json")
    base = tmp_path / "fleetbench"
    for sub in ("configs", "traffic", "limits", "metrics", "models"):
        shutil.copytree(ROOT / "fleetbench" / sub, base / sub)
    calls = tmp_path / "make_data.calls"
    (base / "models" / "mlp_copy.py").write_text(
        (ROOT / "fleetbench" / "models" / "mlp.py").read_text()
        + "\n\n_make_data = make_data\n\n\n"
        "def make_data(seed, spec):\n"
        f"    with open({str(calls)!r}, 'a') as f:\n"
        "        f.write('called\\n')\n"
        "    return _make_data(seed, spec)\n")
    cfg = json.loads((base / "configs" / "xdevice-flude.json").read_text())
    cfg["name"] = "copy-flude"
    cfg["model"]["kind"] = "mlp_copy"
    for block in ("sim", "fl", "data"):
        cfg[block]["num_clients"] = 48
    cfg["fl"]["clients_per_round"] = cfg["fl"]["cohort_size"] = 8
    (base / "configs" / "copy-flude.json").write_text(json.dumps(cfg))
    (base / "limits" / "copy-flude.diurnal.json").write_text(
        (base / "limits" / "xdevice-flude.diurnal.json").read_text())
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "copy-flude", "source": "x",
                             "file": "fleetbench/configs/copy-flude.json",
                             "reduced": ["num_clients"], "why": "test"})
    bench["workloads"].append({"name": "copy-flude.diurnal",
                               "config": "copy-flude", "traffic": "diurnal",
                               "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] == "fed_agg_roofline":
            m["workloads"].append("copy-flude.diurnal")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    # The CPU runs no Pallas kernel: give the trace one fed_agg call of
    # a known length, so that the roofline reader has something to read.
    kernel_ns = 1e5
    orig_reduce = tracing.reduce

    def reduce(raw, k=10):
        out = orig_reduce(raw, k)
        out["ops"].append(("%fed_agg_pallas.1 = custom-call", 0.0, kernel_ns))
        return out

    monkeypatch.setattr(tracing, "reduce", reduce)
    cell = harness.resolve(tmp_path, "copy-flude.diurnal", base=base)
    assert cell.model_code.__file__ == str(base / "models" / "mlp_copy.py")
    res = run(cell, tmp_path / "out", trace=True, base=base)
    assert res["correct"] is True, res["checked"]
    assert calls.read_text() == "called\n"
    assert res["metrics"]["round_mfu"]["value"] > 0
    rounds, dim = res["attempted"], 199210
    p = peaks.peaks("cpu")
    least = rounds * max(counts.agg_bytes(8, dim) / p["hbm_bytes_per_s"],
                         counts.agg_flops(8, dim) / p["bf16_flops_per_s"])
    assert res["metrics"]["fed_agg_roofline"]["value"] == pytest.approx(
        100.0 * least / (kernel_ns * 1e-9))
