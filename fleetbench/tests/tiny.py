"""Benchmark cells cut to a size the CPU runs in seconds (tests only).

``file_cell`` builds a cell from a configuration and a traffic file
that ``BENCHMARK.json`` does not (yet) list, judged by the limits of
the benchmarked cell, so that the program paths of the configurations
kept for later cells (MIFA, the geometric median) stay tested."""
import copy
import dataclasses
from pathlib import Path

from fleetbench import harness

ROOT = Path(__file__).resolve().parents[2]
BASE = ROOT / "fleetbench"
BENCHMARKED = "xdevice-flude.diurnal"
FILE_CELLS = [("xdevice-flude", "diurnal"), ("selectall-mifa", "bernoulli"),
              ("xdevice-flude-gm", "signflip20")]


def file_cell(config: str, traffic: str) -> harness.Cell:
    limits = harness.load_json(BASE / "limits" / f"{BENCHMARKED}.json")
    cfg = harness.load_json(BASE / "configs" / f"{config}.json")
    return harness.Cell(
        f"{config}.{traffic}", cfg,
        harness.load_json(BASE / "traffic" / f"{traffic}.json"), limits,
        harness.resolve(ROOT, BENCHMARKED).end_to_end, [], 1,
        harness.model_of(cfg))


def tiny(cell: harness.Cell, n: int = 64, x: int = 8) -> harness.Cell:
    cell = dataclasses.replace(cell, config=copy.deepcopy(cell.config))
    c = cell.config
    if c["policy"] == "mifa":
        x = n
    for block in ("sim", "fl", "data"):
        c[block]["num_clients"] = n
    c["fl"]["clients_per_round"] = x
    c["fl"]["cohort_size"] = x
    return cell


def run(cell, tmp_path, seed=5, seconds=0.2, trace=False, **kw):
    import time
    return harness.run_cell(cell, seed, seconds, trace, Path(tmp_path),
                            time.perf_counter(), log=lambda m: None,
                            agg_impl="xla", **kw)
