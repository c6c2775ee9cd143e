"""Reduction of a profiler trace to busy time, idle share, per-module
device time and the breakdown."""
import gzip
import json
from pathlib import Path

import pytest

from fleetbench import tracing

DATA = Path(__file__).resolve().parent / "data"


def test_merge_and_busy():
    ev = [("a", 0, 10), ("b", 5, 10), ("c", 30, 5), ("d", 31, 1)]
    assert tracing.merge((s, s + d) for _, s, d in ev) == [[0, 15], [30, 35]]
    assert tracing.busy_ns(ev, 0, 100) == 20
    assert tracing.busy_ns(ev, 8, 32) == 7 + 2      # clipped to the window


def test_gaps_longest_first():
    ev = [("a", 10, 10), ("b", 50, 10)]
    assert tracing.gaps(ev, 0, 100) == [(60, 100), (20, 50), (0, 10)]


def test_per_module_and_labels():
    mods = [("jit_train_x(12)", 0, 100), ("jit_step(3)", 200, 50)]
    ops = [("%while.3 = (s32[]) while(...)", 10, 50),
           ("%fusion.1 = f32[8] fusion(...)", 210, 20),
           ("%copy.2 = f32[8] copy(...)", 400, 5)]
    assert tracing.total_by(mods, r"^jit_train_") == 100
    assert [n for n, _, _ in tracing.label_ops(ops, mods)] == [
        "jit_train_x/while.3", "jit_step/fusion.1", "?/copy.2"]


def test_reduce_synthetic():
    raw = {"window": (0, 1000),
           "ops": {0: [("%a.1 = x", 100, 100), ("%b.2 = y", 150, 100),
                       ("%a.1 = x", 600, 50)]},
           "modules": {0: [("jit_f(1)", 90, 200), ("jit_g(2)", 590, 70)]},
           "host": [("$engine.py:1 run", 0, 1000),
                    ("$cache_store.py:238 fetch", 300, 250),
                    ("ScheduleWork", 0, 1000)]}
    r = tracing.reduce(raw)
    assert r["busy_s"] == pytest.approx(200e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    bd = r["breakdown"]
    assert dict(bd["device_ops"]) == pytest.approx(
        {"jit_f/a.1": 100e-9, "jit_f/b.2": 100e-9, "jit_g/a.1": 50e-9})
    # gaps: [250, 600) -> fetch; [650, 1000) -> run; [0, 100) -> run
    names = dict((n, v) for n, v in bd["idle_gaps"])
    assert names["$cache_store.py:238 fetch"] == pytest.approx(350e-9)
    assert names["$engine.py:1 run"] == pytest.approx(450e-9)


def _recorded():
    with gzip.open(DATA / "chip_trace_3rounds.json.gz", "rt") as f:
        rec = json.load(f)
    to = lambda evs: [tuple(e) for e in evs]      # noqa: E731
    return {"window": tuple(rec["window"]),
            "ops": {int(d): to(v) for d, v in rec["ops"].items()},
            "modules": {int(d): to(v) for d, v in rec["modules"].items()},
            "host": to(rec["host"])}


def _busy_by_sweep(events, lo, hi):
    """Busy time by a sweep over sorted boundaries (independent of
    ``tracing.merge``)."""
    pts = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            pts += [(a, 1), (b, -1)]
    pts.sort(key=lambda p: (p[0], -p[1]))
    busy, depth, last = 0.0, 0, None
    for t, step in pts:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_recorded_chip_trace():
    raw = _recorded()
    lo, hi = raw["window"]
    r = tracing.reduce(raw)
    want = _busy_by_sweep(raw["ops"][0], lo, hi)
    assert r["busy_s"] == pytest.approx(want * 1e-9, rel=1e-12)
    idle = 1 - r["busy_s"] / r["window_s"]
    assert 0.5 < idle < 1.0               # a host-bound round path
    trainer = tracing.total_by(r["modules"], r"^jit_train_")
    assert trainer > 0
    ops = [n for n, _ in r["breakdown"]["device_ops"]]
    assert any(n.startswith("jit_train_cohort_dyn_offload/") for n in ops)
    assert tracing.total_by(r["ops"], r"^%fed_agg_pallas") > 0
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps and all(n.startswith("$") or n == "no python frame"
                        for n, _ in gaps)
    assert sum(v for _, v in gaps) <= r["window_s"] - r["busy_s"] + 1e-9
