"""The program's ``fl.*`` host spans laid against the device's idle
time: nesting, exposed time, self time and the exact split of idle time
by the innermost span."""
import gzip
import importlib.util
import json
import re
from pathlib import Path

import pytest

from fleetbench import spans, tracing

HERE = Path(__file__).resolve().parent
METRICS = HERE.parent / "metrics"


def _spans(host, lo=0, hi=1000):
    return spans.host_spans(host, lo, hi)


def test_host_spans_nest_by_containment_and_clip():
    host = [("fl.rounds", -50, 1100), ("fl.trainer", 100, 50),
            ("$engine.py:1 run", 0, 1000), ("fl.cache_fetch", 200, 300),
            ("fl.cache_read", 220, 30), ("fl.cache_put", 400, 50),
            ("ScheduleWork", 0, 5)]
    sp = _spans(host)
    got = [(s.name, s.start, s.end, sp[s.parent].name if s.parent >= 0
            else None) for s in sp]
    assert got == [("fl.rounds", 0, 1000, None),
                   ("fl.trainer", 100, 150, "fl.rounds"),
                   ("fl.cache_fetch", 200, 500, "fl.rounds"),
                   ("fl.cache_read", 220, 250, "fl.cache_fetch"),
                   ("fl.cache_put", 400, 450, "fl.cache_fetch")]


def test_exposed_is_idle_within_the_union_of_the_spans():
    # two overlapping stream seams: their overlap counts once
    sp = _spans([("fl.cache_fetch", 100, 300), ("fl.cache_stage", 300, 200),
                 ("fl.trainer", 600, 100)])
    idle = [(0, 150), (250, 450), (480, 520), (650, 1000)]
    # fetch ∪ stage = [100, 500): idle within it 50 + 200 + 20
    assert spans.exposed_ns(idle, sp, spans.CACHE_STREAM) == 270
    assert spans.exposed_ns(idle, sp, ["fl.trainer"]) == 50
    assert spans.exposed_ns(idle, sp, ["fl.ledger_resolve"]) == 0


def test_self_time_is_total_less_direct_children():
    sp = _spans([("fl.rounds", 0, 1000), ("fl.plan", 10, 40),
                 ("fl.cache_fetch", 100, 300), ("fl.cache_drain", 110, 100),
                 ("fl.cache_read", 120, 30), ("fl.cache_put", 300, 50)])
    assert spans.self_ns(sp, ["fl.cache_fetch"]) == 300 - 100 - 50
    assert spans.self_ns(sp, ["fl.cache_drain"]) == 100 - 30
    assert spans.self_ns(sp, ["fl.plan", "fl.cache_read"]) == 40 + 30
    assert spans.self_ns(sp, ["fl.rounds"]) == 1000 - 40 - 300


def test_idle_spans_split_one_gap_across_two_spans_exactly():
    # one idle gap [100, 700) under fl.rounds, holding a ledger read
    # [200, 350) and a stream fetch [350, 600) whose read is [400, 450)
    sp = _spans([("fl.rounds", 50, 900), ("fl.ledger_resolve", 200, 150),
                 ("fl.cache_fetch", 350, 250), ("fl.cache_read", 400, 50)])
    idle = [(0, 20), (100, 700)]
    got = spans.idle_by_span(idle, sp, 0, 1000)
    assert got == {spans.NO_SPAN: 20, "fl.rounds": 100 + 100,
                   "fl.ledger_resolve": 150, "fl.cache_fetch": 200,
                   "fl.cache_read": 50}
    assert sum(got.values()) == 20 + 600


def test_innermost_partitions_the_window():
    sp = _spans([("fl.a", 100, 400), ("fl.b", 200, 100), ("fl.c", 300, 100),
                 ("fl.d", 700, 100)])
    assert spans.innermost(sp, 0, 1000) == [
        (0, 100, spans.NO_SPAN), (100, 200, "fl.a"), (200, 300, "fl.b"),
        (300, 400, "fl.c"), (400, 500, "fl.a"), (500, 700, spans.NO_SPAN),
        (700, 800, "fl.d"), (800, 1000, spans.NO_SPAN)]


def test_seams_per_round():
    sp = _spans([("fl.rounds", 0, 4e6), ("fl.trainer", 0, 1e6),
                 ("fl.cache_fetch", 1e6, 2e6), ("fl.cache_read", 1e6, 5e5),
                 ("fl.ledger_resolve", 3e6, 1e6)], hi=4e6)
    got = spans.seams([(5e5, 4e6)], sp, rounds=2)
    assert got == pytest.approx({"cache_stream_exposed_ms": 1.0,
                                 "ledger_exposed_ms": 0.5,
                                 "dispatch_host_ms": 0.5})


def _reader_patterns():
    out = {}
    for path in sorted(METRICS.glob("*.py")):
        spec = importlib.util.spec_from_file_location(
            f"_reader_{path.stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        for attr in ("MODULE", "KERNEL", "KERNELS"):
            if hasattr(mod, attr):
                out[f"{path.stem}.{attr}"] = getattr(mod, attr)
    return out


def test_named_programs_miss_every_reader_pattern():
    """The flude round-0 plan, its run-end update and the dynamics init
    trace under their own names, which no per-layer reader counts."""
    from repro.fl import policies
    from repro.configs.base import FLConfig
    fl = FLConfig(num_clients=8)
    names = [policies._flude_plan_jit(fl, False).__name__,
             policies._flude_update_jit(fl).__name__]
    assert names == ["flude_plan", "flude_update"]
    patterns = _reader_patterns()
    assert "trainer_dev_ms.MODULE" in patterns
    for n in names + ["dynamics_init"]:
        for key, rx in patterns.items():
            assert not re.search(rx, f"jit_{n}(12)"), (n, key)


def _recorded():
    """Three rounds of the benchmarked cell run with spans on, traced on
    the chip: device 0's ops and modules, the host's ``fl.*`` spans."""
    with gzip.open(HERE / "data" / "chip_trace_spans_3rounds.json.gz",
                   "rt") as f:
        rec = json.load(f)
    to = lambda evs: [tuple(e) for e in evs]      # noqa: E731
    return (tuple(rec["window"]), to(rec["ops"]["0"]),
            to(rec["modules"]["0"]), to(rec["host"]), rec["rounds"])


def _covered_us(intervals, lo, hi):
    """Boolean mask of the microseconds of [lo, hi) that ``intervals``
    cover (independent of the interval arithmetic under test)."""
    import numpy as np
    mask = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for s, e in intervals:
        mask[int((s - lo) // 1000):int((e - lo) // 1000)] = True
    return mask


def test_recorded_chip_trace_with_spans():
    (lo, hi), ops, mods, host, rounds = _recorded()
    sp = spans.host_spans(host, lo, hi)
    assert [s.name for s in sp].count("fl.round") == rounds == 3
    assert tracing.total_by(mods, r"^jit_train_") > 0
    idle = spans.idle_intervals(ops, lo, hi)
    idle_ns = sum(e - s for s, e in idle)
    assert idle_ns == pytest.approx(
        (hi - lo) - tracing.busy_ns(ops, lo, hi), rel=1e-12)

    # the split is exact: it sums to the idle time, and the seams own
    # nearly all of it
    by = spans.idle_by_span(idle, sp, lo, hi)
    assert sum(by.values()) == pytest.approx(idle_ns, rel=1e-12)
    outer = sum(by.get(n, 0.0) for n in
                ("fl.rounds", "fl.round", spans.NO_SPAN))
    assert outer < 0.1 * idle_ns
    assert max(by, key=by.get).startswith("fl.cache_")

    # exposed time against a microsecond mask of idle ∩ union
    idle_mask = _covered_us(idle, lo, hi)
    for names in (spans.CACHE_STREAM, spans.LEDGER):
        cover = [(s.start, s.end) for s in sp if s.name in names]
        want = (idle_mask & _covered_us(cover, lo, hi)).sum() * 1e3
        got = spans.exposed_ns(idle, sp, names)
        assert got == pytest.approx(want, rel=2e-3, abs=5e4), names

    # self time against direct children found by brute-force containment
    def inside(c, p):
        return c is not p and p.start <= c.start and c.end <= p.end

    want = 0.0
    for s in sp:
        if s.name in spans.DISPATCH:
            kids = [c for c in sp if inside(c, s) and not any(
                inside(c, m) and inside(m, s) for m in sp)]
            want += (s.end - s.start) - sum(c.end - c.start for c in kids)
    assert spans.self_ns(sp, spans.DISPATCH) == pytest.approx(want)

    got = spans.seams(idle, sp, rounds)
    assert set(got) == {"cache_stream_exposed_ms", "ledger_exposed_ms",
                        "dispatch_host_ms"}
    assert got["cache_stream_exposed_ms"] > got["ledger_exposed_ms"] > 0
    assert got["dispatch_host_ms"] > 0
