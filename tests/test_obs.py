"""Observability subsystem (repro.obs): metric registry + numpy oracles,
span tracer / Perfetto export, sinks, report CLI, and — the invariants
that gate the whole feature — telemetry="full" adding zero per-round
host syncs while telemetry=None stays bit- and dispatch-identical to an
uninstrumented engine.
"""
import dataclasses
import glob
import json
import os

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.base import FLConfig
from repro.core import cache_store as CS
from repro.data.synthetic import federated_classification
from repro.fl import FleetEngine, History, SimConfig, make_policy
from repro.obs import metrics as OM
from repro.obs import report as OR
from repro.obs.trace import NullTracer, Tracer

ALL_POLICIES = ("flude", "random", "oort", "safa", "fedsea",
                "asyncfeded", "mifa")


def _setup(n=16, rounds=3, **fl_kw):
    data = federated_classification(n, seed=0, n_per_client=32)
    sim = SimConfig(num_clients=n, rounds=rounds, seed=0, local_steps=2)
    fl = FLConfig(num_clients=n, clients_per_round=8, **fl_kw)
    return data, sim, fl


def _rows(h):
    return (h.acc, h.wall_clock, h.comm_mb, h.received, h.selected,
            h.eval_mask)


# ---------------------------------------------------------------------------
# Tracer / Chrome export
# ---------------------------------------------------------------------------

def test_tracer_spans_and_summary():
    tr = Tracer()
    with tr.span("a", round=0):
        pass
    with tr.span("a"):
        pass
    with tr.span("b") as sp:
        pass
    assert sp.seconds >= 0.0
    s = tr.summary()
    assert s["a"]["count"] == 2 and s["b"]["count"] == 1
    assert s["a"]["total_s"] >= s["a"]["max_s"] >= 0.0
    assert s["a"]["mean_s"] == pytest.approx(s["a"]["total_s"] / 2)


def test_tracer_chrome_export(tmp_path):
    tr = Tracer()
    with tr.span("trainer", round=1):
        pass
    path = str(tmp_path / "trace.json")
    tr.save(path)
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert evs[0]["ph"] == "M"                      # process metadata
    by_name = {e["name"]: e for e in evs}
    x = by_name["trainer"]
    assert x["ph"] == "X" and x["dur"] >= 0 and x["args"] == {"round": 1}
    assert {"pid", "tid", "ts"} <= set(x)


def test_null_tracer_is_inert():
    nt = NullTracer()
    with nt.span("x", round=9) as sp:
        pass
    assert sp.seconds == 0.0
    assert nt.summary() == {} and nt.events == []
    # the module-level singleton hands out one shared span object
    assert obs.NULL_TRACER.span("a") is obs.NULL_TRACER.span("b")


def test_tracer_reset_clears_events():
    tr = Tracer()
    with tr.span("a"):
        pass
    tr.reset()
    assert tr.events == [] and tr.summary() == {}


def _profile_events(trace_dir, prefix="fl."):
    """``(name, start_ns, end_ns, stats)`` of the host events named
    ``prefix...`` in the one xplane file under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1, paths
    out = []
    for plane in jax.profiler.ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def test_tracer_spans_reach_the_profiler_trace(tmp_path):
    """Spans land in a ``jax.profiler`` trace as ``fl.<name>`` host
    events with their args, nested as they were opened; ``steps`` marks
    each step with ``fl.<name>`` and its number."""
    tr = Tracer()
    with jax.profiler.trace(str(tmp_path)):
        for rnd in tr.steps("round", 2):
            with tr.span("outer", round=rnd):
                with tr.span("inner"):
                    jax.numpy.ones(4).block_until_ready()
    evs = _profile_events(str(tmp_path))
    by = {}
    for name, s, e, stats in evs:
        by.setdefault(name, []).append((s, e, stats))
    assert sorted(by) == ["fl.inner", "fl.outer", "fl.round"]
    assert all(len(v) == 2 for v in by.values())
    steps = sorted(by["fl.round"])
    assert [st["step_num"] for _, _, st in steps] == [0, 1]
    for k, (s, e, st) in enumerate(sorted(by["fl.outer"])):
        assert st["round"] == k
        rs, re_, _ = steps[k]
        assert rs <= s and e <= re_                 # inside its step
        ins, ine, _ = sorted(by["fl.inner"])[k]
        assert s <= ins and ine <= e                # child inside parent
    # the tracer's own record agrees on the nesting
    assert [(n, p) for n, _, _, _, p, _ in tr.events] == [
        ("inner", "outer"), ("outer", None)] * 2


def test_tracer_self_time_is_total_less_children():
    """``summary()["self_s"]`` is a span's time less its direct
    children's (a scripted clock makes every duration exact)."""
    tr = Tracer()
    ticks = iter([0.0, 1.0, 2.0, 4.0, 5.0, 8.0, 10.0,     # first tree
                  20.0, 21.0])                            # a lone leaf
    tr._clock = lambda: next(ticks)
    tr.reset()                                            # epoch 0.0
    with tr.span("a"):                                    # [1, 10)
        with tr.span("b"):                                # [2, 8)
            with tr.span("c"):                            # [4, 5)
                pass
    with tr.span("c"):                                    # [20, 21)
        pass
    s = tr.summary()
    assert s["a"]["total_s"] == 9.0 and s["a"]["self_s"] == 9.0 - 6.0
    assert s["b"]["total_s"] == 6.0 and s["b"]["self_s"] == 6.0 - 1.0
    assert s["c"]["count"] == 2
    assert s["c"]["total_s"] == s["c"]["self_s"] == 2.0
    assert [p for *_, p, _ in tr.events] == ["b", "a", None, None]


def test_null_tracer_steps_is_a_plain_range():
    assert obs.NULL_TRACER.steps("round", 3) == range(3)


# ---------------------------------------------------------------------------
# Sinks
# ---------------------------------------------------------------------------

def test_jsonl_sink_appends_valid_lines(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    s = obs.JsonlSink(path)
    s.emit({"kind": "round", "x": 1.5, "v": [1, 2]})
    s.emit({"kind": "round", "f": np.float32(2.0)})   # default=float
    s.close()
    s2 = obs.JsonlSink(path)                          # append, not truncate
    s2.emit({"kind": "run_end"})
    s2.close()
    lines = [json.loads(l) for l in open(path)]
    assert [l["kind"] for l in lines] == ["round", "round", "run_end"]
    assert lines[1]["f"] == 2.0


def test_tee_sink_fans_out_and_drops_none():
    a, b = obs.MemorySink(), obs.MemorySink()
    t = obs.TeeSink(a, None, b)
    t.emit({"kind": "x"})
    assert a.events == b.events == [{"kind": "x"}]
    t.close()


# ---------------------------------------------------------------------------
# Metric registry
# ---------------------------------------------------------------------------

def test_registry_levels_and_needs():
    specs = {s.name: s for s in OM.metrics_for(
        "full", {"selected", "received", "fail", "online", "distribute",
                 "losses", "times", "stamp", "resume", "rnd"})}
    assert "counts" in specs and "staleness_hist" in specs
    assert "update_norm" not in specs        # rows/global not available
    basic = {s.name for s in OM.metrics_for(
        "basic", {"selected", "received", "fail", "online", "distribute",
                  "stamp", "rnd"})}
    assert "staleness_hist" not in basic     # full-level metric
    assert "counts" in basic
    with pytest.raises(ValueError, match="telemetry level"):
        OM.metrics_for("verbose", set())


def test_register_metric_validation():
    with pytest.raises(ValueError, match="metric level"):
        OM.register_metric("_t_bad", level="loud")(lambda c, s: {})
    OM.register_metric("_t_dup", needs=())(lambda c, s: {"_t_dup": 0})
    try:
        with pytest.raises(ValueError, match="already registered"):
            OM.register_metric("_t_dup")(lambda c, s: {})
        OM.register_metric("_t_dup", allow_override=True)(
            lambda c, s: {"_t_dup": 1})
        assert "_t_dup" in OM.available_metrics()
    finally:
        OM._REGISTRY.pop("_t_dup", None)


def test_make_metrics_fn_empty_and_needed_keys():
    fn, needed = OM.make_metrics_fn("basic", set(), {})
    assert fn is None and needed == ()
    fn, needed = OM.make_metrics_fn(
        "basic", {"selected", "received", "fail", "online", "distribute"},
        {"num_clients": 8})
    assert fn is not None and "selected" in needed
    assert "num_clients" not in needed       # static keys aren't ctx


# ---------------------------------------------------------------------------
# Metric numpy oracles (synthetic round context)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def synth_ctx():
    rng = np.random.default_rng(7)
    n = 12
    sel = np.zeros(n, bool); sel[:8] = True
    online = rng.random(n) < 0.8
    dist = sel.copy()
    recv = sel & online & (rng.random(n) < 0.7)
    fail = sel & ~recv
    resume = np.zeros(n, bool); resume[2:5] = True
    losses = rng.random(n).astype(np.float32) * 2
    times = rng.random(n).astype(np.float32) * 50
    stamp = rng.integers(-1, 6, n).astype(np.int32)
    stamp_pre = stamp.copy()
    stamp[stamp == 1] = -1                   # "expired" rows
    rule_state = rng.random(n).astype(np.float32)
    rows = {"w": rng.standard_normal((n, 3, 2)).astype(np.float32),
            "b": rng.standard_normal((n, 4)).astype(np.float32)}
    glob = {"w": rng.standard_normal((3, 2)).astype(np.float32),
            "b": rng.standard_normal(4).astype(np.float32)}
    return dict(selected=sel, distribute=dist, resume=resume,
                online=online, received=recv, fail=fail, losses=losses,
                times=times, progress=np.zeros(n, np.int32), stamp=stamp,
                stamp_pre_expire=stamp_pre, rule_state=rule_state,
                rows=rows, rows_mask=recv, rnd=7, **{"global": glob})


@pytest.fixture(scope="module")
def synth_out(synth_ctx):
    avail = set(synth_ctx) | {"cohort_size"}
    static = {"num_clients": 12, "cohort_size": 8, "local_steps": 2,
              "staleness_edges": OM.STALENESS_EDGES}
    fn, needed = OM.make_metrics_fn("full", avail, static)
    assert set(needed) <= set(synth_ctx)
    return jax.device_get(fn({k: synth_ctx[k] for k in needed}))


def test_oracle_counts(synth_ctx, synth_out):
    c = synth_ctx
    assert synth_out["selected_count"] == c["selected"].sum()
    assert synth_out["received_count"] == c["received"].sum()
    assert synth_out["interrupted_count"] == c["fail"].sum()
    assert synth_out["online_count"] == c["online"].sum()
    assert synth_out["download_count"] == \
        (c["distribute"] & c["online"]).sum()


def test_oracle_masked_means(synth_ctx, synth_out):
    c = synth_ctx
    got = c["losses"][c["received"]]
    np.testing.assert_allclose(synth_out["local_loss_mean"], got.mean(),
                               rtol=1e-6)
    np.testing.assert_allclose(synth_out["local_loss_max"], got.max(),
                               rtol=1e-6)
    t = c["times"][c["received"]]
    np.testing.assert_allclose(synth_out["finish_time_mean"], t.mean(),
                               rtol=1e-6)
    np.testing.assert_allclose(synth_out["finish_time_max"], t.max(),
                               rtol=1e-6)


def test_oracle_cache_and_cohort(synth_ctx, synth_out):
    c = synth_ctx
    assert synth_out["cache_rows"] == (c["stamp"] >= 0).sum()
    assert synth_out["cache_hit_count"] == \
        (c["resume"] & c["selected"]).sum()
    assert synth_out["cache_expired_count"] == \
        ((c["stamp_pre_expire"] >= 0) & (c["stamp"] < 0)).sum()
    np.testing.assert_allclose(synth_out["cohort_fill"],
                               c["selected"].sum() / 8.0, rtol=1e-6)


def test_oracle_staleness_hist(synth_ctx, synth_out):
    c = synth_ctx
    live = c["stamp"] >= 0
    s = c["rnd"] - c["stamp"]
    edges = OM.STALENESS_EDGES
    want = []
    for b, lo in enumerate(edges):
        hi = edges[b + 1] if b + 1 < len(edges) else np.inf
        want.append((live & (s >= lo) & (s < hi)).sum())
    np.testing.assert_array_equal(synth_out["staleness_hist"], want)
    assert synth_out["staleness_hist"].sum() == live.sum()


def test_oracle_trust_quantiles(synth_ctx, synth_out):
    st = synth_ctx["rule_state"]
    np.testing.assert_allclose(
        synth_out["trust_quartiles"],
        np.quantile(st, [0.25, 0.5, 0.75]), rtol=1e-5)
    np.testing.assert_allclose(synth_out["trust_min"], st.min())
    np.testing.assert_allclose(synth_out["trust_max"], st.max())


def test_oracle_update_norms(synth_ctx, synth_out):
    c = synth_ctx
    rows, g, mask = c["rows"], c["global"], c["rows_mask"]
    flat = np.concatenate(
        [(rows["w"] - g["w"]).reshape(12, -1),
         (rows["b"] - g["b"]).reshape(12, -1)], axis=1)
    norms = np.linalg.norm(flat, axis=1)
    np.testing.assert_allclose(synth_out["update_norm_mean"],
                               norms[mask].mean(), rtol=1e-5)
    np.testing.assert_allclose(synth_out["update_norm_max"],
                               norms[mask].max(), rtol=1e-5)
    mean_row = {k: g[k] + (rows[k] - g[k])[mask].sum(0) / mask.sum()
                for k in rows}
    rflat = np.concatenate(
        [(rows["w"] - mean_row["w"]).reshape(12, -1),
         (rows["b"] - mean_row["b"]).reshape(12, -1)], axis=1)
    resid = np.linalg.norm(rflat, axis=1)
    np.testing.assert_allclose(synth_out["agg_residual_mean"],
                               resid[mask].mean(), rtol=1e-5)
    np.testing.assert_allclose(synth_out["agg_residual_max"],
                               resid[mask].max(), rtol=1e-5)


@pytest.mark.parametrize("bound", [8, 12, 20])
def test_update_norm_rows_bound_gather_matches(synth_ctx, synth_out,
                                               bound):
    """``rows_bound`` makes update_norm gather the received rows into a
    compact (K, ...) block before reducing (the full-scan fast path);
    the stats must match the ungathered reduction, whether the bound is
    tight, equal to, or above the fleet view."""
    avail = set(synth_ctx) | {"cohort_size"}
    static = {"num_clients": 12, "cohort_size": 8, "local_steps": 2,
              "staleness_edges": OM.STALENESS_EDGES,
              "rows_bound": bound}
    fn, needed = OM.make_metrics_fn("full", avail, static)
    out = jax.device_get(fn({k: synth_ctx[k] for k in needed}))
    for col in ("update_norm_mean", "update_norm_max",
                "agg_residual_mean", "agg_residual_max"):
        np.testing.assert_allclose(out[col], synth_out[col], rtol=1e-5,
                                   err_msg=col)


# ---------------------------------------------------------------------------
# Engine integration: the invariants
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module", params=[1, 2],
                ids=["depth1", "depth2"])
def depth_engine(request):
    """One engine per pipeline depth, shared across the policy sweep so
    the compiled trainer is reused (same-task multi-policy loop)."""
    data, sim, fl = _setup(dynamics="bernoulli",
                           pipeline_depth=request.param)
    return FleetEngine(data, sim, fl)


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_full_telemetry_is_bit_identical(depth_engine, policy):
    """telemetry="full" must not perturb the trajectory: History rows
    are bit-identical to a telemetry-off run for every policy at
    pipeline depths 1 and 2."""
    h0 = depth_engine.run(policy, diagnostics=False, telemetry=False)
    h1 = depth_engine.run(policy, diagnostics=False, telemetry="full")
    assert _rows(h1) == _rows(h0), policy
    assert h0.metrics is None
    assert h1.metrics is not None and len(h1.metrics["selected_count"]) \
        == len(h1.acc)


def test_host_loop_telemetry_bit_identical():
    data, sim, fl = _setup()                 # bernoulli_host loop
    engine = FleetEngine(data, sim, fl)
    h0 = engine.run("flude", diagnostics=False, telemetry=False)
    h1 = engine.run("flude", diagnostics=False, telemetry="full")
    assert _rows(h1) == _rows(h0)
    assert h1.metrics["received_count"] == h1.received
    assert h1.metrics["selected_count"] == h1.selected


def test_full_telemetry_adds_zero_host_syncs(monkeypatch):
    """The fused metrics dispatch rides the ledger's existing readback:
    a telemetry="full" run performs exactly as many ``jax.device_get``
    host syncs as a telemetry-off run (flude = device-native planning,
    pipelined)."""
    data, sim, fl = _setup(dynamics="bernoulli", pipeline_depth=2)
    engine = FleetEngine(data, sim, fl)
    engine.run("flude", diagnostics=False, telemetry=False)   # warm up

    counts = []
    real = jax.device_get

    def counting(x):
        counts.append(1)
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    engine.run("flude", diagnostics=False, telemetry=False)
    off = len(counts)
    counts.clear()
    engine.run("flude", diagnostics=False, telemetry="full")
    on = len(counts)
    assert on == off > 0


class _CompileEvents:
    """Counts JAX traces and backend compiles while open (the events
    the benchmark harness counts)."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.count = 0

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def test_spans_level_runs_the_telemetry_off_programs(monkeypatch):
    """telemetry="spans" after a warm untraced run: bit-identical
    History, no trace or compile (no metrics dispatch is built), the
    same host-sync count as telemetry off, an empty metrics dict, and
    the seams traced."""
    def boom(*a, **k):
        raise AssertionError("make_metrics_fn called at level 'spans'")

    monkeypatch.setattr(obs, "make_metrics_fn", boom)
    monkeypatch.setattr(OM, "make_metrics_fn", boom)
    data, sim, fl = _setup(dynamics="bernoulli", pipeline_depth=2,
                           cohort_size=8, cache_offload="host")
    engine = FleetEngine(data, sim, fl)
    engine.run("flude", diagnostics=False, telemetry=False)   # warm up

    counts = []
    real = jax.device_get

    def counting(x):
        counts.append(1)
        return real(x)

    monkeypatch.setattr(jax, "device_get", counting)
    h0 = engine.run("flude", diagnostics=False, telemetry=False)
    off = len(counts)
    counts.clear()
    tel = obs.Telemetry(level="spans")
    with _CompileEvents() as compiles:
        h1 = engine.run("flude", diagnostics=False, telemetry=tel)
    assert compiles.count == 0
    assert len(counts) == off > 0
    assert _rows(h1) == _rows(h0)
    assert h1.metrics == {} and h0.metrics is None
    spans = tel.tracer.summary()
    assert spans["trainer"]["count"] == len(h1.acc)
    assert spans["cohort_index"]["count"] == len(h1.acc)
    assert engine._tracer is obs.NULL_TRACER
    assert engine._cache_stream.tracer is obs.NULL_TRACER


def test_spans_level_from_config():
    data, sim, fl = _setup(dynamics="bernoulli", telemetry="spans")
    h = FleetEngine(data, sim, fl).run("flude", diagnostics=False)
    assert h.metrics == {}


def test_offload_run_nests_cache_stream_spans():
    """Every cache-stream step is spanned, under the engine seam that
    drives it."""
    data, sim, fl = _setup(dynamics="bernoulli", cohort_size=8,
                           cache_offload="host")
    tel = obs.Telemetry(level="spans")
    h = FleetEngine(data, sim, fl).run("flude", diagnostics=False,
                                      telemetry=tel)
    parents = {}
    for name, _ts, _dur, _args, parent, _self in tel.tracer.events:
        parents.setdefault(name, set()).add(parent)
    want = {"cache_d2h_issue": {"cache_fetch", "cache_stage"},
            "cache_count_bytes": {"cache_fetch", "cache_stage"},
            "cache_drain": {"cache_fetch", "cache_flush"},
            "cache_read": {"cache_fetch", "cache_drain"},
            "cache_apply": {"cache_drain"},
            "cache_gather": {"cache_fetch"},
            "cache_put": {"cache_fetch"},
            "cache_fetch": {"rounds"}, "cache_stage": {"rounds"},
            "cache_flush": {"rounds"}, "cohort_index": {"rounds"}}
    for name, ps in want.items():
        assert parents.get(name) == ps, (name, parents.get(name))
    s = tel.tracer.summary()
    n = len(h.acc)
    assert s["cache_gather"]["count"] == s["cache_put"]["count"] == n
    # one drain a round after the first, and the run-end flush's
    assert s["cache_drain"]["count"] == s["cache_apply"]["count"] == n
    assert s["cache_fetch"]["self_s"] <= s["cache_fetch"]["total_s"]


def test_device_round_path_traces_into_the_profiler(tmp_path):
    """A traced offload run writes its seams, the cache stream's
    children and one ``fl.round`` step marker per round into a
    ``jax.profiler`` trace."""
    data, sim, fl = _setup(dynamics="bernoulli", cohort_size=8,
                           cache_offload="host")
    engine = FleetEngine(data, sim, fl)
    engine.run("flude", diagnostics=False, telemetry=False)   # warm up
    with jax.profiler.trace(str(tmp_path)):
        h = engine.run("flude", diagnostics=False, telemetry="spans")
    names = [n for n, *_ in _profile_events(str(tmp_path))]
    assert names.count("fl.round") == len(h.acc)
    (_, init_fn, _, _), = engine._dyn_cache.values()
    assert init_fn.__name__ == "dynamics_init"
    assert {"fl.rounds", "fl.dynamics_step", "fl.plan", "fl.cohort_index",
            "fl.cache_fetch", "fl.cache_read", "fl.cache_put",
            "fl.trainer", "fl.round_cut", "fl.server_step",
            "fl.cache_stage", "fl.cache_d2h_issue",
            "fl.cache_count_bytes", "fl.ledger_resolve", "fl.eval",
            "fl.cache_flush"} <= set(names)


def test_telemetry_off_never_builds_metrics(monkeypatch):
    """telemetry=None is compiled out: the metrics factory must never
    run and the tracer stays the shared null singleton."""
    def boom(*a, **k):
        raise AssertionError("make_metrics_fn called with telemetry off")

    monkeypatch.setattr(obs, "make_metrics_fn", boom)
    monkeypatch.setattr(OM, "make_metrics_fn", boom)
    data, sim, fl = _setup(dynamics="bernoulli")
    engine = FleetEngine(data, sim, fl)
    h = engine.run("flude", diagnostics=False)
    assert h.metrics is None
    assert engine._tracer is obs.NULL_TRACER


def test_metric_columns_match_history_counts():
    """Device-computed counters agree with the ledger's History ints on
    a seeded run (independent reductions over the same masks)."""
    data, sim, fl = _setup(dynamics="bernoulli")
    h = FleetEngine(data, sim, fl).run("flude", diagnostics=False,
                                      telemetry="full")
    assert h.metrics["received_count"] == h.received
    assert h.metrics["selected_count"] == h.selected
    for r in range(len(h.acc)):
        assert h.metrics["interrupted_count"][r] >= 0
        assert h.metrics["download_count"][r] <= \
            h.metrics["selected_count"][r]
        assert h.metrics["online_count"][r] <= sim.num_clients


def test_report_losses_match_metrics():
    """local_loss_* and finish_time_* equal numpy reductions of the
    RoundReport the policy observed (full-scan (N,) views)."""
    data, sim, fl = _setup(dynamics="bernoulli")
    pol = make_policy("flude", sim, fl)
    reports = []
    orig = pol.observe

    def recording(state, plan, report):
        reports.append(jax.device_get(
            (report.received, report.losses, report.durations)))
        return orig(state, plan, report)

    object.__setattr__(pol, "observe", recording)
    h = FleetEngine(data, sim, fl).run(pol, diagnostics=False,
                                      telemetry="full")
    assert len(reports) == len(h.acc)
    for r, (recv, losses, times) in enumerate(reports):
        got = losses[recv]
        np.testing.assert_allclose(h.metrics["local_loss_mean"][r],
                                   got.mean(), rtol=1e-5)
        np.testing.assert_allclose(h.metrics["local_loss_max"][r],
                                   got.max(), rtol=1e-5)
        np.testing.assert_allclose(h.metrics["finish_time_mean"][r],
                                   times[recv].mean(), rtol=1e-5)


def test_basic_level_and_config_default():
    """FLConfig.telemetry="basic" turns metrics on by default and the
    full-level reductions stay compiled out."""
    data, sim, fl = _setup(dynamics="bernoulli", telemetry="basic")
    h = FleetEngine(data, sim, fl).run("flude", diagnostics=False)
    assert h.metrics is not None
    assert "selected_count" in h.metrics
    assert "update_norm_mean" not in h.metrics
    assert "staleness_hist" not in h.metrics


def test_flconfig_telemetry_validated():
    with pytest.raises(ValueError, match="telemetry"):
        FLConfig(num_clients=8, telemetry="verbose")
    assert FLConfig(num_clients=8, telemetry="spans").telemetry == "spans"
    with pytest.raises(ValueError, match="telemetry level"):
        obs.Telemetry(level="loud")


def test_offload_discard_emits_cache_metrics():
    data, sim, fl = _setup(dynamics="bernoulli", cohort_size=8,
                           cache_offload="discard",
                           cache_staleness_bound=2)
    engine = FleetEngine(data, sim, fl)
    h0 = engine.run("flude", diagnostics=False, telemetry=False)
    h1 = engine.run("flude", diagnostics=False, telemetry="full")
    assert _rows(h1) == _rows(h0)
    assert "cache_expired_count" in h1.metrics
    assert "cohort_fill" in h1.metrics
    assert all(0.0 <= f <= 1.0 for f in h1.metrics["cohort_fill"])


# ---------------------------------------------------------------------------
# Per-engine transfer stats
# ---------------------------------------------------------------------------

def test_transfer_stats_are_per_engine():
    data, sim, fl = _setup(dynamics="bernoulli", cohort_size=8,
                           cache_offload="host")
    e1 = FleetEngine(data, sim, fl)
    e2 = FleetEngine(data, sim, fl)
    e1.run("flude", diagnostics=False)
    assert e1.transfer_stats.d2h_async > 0
    assert e1.transfer_stats.sync_copies == 0
    # the second engine's counters are untouched by the first's run
    assert e2.transfer_stats.d2h_async == 0
    e2.run("flude", diagnostics=False)
    assert e2.transfer_stats.d2h_async == e1.transfer_stats.d2h_async
    # the module exposes no process-wide aggregate (lint enforces this)
    assert not hasattr(CS, "STATS")


def test_engine_without_offload_has_zero_transfer_stats():
    data, sim, fl = _setup(dynamics="bernoulli")
    e = FleetEngine(data, sim, fl)
    e.run("flude", diagnostics=False)
    assert e.transfer_stats.snapshot() == {
        "h2d_async": 0, "d2h_async": 0, "h2d_bytes": 0, "d2h_bytes": 0,
        "pre_issued_reads": 0, "sync_copies": 0, "rows_written": 0,
        "rows_cleared": 0, "rows_hit": 0, "host_grows": 0}


# ---------------------------------------------------------------------------
# History JSON round-trip (golden-file format)
# ---------------------------------------------------------------------------

def test_history_json_roundtrip():
    data, sim, fl = _setup(dynamics="bernoulli")
    h = FleetEngine(data, sim, fl).run("flude", telemetry="full")
    h.trust = np.linspace(0, 1, sim.num_clients)      # dynamic extra
    d = json.loads(json.dumps(h.to_json()))           # through real JSON
    assert "final_params" not in d
    h2 = History.from_json(d)
    assert _rows(h2) == _rows(h)
    assert h2.metrics == h.metrics
    np.testing.assert_allclose(h2.trust, h.trust)
    np.testing.assert_allclose(h2.part_count, h.part_count)


def test_history_from_json_tolerates_golden_dicts():
    h = History.from_json({"acc": [0.5], "wall_clock": [1.0],
                           "comm_mb": [2.0], "received": [3],
                           "selected": [4]})
    assert h.eval_mask == [] and h.metrics is None
    assert h.time_to_accuracy(0.4) == 1.0             # empty mask = all-True


# ---------------------------------------------------------------------------
# Telemetry session + JSONL + report CLI end-to-end
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def run_artifacts(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("obs")
    jsonl = str(tmp / "run.jsonl")
    trace = str(tmp / "trace.json")
    data, sim, fl = _setup(dynamics="bernoulli")
    tel = obs.Telemetry(level="full", jsonl=jsonl, trace=trace)
    h = FleetEngine(data, sim, fl).run("flude", diagnostics=False,
                                      telemetry=tel)
    tel.close()
    return jsonl, trace, tel, h


def test_jsonl_stream_well_formed(run_artifacts):
    jsonl, _, tel, h = run_artifacts
    lines = [json.loads(l) for l in open(jsonl)]
    kinds = [l["kind"] for l in lines]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    assert kinds.count("round") == len(h.acc)
    start = lines[0]
    assert start["policy"] == "flude" and start["level"] == "full"
    rounds = [l for l in lines if l["kind"] == "round"]
    assert [r["round"] for r in rounds] == list(range(len(h.acc)))
    for r in rounds:
        assert r["received"] == h.received[r["round"]]
        assert r["selected_count"] == h.selected[r["round"]]
    end = lines[-1]
    assert end["rounds"] == len(h.acc)
    assert end["final_acc"] == pytest.approx(h.acc[-1])
    assert "spans" in end and end["spans"]["trainer"]["count"] == \
        len(h.acc)
    assert tel.last_events == lines


def test_trace_file_is_perfetto_loadable(run_artifacts):
    _, trace, tel, h = run_artifacts
    doc = json.load(open(trace))
    evs = doc["traceEvents"]
    assert evs and doc["displayTimeUnit"] == "ms"
    names = {e["name"] for e in evs}
    assert {"trainer", "server_step", "round_cut", "plan",
            "ledger_resolve", "metrics", "rounds"} <= names
    for e in evs:
        assert "ph" in e and "pid" in e and "tid" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0 and isinstance(e["ts"], float)
    # span summary agrees with the event stream
    assert tel.tracer.summary()["trainer"]["count"] == len(h.acc)


def test_report_cli_renders_and_exits_zero(run_artifacts, capsys):
    jsonl, _, _, h = run_artifacts
    assert OR.main([jsonl]) == 0
    out = capsys.readouterr().out
    assert "round-time breakdown" in out
    assert "policy=flude" in out
    assert "local_loss_mean" in out
    assert OR.main([jsonl, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["rounds"] == len(h.acc)
    assert doc["metrics"]["selected_count"]["last"] == h.selected[-1]
    assert doc["spans"]["trainer"]["count"] == len(h.acc)


def test_report_cli_error_paths(tmp_path, capsys):
    assert OR.main([str(tmp_path / "missing.jsonl")]) == 1
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"kind": "round"\n')
    assert OR.main([str(bad)]) == 1
    assert "bad JSON line" in capsys.readouterr().err
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert OR.main([str(empty)]) == 1


def test_report_parse_groups_multiple_runs(tmp_path):
    path = str(tmp_path / "multi.jsonl")
    data, sim, fl = _setup(dynamics="bernoulli", rounds=2)
    engine = FleetEngine(data, sim, fl)
    for policy in ("flude", "random"):
        tel = obs.Telemetry(level="basic", jsonl=path)
        engine.run(policy, diagnostics=False, telemetry=tel)
        tel.close()
    runs = OR.parse_runs(path)
    assert len(runs) == 2
    assert runs[0]["start"]["policy"] == "flude"
    assert runs[1]["start"]["policy"] == "random"
    assert len(runs[1]["rounds"]) == 2 and runs[1]["end"] is not None
    s = OR.summarize(runs[-1])
    assert s["policy"] == "random" and s["rounds"] == 2


def test_sparkline():
    assert OR.sparkline([]) == ""
    assert OR.sparkline([1.0]) == "▁"
    line = OR.sparkline([0, 1, 2, 3])
    assert line[0] == "▁" and line[-1] == "█" and len(line) == 4
    assert len(OR.sparkline(list(range(100)), width=32)) == 32
