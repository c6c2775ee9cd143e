"""One run of one benchmark cell: set-up, the timed window, the check.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by the names in
``BENCHMARK.json``:

* ``configs/<config>.json``  the deployment (SimConfig and FLConfig
  fields, policy, model kind and widths, data scale, source, cuts);
* ``models/<kind>.py``  the model a configuration's ``model`` block
  names by ``kind``: its data, its plain reference and its counts
  (``MODEL_API``; ``models/mlp.py`` says what each function does);
* ``traffic/<traffic>.json``  the fleet's behaviour (availability
  process and its parameters, adversary);
* ``limits/<workload>.json``  the limit of each number the check
  compares, with the readings it was set from;
* ``metrics/<metric>.py``  a reader with ``read(ctx) -> float | None``.

The run drives the program's entry, ``FleetEngine(data, sim, fl)
.run(policy, rounds=R, diagnostics=False)``.  Set-up makes the data on
the device, builds the engine and runs it three times from the seed
(one round, the rounds the check follows, a short calibration); the
window is one
``run`` of R rounds, R chosen from the calibration so that it fills
the requested seconds.  Every ``run`` starts from the seed, so the
window's first rounds are the rounds set-up followed; the reference
(``fleetbench.reference``) follows the whole window's fleet
simulation and the model over its first rounds (``model_rounds`` of
the cell's limits file, three where it names none).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from fleetbench import checks

HERE = Path(__file__).resolve().parent
NUMERIC_ROUNDS = 3
CALIBRATION_ROUNDS = 10
TRACE_SECONDS = 3.0
CACHE_SAMPLE = 256
MODEL_API = ("make_data", "leaf_shapes", "init_params", "loss_fn",
             "train_flops", "eval_flops", "packed_dim")


# ---------------------------------------------------------------------------
# Finding a cell's files
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    chips: int
    model_code: Any       # the module models/<kind>.py


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: Path, name: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_model(kind: str, base: Path = HERE):
    """The module ``models/<kind>.py``; raises ``FileNotFoundError``
    without the file and ``AttributeError`` naming a function of
    ``MODEL_API`` that it lacks."""
    path = base / "models" / f"{kind}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no model file models/{kind}.py "
                                f"(looked for {path})")
    mod = _load_module(path, f"fleetbench_model_{kind.replace('.', '_')}")
    for fn in MODEL_API:
        if not callable(getattr(mod, fn, None)):
            raise AttributeError(f"models/{kind}.py has no {fn}()")
    return mod


def model_of(config: dict, base: Path = HERE):
    """The model file a configuration names; raises ``KeyError`` where
    its ``model`` block names no ``kind``."""
    kind = config.get("model", {}).get("kind")
    if not kind:
        raise KeyError(f"configuration {config.get('name')!r} names no "
                       f"model kind (\"model\": {{\"kind\": ...}})")
    return load_model(kind, base)


def resolve(root: Path, workload: str, bench: Optional[dict] = None,
            base: Path = HERE) -> Cell:
    """The files of ``workload``; raises ``KeyError`` when the benchmark
    does not name it or its configuration names no model kind,
    ``FileNotFoundError`` when a file is missing and ``AttributeError``
    when the model file lacks a function of ``MODEL_API``."""
    bench = bench if bench is not None else load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(root / configs[w["config"]]["file"])
    model_code = model_of(cfg, base)
    traffic = load_json(base / "traffic" / f"{w['traffic']}.json")
    limits = load_json(base / "limits" / f"{workload}.json")
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    layer = [m for m in bench["per_layer"]
             if workload in m.get("workloads", [workload])]
    for m in layer:
        if not (base / "metrics" / f"{m['name']}.py").is_file():
            raise FileNotFoundError(f"no reader metrics/{m['name']}.py")
    return Cell(workload, cfg, traffic, limits, e2e, layer,
                int(w["chips"]), model_code)


def spec_of(cell: Cell) -> dict:
    """The blocks the program and the reference are both built from."""
    c, t = cell.config, cell.traffic
    adv = None
    if t.get("adversary"):
        ap = dict(t.get("adversary_params", {}))
        sign = {"sign_flip": -1.0, "grad_scale": 1.0}[t["adversary"]]
        adv = {"kind": t["adversary"],
               "malicious_frac": float(ap["malicious_frac"]),
               "delta_scale": sign * float(ap["scale"])}
    return {"policy": c["policy"], "sim": dict(c["sim"]),
            "model_rounds": int(cell.limits.get("model_rounds",
                                                NUMERIC_ROUNDS)),
            "fl": dict(c["fl"]), "model": dict(c["model"]),
            "model_code": cell.model_code,
            "data": dict(c["data"]), "eval_every": int(c["eval_every"]),
            "dynamics": t["dynamics"],
            "dynamics_params": dict(t.get("dynamics_params", {})),
            "adversary": adv, "adversary_params":
                dict(t.get("adversary_params", {}))}


def load_reader(name: str, base: Path = HERE) -> Callable:
    return _load_module(base / "metrics" / f"{name}.py",
                        f"fleetbench_metric_{name.replace('.', '_')}").read


# ---------------------------------------------------------------------------
# The program
# ---------------------------------------------------------------------------

def _pairs(d: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in d.items()))


def program_configs(spec: dict, seed: int, agg_impl: Optional[str] = None):
    from repro.configs.base import FLConfig
    from repro.fl import SimConfig

    sim = dict(spec["sim"])
    for k in ("undep_means", "steps_per_sec", "bandwidth_mbps"):
        sim[k] = tuple(sim[k])
    sim_cfg = SimConfig(**sim, seed=int(seed))
    fl = dict(spec["fl"])
    if "agg_rule_params" in fl:
        fl["agg_rule_params"] = _pairs(fl["agg_rule_params"])
    if agg_impl is not None:
        fl["agg_impl"] = agg_impl
    fl_cfg = FLConfig(**fl, dynamics=spec["dynamics"],
                      dynamics_params=_pairs(spec["dynamics_params"]),
                      adversary=None if spec["adversary"] is None
                      else spec["adversary"]["kind"],
                      adversary_params=_pairs(spec["adversary_params"])
                      if spec["adversary"] else ())
    return sim_cfg, fl_cfg


class Recorder:
    """The program's policy, with the first rounds' plans and reports
    kept (device handles only: nothing is read back during a run)."""

    def __init__(self, policy, keep: int):
        self._policy = policy
        self.keep = keep
        self.selected: Dict[int, Any] = {}
        self.reports: Dict[int, Any] = {}

    def __getattr__(self, name):
        return getattr(self._policy, name)

    def init_state(self):
        self.selected, self.reports = {}, {}
        return self._policy.init_state()

    def plan(self, state, obs, rng):
        state, plan = self._policy.plan(state, obs, rng)
        if obs.rnd < self.keep:
            self.selected[obs.rnd] = plan.selected
        return state, plan

    def observe(self, state, plan, report):
        if report.rnd < self.keep:
            self.reports[report.rnd] = (report.received, report.losses)
        return self._policy.observe(state, plan, report)


class CompileCounter:
    """Counts traces, compiles and persistent-cache loads, by phase
    (``phase`` is ``"setup"``, ``"window"`` or None for neither)."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon
        self.phase: Optional[str] = None
        self.counts = {ph: {"traces": 0, "compiles": 0, "cache_loads": 0}
                       for ph in ("setup", "window")}
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **kw):
        if self.phase is None:
            return
        if event == self.EVENTS[0]:
            self.counts[self.phase]["traces"] += 1
        elif event == self.EVENTS[1]:
            self.counts[self.phase]["compiles"] += 1

    def _on_event(self, event, **kw):
        if self.phase is not None \
                and event == "/jax/compilation_cache/cache_hits":
            self.counts[self.phase]["cache_loads"] += 1

    def close(self):
        import jax.monitoring as mon
        self.phase = None
        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)


def _packed(tree) -> np.ndarray:
    """A model pytree as one float32 host vector, leaves in order."""
    import jax
    return np.concatenate([np.asarray(l, np.float32).reshape(-1)
                           for l in jax.tree.leaves(tree)])


@dataclasses.dataclass
class Program:
    engine: Any
    policy: Recorder
    data: Any
    spec: dict
    seed: int


def build(spec: dict, seed: int, agg_impl: Optional[str] = None,
          log: Callable = print) -> Program:
    from repro.fl import Fleet, FleetEngine, make_policy
    import jax

    t0 = time.perf_counter()
    data = spec["model_code"].make_data(seed, spec["data"])
    jax.block_until_ready(data.x)
    log(f"[setup] data {tuple(data.x.shape)} in "
        f"{time.perf_counter() - t0:.2f} s")
    sim_cfg, fl_cfg = program_configs(spec, seed, agg_impl)
    engine = FleetEngine(data, sim_cfg, fl_cfg)
    policy = make_policy(spec["policy"], sim_cfg, fl_cfg, Fleet(sim_cfg),
                         mesh=engine.mesh)
    return Program(engine, Recorder(policy, spec["model_rounds"]), data,
                   spec,
                   int(seed))


def run_rounds(prog: Program, rounds: int):
    """One ``run`` of the program, waited for; returns (hist, seconds)."""
    import jax
    t0 = time.perf_counter()
    hist = prog.engine.run(prog.policy, rounds=int(rounds),
                           eval_every=prog.spec["eval_every"],
                           diagnostics=False)
    jax.block_until_ready(hist.final_params)
    return hist, time.perf_counter() - t0


def store_ids(store) -> List[int]:
    """Client ids the host cache store holds a row for, read through its
    public ``stamp_of``; raises if it holds rows for other ids."""
    ids = [c for c in range(store.num_clients)
           if store.stamp_of(c) is not None]
    if len(ids) != len(store):
        raise RuntimeError(f"cache store holds {len(store)} rows, "
                           f"{len(ids)} of them for client ids")
    return ids


def cached_ids(prog: Program) -> List[int]:
    """Client ids the program holds a cache row for."""
    eng = prog.engine
    if eng.cache_store is not None:
        return store_ids(eng.cache_store)
    return [int(c) for c in np.flatnonzero(
        np.asarray(eng._last_caches.round_stamp) >= 0)]


def cached_rows(prog: Program, ids: List[int]) -> Dict[int, np.ndarray]:
    """The program's cache rows of ``ids``, packed, by client id."""
    import jax
    eng = prog.engine
    if not ids:
        return {}
    if eng.cache_store is not None:
        block = eng.cache_store.gather(np.asarray(ids))
    else:
        block = jax.tree.map(lambda l: jax.numpy.take(
            l, np.asarray(ids), axis=0), eng._last_caches.params)
    rows = np.concatenate([np.asarray(l, np.float32).reshape(len(ids), -1)
                           for l in jax.tree.leaves(block)], axis=1)
    return {c: rows[k] for k, c in enumerate(ids)}


def sample_ids(ids: List[int], seed: int, k: int = CACHE_SAMPLE) -> List[int]:
    """At most ``k`` of ``ids``, drawn from ``seed``."""
    if len(ids) <= k:
        return list(ids)
    pick = np.random.default_rng(seed).choice(len(ids), k, replace=False)
    return [ids[i] for i in sorted(pick)]


def setup(prog: Program, seconds: float, log: Callable = print) -> dict:
    """Warm-up runs that compile every program of the window, follow the
    first rounds for the check and pick the window's round count."""
    out = {}
    hist1, s1 = run_rounds(prog, 1)
    out["theta1"] = _packed(hist1.final_params)
    log(f"[setup] run(rounds=1) {s1:.2f} s")
    del hist1
    k = prog.policy.keep
    hist_k, s_k = run_rounds(prog, k)
    log(f"[setup] run(rounds={k}) {s_k:.2f} s")
    out["theta_k"] = _packed(hist_k.final_params)
    out["losses"] = []
    for r in range(k):
        _, loss = prog.policy.reports[r]
        sel = np.asarray(prog.policy.selected[r])
        out["losses"].append(float(np.asarray(loss)[sel].sum()
                                   / max(int(sel.sum()), 1)))
    out["cache_ids"] = cached_ids(prog)
    out["cache_after"] = cached_rows(prog, sample_ids(out["cache_ids"],
                                                      prog.seed))
    del hist_k
    hist, sc = run_rounds(prog, CALIBRATION_ROUNDS)
    per_round = sc / CALIBRATION_ROUNDS
    out["rounds"] = max(k, int(math.ceil(seconds / per_round)))
    log(f"[setup] calibration run(rounds={CALIBRATION_ROUNDS}) {sc:.3f} s; "
        f"window of {out['rounds']} rounds")
    del hist
    return out


def observe_run(prog: Program, hist) -> dict:
    """What the check reads of a finished run, copied to the host."""
    eng = prog.engine
    pol = prog.policy
    first = []
    for r in range(min(pol.keep, len(hist.selected))):
        first.append(dict(selected=np.asarray(pol.selected[r]),
                          received=np.asarray(pol.reports[r][0])))
    caches = eng._last_caches
    store = None
    if eng.cache_store is not None:
        store = {c: int(eng.cache_store.stamp_of(c))
                 for c in store_ids(eng.cache_store)}
    pc = getattr(hist, "part_count", None)
    return dict(selected=list(hist.selected), received=list(hist.received),
                wall_clock=list(hist.wall_clock),
                comm_mb=list(hist.comm_mb), first=first,
                progress=np.asarray(caches.progress),
                stamp=np.asarray(caches.round_stamp),
                store=store, part_count=None if pc is None
                else np.asarray(pc))


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------

def device_info(devices, trace: Optional[dict] = None) -> dict:
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devices]
    info["memory_peak_bytes"] = max(peaks) if peaks else 0
    if trace is not None:
        info["busy_s"] = trace["busy_s"]
        info["window_s"] = trace["window_s"]
    return info


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             out_dir: Path, t_start: float, log: Callable = print,
             agg_impl: Optional[str] = None, devices=None,
             base: Path = HERE, keep_trace: bool = False) -> dict:
    """One run of ``cell``: returns the result object of the last line."""
    import jax
    from fleetbench import tracing

    devices = devices if devices is not None else jax.devices()
    spec = spec_of(cell)
    counter = CompileCounter()
    counter.phase = "setup"
    prog = build(spec, seed, agg_impl=agg_impl, log=log)
    warm = setup(prog, TRACE_SECONDS if trace else seconds, log=log)
    rounds = warm["rounds"]
    setup_s = time.perf_counter() - t_start
    log(f"[setup] setup_s {setup_s:.3f}")

    stats0 = prog.engine.transfer_stats.snapshot()
    counter.phase = "window"
    trace_info = None
    if trace:
        hist, _, trace_info = tracing.traced(
            lambda: run_rounds(prog, rounds), out_dir, keep=keep_trace)
        window_s = trace_info["window_s"]
    else:
        hist, window_s = run_rounds(prog, rounds)
    counter.close()
    stats1 = prog.engine.transfer_stats.snapshot()
    log(f"[setup] compiles {counter.counts['setup']}")
    log(f"[window] {rounds} rounds in {window_s:.4f} s; "
        f"in-window {counter.counts['window']}")
    device = device_info(devices, trace_info)
    got = observe_run(prog, hist)
    got.update({k: warm[k] for k in ("theta1", "theta_k", "losses",
                                      "cache_ids", "cache_after")})
    data = prog.data
    del hist, prog
    gc.collect()

    from fleetbench import reference
    t0 = time.perf_counter()
    ref = reference.simulate(spec, data, seed, rounds,
                             numeric_rounds=spec["model_rounds"])
    log(f"[check] reference in {time.perf_counter() - t0:.2f} s; "
        f"{ref['resumed']} clients resumed from a cache in the first "
        f"{spec['model_rounds']} rounds")
    numbers = checks.compare(got, ref, cell.limits)
    in_window = counter.counts["window"]
    correct = checks.passed(numbers) and in_window["compiles"] == 0 \
        and in_window["traces"] == 0
    attempted = rounds
    failed = sum(1 for r in range(rounds)
                 if got["selected"][r] != ref["selected"][r]
                 or got["received"][r] != ref["received"][r]
                 or got["wall_clock"][r] != ref["wall_clock"][r])

    if trace:
        ctx = tracing.Context(
            trace=trace_info, rounds=rounds, window_s=window_s,
            spec=spec, device_kind=devices[0].device_kind,
            counters={"transfer_bytes": (stats1["h2d_bytes"]
                                         - stats0["h2d_bytes"]
                                         + stats1["d2h_bytes"]
                                         - stats0["d2h_bytes"]),
                      "completed_steps": sum(ref["completed_steps"]),
                      "selected": sum(ref["selected"])})
        metrics = {}
        for m in cell.per_layer:
            v = load_reader(m["name"], base)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {"round_ms": 1e3 * window_s / rounds, "setup_s": setup_s,
                  "peak_hbm_gb": device["memory_peak_bytes"] / 1e9}
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = trace_info["breakdown"]
    # Programs compiled in set-up apart from those loaded from the
    # persistent cache: a checkout's first run compiles all of them.
    result["setup_compiles"] = counter.counts["setup"]
    result["checked"] = checks.summary(numbers)
    result["checked"]["in_window_compiles"] = {
        "value": in_window["compiles"] + in_window["traces"], "limit": 0}
    return result
