"""Old host-side server loop vs device-resident FleetEngine, rounds/sec.

The baseline reconstructs the pre-fusion runner: every round it pulls the
stacked trainer outputs to host, runs the server step in numpy (weights
incl. staleness discount, leaf-wise weighted aggregation, C3 cache
bookkeeping), pushes the new global model + caches back to device, and
evaluates test accuracy — the host-side loop the typed FleetEngine
replaced.  The engine keeps params and caches device-resident across
rounds and syncs to host only at eval boundaries.

Each loop runs with its own default eval cadence (host loop: every
round, like the old runner; engine: eval boundaries only) — the cadence
difference is part of what the device-resident design buys and is
included in the measured speedup deliberately.  Numerical equivalence of
the two paths is NOT asserted here (the two runs train for different
cumulative rounds); that is covered by the golden-file tests in
tests/test_policy_api.py.

Fleet sizes N ∈ {256, 1024, 4096}; records results/benchmarks/
BENCH_engine.json.

``--mesh`` instead sweeps the client-mesh round path: forced host device
counts 1/2/4/8 (each in a fresh subprocess so
``--xla_force_host_platform_device_count`` lands before the jax import),
recording sharded rounds/sec and the fused server step's peak live bytes
with buffer donation on vs off, merged into the same JSON under "mesh".
"""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import QUICK, RESULTS, emit
from repro import core
from repro.configs.base import FLConfig
from repro.data.synthetic import federated_classification
from repro.fl import Fleet, FleetEngine, SimConfig, make_trainer
from repro.fl import classifier as CLF
from repro.obs import Tracer

# benchmark clock: every timed section is a tracer span, so one run's
# measurement timeline can be dumped as a Perfetto trace if needed
TRACER = Tracer()

BIG = 1 << 20
SIZES = (64, 256) if QUICK else (256, 1024, 4096)
ROUNDS = 3 if QUICK else 5
WARMUP = 1
POLICY = "flude"
MESH_DEVICES = (1, 2, 4, 8)
N_MESH = 256 if QUICK else 4096


def _setup(n):
    sim = SimConfig(num_clients=n, rounds=WARMUP + ROUNDS, seed=7,
                    local_steps=2, batch_size=16)
    fl = FLConfig(num_clients=n, clients_per_round=max(n // 8, 8))
    data = federated_classification(n, seed=8, n_per_client=16)
    return sim, fl, data


def host_loop(data, sim, fl, n_rounds, fleet):
    """Per-round host round-trip of the server step (the old loop).

    FLUDE planning/bookkeeping run eagerly (op-by-op, as the dict-era
    runner did) rather than through the policy's jitted plan path.
    ``fleet`` is constructed by the caller so every variant at a sweep
    point runs on the same identically-seeded draw stream."""
    N = fl.num_clients
    hints = jnp.asarray(fleet.battery * fleet.stability, jnp.float32)
    fstate = core.init_state(fl)
    trainer = make_trainer(sim, data)
    acc_fn = jax.jit(CLF.clf_accuracy)
    params = CLF.init_classifier(jax.random.key(sim.seed + 1),
                                 dim=data.x.shape[-1],
                                 num_classes=data.num_classes)
    caches = core.init_caches(params, N)
    cache_every = jnp.asarray(np.clip(np.round(core.adaptive_cache_interval(
        2.0, fleet.battery, fleet.stability)), 1, 4).astype(np.int32))
    n_samples = np.full(N, data.x.shape[1], np.float32)
    test_x = jnp.asarray(data.test_x)
    test_y = jnp.asarray(data.test_y)
    rng = jax.random.key(sim.seed)
    acc = float("nan")

    def _round(rnd):
        nonlocal rng, fstate, caches, params, acc
        rng, k_sel = jax.random.split(rng)
        online = fleet.online_mask()
        p = core.plan_round(fstate, caches, jnp.asarray(online), fl, k_sel,
                            explore_hints=hints)
        selected = np.asarray(p.selected)
        distribute = np.asarray(p.distribute)
        resume = np.asarray(p.resume)

        progress_h = np.asarray(caches.progress)
        stamp_h = np.asarray(caches.round_stamp)
        prior_steps = np.round(progress_h * sim.local_steps).astype(np.int32)
        steps_needed = np.where(resume,
                                np.maximum(sim.local_steps - prior_steps, 1),
                                sim.local_steps).astype(np.int32)
        steps_needed = np.where(selected, steps_needed, 0)
        fail = fleet.failure_draw(steps_needed / max(sim.local_steps, 1))
        fail &= selected
        stop = np.where(fail, fleet.failure_step(steps_needed), BIG)

        final, cache_p, cached_steps, _ = trainer(
            params, caches, jnp.asarray(resume), jnp.asarray(steps_needed),
            jnp.asarray(stop), cache_every)

        success = selected & ~fail & (steps_needed > 0)
        completed = np.minimum(steps_needed, stop)
        times = fleet.round_times(steps_needed, distribute, completed,
                                  success)
        quorum = int(np.ceil(min(float(p.quorum), float(selected.sum()))))
        finite = np.sort(times[np.isfinite(times)])
        if finite.size >= quorum and quorum > 0:
            t_cut = min(finite[quorum - 1], sim.round_deadline)
        else:
            t_cut = sim.round_deadline
        received = success & (times <= t_cut)
        fstate = core.update_after_round(fstate, p, jnp.asarray(received),
                                         fl)

        # --- host-side server step: pull, numpy aggregate, push --------
        final_h = jax.device_get(final)
        cache_h = jax.device_get(cache_p)
        cached_h = np.asarray(cached_steps)
        base_stale = np.where(resume & (stamp_h >= 0),
                              np.maximum(rnd - stamp_h, 0), 0)
        w = received * n_samples / (1.0 + base_stale)
        total = max(w.sum(), 1e-30)
        params_h = jax.device_get(params)
        if w.sum() > 0:
            wv = (w / total).astype(np.float32)
            params_h = jax.tree.map(
                lambda c, g: (c.astype(np.float32)
                              * wv.reshape((-1,) + (1,) * (c.ndim - 1))
                              ).sum(0).astype(g.dtype), final_h, params_h)
        total_cached = np.where(resume, prior_steps, 0) + cached_h
        write = selected & fail & (total_cached > 0)
        base_round = np.where(resume & (stamp_h >= 0), stamp_h, rnd)
        cache_leaves = jax.tree.map(
            lambda old, new: np.where(
                write.reshape((-1,) + (1,) * (old.ndim - 1)), new, old),
            jax.device_get(caches.params), cache_h)
        progress_h = np.where(write, total_cached / max(sim.local_steps, 1),
                              progress_h)
        stamp_h = np.where(write, base_round, stamp_h).astype(np.int32)
        progress_h = np.where(received, 0.0, progress_h).astype(np.float32)
        stamp_h = np.where(received, -1, stamp_h).astype(np.int32)
        params = jax.device_put(params_h)
        caches = core.ClientCaches(
            jax.tree.map(jnp.asarray, cache_leaves),
            jnp.asarray(progress_h), jnp.asarray(stamp_h))
        # per-round eval (the old loop's default)
        acc = float(acc_fn(params, test_x, test_y))

    for rnd in range(WARMUP):
        _round(rnd)
    with TRACER.span("bench_host_loop", n=N) as sp:
        for rnd in range(WARMUP, n_rounds):
            _round(rnd)
    return acc, sp.seconds


def engine_loop(data, sim, fl, n_rounds, fleet):
    # one shared fleet per sweep point: warmup advances the same stream
    # the measured rounds continue, exactly like the host loop — the A/B
    # variants see identical draws
    engine = FleetEngine(data, sim, fl, fleet=fleet)
    engine.run(POLICY, rounds=WARMUP, diagnostics=False)    # jit warmup
    with TRACER.span("bench_engine_loop", n=fl.num_clients) as sp:
        h = engine.run(POLICY, rounds=n_rounds - WARMUP,
                       eval_every=n_rounds, diagnostics=False)
    return h.acc[-1], sp.seconds


def run():
    # read-merge so a previously recorded --mesh sweep survives a plain
    # engine re-run (run_mesh() merges the other way for the same reason)
    path = os.path.join(RESULTS, "BENCH_engine.json")
    record = {}
    if os.path.exists(path):
        with open(path) as f:
            record = json.load(f)
    record.update(
        {"policy": POLICY, "rounds": ROUNDS,
         "note": "host loop evals every round (old default), engine "
                 "evals at boundaries; accs are sanity values, not "
                 "an equivalence check (see tests/test_policy_api.py)",
         "sizes": {}})
    for n in SIZES:
        sim, fl, data = _setup(n)
        # identically-seeded fleet per variant: both loops consume the
        # same warmup+measured draw sequence (A/B on one stream)
        acc_e, dt_e = engine_loop(data, sim, fl, WARMUP + ROUNDS,
                                  Fleet(sim))
        acc_h, dt_h = host_loop(data, sim, fl, WARMUP + ROUNDS,
                                Fleet(sim))
        rps_e = ROUNDS / dt_e
        rps_h = ROUNDS / dt_h
        record["sizes"][str(n)] = {
            "engine_rounds_per_sec": rps_e,
            "host_rounds_per_sec": rps_h,
            "speedup": rps_e / rps_h,
            "engine_final_acc": acc_e, "host_final_acc": acc_h,
        }
        emit(f"engine_n{n}", dt_e * 1e6 / ROUNDS,
             f"engine_rps={rps_e:.2f};host_rps={rps_h:.2f};"
             f"speedup={rps_e / rps_h:.2f}x")
    os.makedirs(RESULTS, exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    emit("engine_summary", 0.0,
         f"max_speedup={max(v['speedup'] for v in record['sizes'].values()):.2f}x",
         record=None)
    return record


def mesh_child(k: int):
    """One forced-host-device-count measurement (runs in a subprocess).

    The parent sets ``--xla_force_host_platform_device_count=k`` through
    ``repro.launch.mesh.force_host_platform_device_count`` *before* this
    module (and therefore jax) is imported.
    """
    sim, fl, data = _setup(N_MESH)
    out = {"devices": k, "n": N_MESH, "policy": POLICY,
           "rounds": ROUNDS, "donate": {}}
    for donate in (False, True):
        fl2 = dataclasses.replace(fl,
                                  mesh_shape=(k,) if k > 1 else None,
                                  donate_buffers=donate)
        # one identically-seeded fleet per variant: donate on/off compare
        # on the same draw stream
        engine = FleetEngine(data, sim, fl2, fleet=Fleet(sim))
        engine.run(POLICY, rounds=WARMUP, diagnostics=False)   # jit warmup
        with TRACER.span("bench_mesh", devices=k, donate=donate) as sp:
            engine.run(POLICY, rounds=ROUNDS, eval_every=ROUNDS,
                       diagnostics=False)
        out["donate"]["on" if donate else "off"] = {
            "rounds_per_sec": ROUNDS / sp.seconds,
            **engine.server_step_memory(uses_cache=True)}
    print(json.dumps(out))


def run_mesh():
    """CPU-only sweep: each device count runs in a child process with
    forced host devices.  On an accelerator host the children would
    compete with this process for the chip, so it refuses there."""
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            f"engine_mesh sweeps forced CPU host devices in child "
            f"processes; on a {jax.default_backend()} backend a child "
            f"cannot reach the chip this process holds.  Run it with "
            f"JAX_PLATFORMS=cpu; the on-chip client mesh runs in one "
            f"process (python chip_smoke.py --chips 4)")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    sweep = []
    for k in MESH_DEVICES:
        code = ("from repro.launch.mesh import "
                "force_host_platform_device_count as F; "
                f"F({k}); "
                "from benchmarks.bench_engine import mesh_child; "
                f"mesh_child({k})")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             cwd=root, capture_output=True, text=True,
                             timeout=3600)
        if out.returncode != 0:
            raise RuntimeError(f"mesh child k={k} failed:\n"
                               + out.stderr[-3000:])
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        sweep.append(rec)
        on, off = rec["donate"]["on"], rec["donate"]["off"]
        emit(f"engine_mesh{k}_cpu", 1e6 / max(on["rounds_per_sec"], 1e-9),
             f"rps_on={on['rounds_per_sec']:.2f};"
             f"rps_off={off['rounds_per_sec']:.2f};"
             f"peak_on={on['peak_live_bytes']};"
             f"peak_off={off['peak_live_bytes']}")
    path = os.path.join(RESULTS, "BENCH_engine.json")
    record = {}
    if os.path.exists(path):
        with open(path) as f:
            record = json.load(f)
    record["mesh"] = {
        "policy": POLICY, "n": N_MESH, "rounds": ROUNDS, "platform": "cpu",
        "note": "forced CPU host devices, not a chip measurement; donate "
                "on/off compared per device count.  peak_live_bytes = "
                "argument+output+temp-alias of the compiled fused server "
                "step (donation aliases the "
                "previous global model + caches into the outputs)",
        "sweep": sweep}
    os.makedirs(RESULTS, exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    return record


PIPE_DEPTHS = (1, 2, 4)
PIPE_ROUNDS = 4 if QUICK else 10
PIPE_EVAL_EVERY = 10
PIPE_REPS = 1 if QUICK else 3


def run_pipeline():
    """Pipelined device round loop: rounds/sec at pipeline_depth 1/2/4.

    Depth 1 is the PR-4 device loop's scheduling (every round's
    bookkeeping resolves before the next round is planned); depth d
    keeps d-1 rounds of bookkeeping in flight, so round k+1's fused
    trainer + server step dispatch while round k executes.  Same policy,
    fleet, dynamics and eval cadence per depth — trajectories are
    bit-identical (tier-1 parity tests); only host/device overlap
    changes.  The measurement interleaves PIPE_REPS repetitions of every
    depth on pre-compiled engines and keeps each depth's best rep, so
    slow machine-load drift cannot masquerade as (or hide) a speedup.
    Merged into BENCH_engine.json under "pipeline"."""
    n = N_MESH
    sim, fl, data = _setup(n)
    sim = dataclasses.replace(sim, rounds=WARMUP + PIPE_ROUNDS * PIPE_REPS)
    engines = {}
    for depth in PIPE_DEPTHS:
        fl2 = dataclasses.replace(fl, dynamics="bernoulli",
                                  pipeline_depth=depth)
        engine = FleetEngine(data, sim, fl2, fleet=Fleet(sim))
        engine.run(POLICY, rounds=WARMUP, diagnostics=False)  # jit warmup
        engines[depth] = engine
    reps = {depth: [] for depth in PIPE_DEPTHS}
    acc = {}
    for _ in range(PIPE_REPS):
        for depth in PIPE_DEPTHS:
            with TRACER.span("bench_pipeline", depth=depth) as sp:
                h = engines[depth].run(POLICY, rounds=PIPE_ROUNDS,
                                       eval_every=PIPE_EVAL_EVERY,
                                       diagnostics=False)
            reps[depth].append(PIPE_ROUNDS / sp.seconds)
            acc[depth] = h.acc[-1]
    depths = {}
    for depth in PIPE_DEPTHS:
        best = max(reps[depth])
        depths[str(depth)] = {"rounds_per_sec": best,
                              "reps_rounds_per_sec": reps[depth],
                              "final_acc": acc[depth]}
        emit(f"engine_pipe_d{depth}", 1e6 / best,
             f"n={n};rps={best:.3f}")
    speedup = depths["2"]["rounds_per_sec"] / depths["1"]["rounds_per_sec"]
    path = os.path.join(RESULTS, "BENCH_engine.json")
    record = {}
    if os.path.exists(path):
        with open(path) as f:
            record = json.load(f)
    record["pipeline"] = {
        "policy": POLICY, "n": n, "rounds": PIPE_ROUNDS,
        "reps": PIPE_REPS, "eval_every": PIPE_EVAL_EVERY,
        "dynamics": "bernoulli",
        "depth2_over_depth1_speedup": speedup,
        "note": "depth 1 = the PR-4 device loop's per-round host sync; "
                "depth d defers History readback so up to d-1 rounds "
                "stay in flight.  Trajectories are depth-invariant "
                "(tests/test_round_close.py, tests/test_fleet_dynamics"
                ".py).  The speedup is pure host/device overlap: it is "
                "bounded by the host-side gap pipelining removes, which "
                "on the 2-core CPU recording container is ~5% of a "
                "round (fully-async dispatch upper bound measured "
                "1.06x) and within that machine's load noise — "
                "accelerator-backed hosts, where a round's host gap is "
                "a much larger fraction, are where depth > 1 pays",
        "depths": depths}
    os.makedirs(RESULTS, exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    emit("engine_pipe_summary", 0.0,
         f"depth2_over_depth1={speedup:.3f}x", record=None)
    return record


COHORT_XS = (128, 512, 1024, None)            # None = full scan
# 30-round reps amortize the per-rep run boundary (fresh policy state +
# fleet cache reset, which is O(N) and so asymmetric across fleet sizes)
# down to noise; shorter reps understate the compact path's steady state
COHORT_ROUNDS = 4 if QUICK else 30
# 5 reps: the recording container shares cores, and per-rep throughput
# swings ~10% with co-tenant load — best-of-5 pins each engine's
# quiet-machine rate where best-of-3 still carries rep lottery
COHORT_REPS = 1 if QUICK else 5
PAIR_EXTRA_REPS = 0 if QUICK else 10
N_SMOKE = 20_000 if QUICK else 1_000_000
X_SMOKE = 512
SMOKE_ROUNDS = 3


def _vec_classification(n, *, num_classes=2, dim=4, n_per_client=2,
                        n_test=256, seed=0):
    """Vectorized tiny-task synthesis for the million-client smoke —
    ``federated_classification``'s per-client python loop is O(N) host
    work that would dwarf the measurement at N=1M."""
    from repro.data.synthetic import FederatedClassification
    rng = np.random.RandomState(seed)
    centers = (rng.randn(num_classes, dim) * 2.2).astype(np.float32)
    y = rng.randint(0, num_classes, (n, n_per_client))
    x = centers[y] + rng.randn(n, n_per_client, dim).astype(np.float32)
    ty = rng.randint(0, num_classes, n_test)
    tx = centers[ty] + rng.randn(n_test, dim).astype(np.float32)
    return FederatedClassification(
        x, y.astype(np.int32), tx, ty.astype(np.int32),
        y[:, :1].astype(np.int32), num_classes)


def run_cohort():
    """Compact-cohort round path: rounds/sec vs cohort width X at
    N=4096, plus the N=1M fleet-state smoke.

    The sweep holds the fleet fixed and varies ``FLConfig.cohort_size``
    (None = the full (N, ...) scan); ``clients_per_round`` is capped at
    X so every compact point satisfies the static selection bound.  The
    acceptance reference is a *full-scan* N=512 run: compact N=4096,
    X=512 vmaps the same 512 trainer rows, so its rate should meet or
    beat the small fleet's — that is what "round cost tracks the cohort,
    not the fleet" means.  Reps are interleaved on pre-compiled engines
    and each point keeps its best rep (machine-load drift cannot
    masquerade as a speedup).  Merged into BENCH_engine.json under
    "cohort"."""
    n = N_MESH
    sim, fl, data = _setup(n)
    sim = dataclasses.replace(
        sim, rounds=WARMUP + COHORT_ROUNDS * COHORT_REPS)
    sim512, fl512, data512 = _setup(512)
    sim512 = dataclasses.replace(
        sim512, rounds=WARMUP + COHORT_ROUNDS * COHORT_REPS)

    engines = {}
    # quick mode shrinks the fleet below the larger sweep points
    xs = tuple(x for x in COHORT_XS if x is None or x <= n)
    for x in xs:
        cpr = fl.clients_per_round if x is None \
            else min(x, fl.clients_per_round)
        # donation is the steady-state config the compact path is built
        # for: the cohort cache scatter updates the donated (N, D) buffer
        # in place (undonated, XLA copies the whole fleet cache per
        # round, which is O(N) work the cohort exists to avoid)
        fl2 = dataclasses.replace(fl, dynamics="bernoulli",
                                  cohort_size=x, clients_per_round=cpr,
                                  donate_buffers=True)
        engine = FleetEngine(data, sim, fl2, fleet=Fleet(sim))
        engine.run(POLICY, rounds=WARMUP, diagnostics=False)  # jit warmup
        engines["full" if x is None else str(x)] = (engine, cpr)
    ref_fl = dataclasses.replace(fl512, dynamics="bernoulli",
                                 donate_buffers=True)
    ref_engine = FleetEngine(data512, sim512, ref_fl, fleet=Fleet(sim512))
    ref_engine.run(POLICY, rounds=WARMUP, diagnostics=False)
    engines["full_n512"] = (ref_engine, ref_fl.clients_per_round)
    # run the acceptance-critical pair (compact X=512 vs the full-scan
    # N=512 reference — the "round cost tracks the cohort" comparison)
    # back-to-back within each rep: the slow full-fleet points otherwise
    # sit between them and transient machine load decorrelates exactly
    # the two rates being compared
    order = [k for k in ("128", "512", "full_n512", "1024", "full")
             if k in engines] + [k for k in engines
                                 if k not in ("128", "512", "full_n512",
                                              "1024", "full")]

    reps = {k: [] for k in engines}
    for _ in range(COHORT_REPS):
        for k in order:
            engine, _cpr = engines[k]
            with TRACER.span("bench_cohort", point=k) as sp:
                engine.run(POLICY, rounds=COHORT_ROUNDS,
                           eval_every=10 * COHORT_ROUNDS,
                           diagnostics=False)
            reps[k].append(COHORT_ROUNDS / sp.seconds)
    # the pair is ~1% of the sweep's wall-clock, so oversample it: the
    # two rates sit within a few percent of each other and a handful of
    # paired samples still leaves their median at the mercy of one bad
    # weather window
    for _ in range(PAIR_EXTRA_REPS if "512" in engines else 0):
        for k in ("512", "full_n512"):
            engine, _cpr = engines[k]
            with TRACER.span("bench_cohort_pair", point=k) as sp:
                engine.run(POLICY, rounds=COHORT_ROUNDS,
                           eval_every=10 * COHORT_ROUNDS,
                           diagnostics=False)
            reps[k].append(COHORT_ROUNDS / sp.seconds)
    sweep = {}
    for k, (engine, cpr) in engines.items():
        best = max(reps[k])
        sweep[k] = {"n": engine.fl_cfg.num_clients,
                    "cohort_size": engine.fl_cfg.cohort_size,
                    "clients_per_round": cpr,
                    "rounds_per_sec": best,
                    "reps_rounds_per_sec": reps[k],
                    "packed_rows":
                        engine.server_step_memory()["packed_rows"]}
        emit(f"engine_cohort_{k}", 1e6 / best,
             f"n={sweep[k]['n']};rps={best:.3f}")
    del engines, ref_engine

    # ---- N=1M fleet-state smoke: (N,) state is the only N-proportional
    # memory; the trainer, cut and aggregation all run on (X, ...) blocks
    smoke_sim = SimConfig(num_clients=N_SMOKE, rounds=WARMUP + SMOKE_ROUNDS,
                          local_steps=2, batch_size=2, seed=7,
                          model_hidden=4, model_depth=1)
    smoke_fl = FLConfig(num_clients=N_SMOKE, clients_per_round=X_SMOKE,
                        cohort_size=X_SMOKE, dynamics="bernoulli",
                        donate_buffers=True)
    smoke_data = _vec_classification(N_SMOKE, seed=8)
    engine = FleetEngine(smoke_data, smoke_sim, smoke_fl,
                         fleet=Fleet(smoke_sim))
    engine.run(POLICY, rounds=WARMUP, diagnostics=False)      # jit warmup
    with TRACER.span("bench_cohort_smoke", n=N_SMOKE) as sp:
        engine.run(POLICY, rounds=SMOKE_ROUNDS,
                   eval_every=10 * SMOKE_ROUNDS, diagnostics=False)
    dt = sp.seconds
    mem = engine.server_step_memory()
    live = int(sum(a.nbytes for a in jax.live_arrays()))
    smoke = {"n": N_SMOKE, "cohort_size": X_SMOKE,
             "rounds_run": SMOKE_ROUNDS,
             "rounds_per_sec": SMOKE_ROUNDS / dt,
             "model_hidden": smoke_sim.model_hidden,
             "model_depth": smoke_sim.model_depth,
             "server_step_peak_live_bytes": mem["peak_live_bytes"],
             "packed_rows": mem["packed_rows"],
             "packed_buffer_bytes": mem["packed_buffer_bytes"],
             "live_device_bytes": live}
    emit("engine_cohort_smoke", dt * 1e6 / SMOKE_ROUNDS,
         f"n={N_SMOKE};x={X_SMOKE};rps={SMOKE_ROUNDS / dt:.3f};"
         f"live_bytes={live}")

    path = os.path.join(RESULTS, "BENCH_engine.json")
    record = {}
    if os.path.exists(path):
        with open(path) as f:
            record = json.load(f)
    record["cohort"] = {
        "policy": POLICY, "rounds": COHORT_ROUNDS, "reps": COHORT_REPS,
        "pair_extra_reps": PAIR_EXTRA_REPS,
        "dynamics": "bernoulli", "donate_buffers": True,
        "note": "cohort_size=X gathers the selected cohort into dense "
                "(X, ...) blocks for train/cut/aggregate and scatters "
                "back to (N,) fleet state; full_n512 is the full-scan "
                "acceptance reference (same 512 trainer rows as the "
                "N=4096, X=512 compact point).  smoke: only the (N,) "
                "fleet state scales with N (tiny model via "
                "SimConfig.model_hidden/model_depth, vectorized data)",
        "sweep": sweep, "smoke": smoke}
    if "512" in sweep and "full_n512" in sweep:
        # the controlled acceptance contrast: rep i runs the two engines
        # back-to-back (see the order comment above), so the per-rep
        # ratio differences out the co-tenant load swing of that weather
        # window; the median over reps is the noise-robust "compact
        # round meets the same-cohort full-scan rate" statistic, where
        # a ratio of two independently-taken maxima still carries the
        # per-engine rep lottery (~+-8% swings on the shared container)
        paired = sorted(a / b for a, b in
                        zip(reps["512"], reps["full_n512"]))
        record["cohort"]["pair"] = {
            "paired_ratios": paired,
            "x512_over_full_n512_paired_median":
                paired[len(paired) // 2],
            "x512_over_full_n512_best_rates":
                sweep["512"]["rounds_per_sec"]
                / sweep["full_n512"]["rounds_per_sec"]}
    os.makedirs(RESULTS, exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    if "512" in sweep:
        pair = record["cohort"]["pair"]
        emit("engine_cohort_summary", 0.0,
             f"x512_over_full_n512_paired_median="
             f"{pair['x512_over_full_n512_paired_median']:.3f}x;"
             f"best_rates="
             f"{pair['x512_over_full_n512_best_rates']:.3f}x",
             record=None)
    return record


OFFLOAD_XS = (128, 512)
OFFLOAD_MODES = (None, "host", "discard")
OFFLOAD_ROUNDS = COHORT_ROUNDS
OFFLOAD_REPS = COHORT_REPS
OFFLOAD_STALENESS = 8          # discard bound, in rounds
STATS_ROUNDS = 3               # transfer-counter probe after timing


def run_offload():
    """C3 cache residency: resident (N, D) pytree vs the host-offloaded
    store ("host") vs the staleness-bounded store ("discard"),
    rounds/sec at N=4096, X in {128, 512}.

    The three residency modes of one cohort width run back-to-back
    within each rep, so the host/resident ratio is paired against the
    same machine-load window; each point keeps its best rep.  After
    timing, each offload engine reruns a short probe with the transfer
    counters reset to record the per-round async-copy footprint (the
    streaming contract: zero synchronous round-blocking copies).  The
    N=1M smoke reruns the fleet-state scaling check with the *default*
    full-size model (hidden=128, depth=2) — resident C3 state for that
    model is ~70 GB at N=1M, so the host store is what makes the run
    fit; the recorded residency split shows device cache bytes tracking
    X, not N.  Merged into BENCH_engine.json under "offload"."""
    n = N_MESH
    sim, fl, data = _setup(n)
    sim = dataclasses.replace(
        sim, rounds=WARMUP + OFFLOAD_ROUNDS * OFFLOAD_REPS)

    engines = {}
    for x in (x for x in OFFLOAD_XS if x <= n):
        for mode in OFFLOAD_MODES:
            fl2 = dataclasses.replace(
                fl, dynamics="bernoulli", cohort_size=x,
                clients_per_round=min(x, fl.clients_per_round),
                donate_buffers=True, cache_offload=mode,
                cache_staleness_bound=(
                    OFFLOAD_STALENESS if mode == "discard"
                    else fl.cache_staleness_bound))
            engine = FleetEngine(data, sim, fl2, fleet=Fleet(sim))
            engine.run(POLICY, rounds=WARMUP, diagnostics=False)  # warmup
            engines[f"x{x}_{mode or 'resident'}"] = engine

    reps = {k: [] for k in engines}
    for _ in range(OFFLOAD_REPS):
        for k, engine in engines.items():   # modes of one X stay paired
            with TRACER.span("bench_offload", point=k) as sp:
                engine.run(POLICY, rounds=OFFLOAD_ROUNDS,
                           eval_every=10 * OFFLOAD_ROUNDS,
                           diagnostics=False)
            reps[k].append(OFFLOAD_ROUNDS / sp.seconds)
    # oversample the acceptance-critical X=512 trio: the resident point
    # is compared against the prior cohort record's best-of-15 rate (5
    # reps + 10 pair-extra), so a best-of-5 here would understate it by
    # pure rep lottery on the shared container
    pair_keys = tuple(k for k in ("x512_resident", "x512_host",
                                  "x512_discard") if k in engines)
    for _ in range(PAIR_EXTRA_REPS if pair_keys else 0):
        for k in pair_keys:
            engine = engines[k]
            with TRACER.span("bench_offload_pair", point=k) as sp:
                engine.run(POLICY, rounds=OFFLOAD_ROUNDS,
                           eval_every=10 * OFFLOAD_ROUNDS,
                           diagnostics=False)
            reps[k].append(OFFLOAD_ROUNDS / sp.seconds)

    sweep = {}
    for k, engine in engines.items():
        point = {"n": n, "cohort_size": engine.fl_cfg.cohort_size,
                 "cache_offload": engine.fl_cfg.cache_offload,
                 "rounds_per_sec": max(reps[k]),
                 "reps_rounds_per_sec": reps[k]}
        if engine.fl_cfg.cache_offload is not None:
            engine.transfer_stats.reset()
            engine.run(POLICY, rounds=STATS_ROUNDS,
                       eval_every=10 * STATS_ROUNDS, diagnostics=False)
            point["transfer_stats_rounds"] = STATS_ROUNDS
            point["transfer_stats"] = engine.transfer_stats.snapshot()
        mem = engine.server_step_memory()
        point["cache_device_bytes"] = mem["cache_device_bytes"]
        point["cache_host_bytes"] = mem["cache_host_bytes"]
        sweep[k] = point
        emit(f"engine_offload_{k}", 1e6 / point["rounds_per_sec"],
             f"n={n};rps={point['rounds_per_sec']:.3f};"
             f"cache_dev={mem['cache_device_bytes']}")
    del engines

    # paired host/resident + discard/resident ratios per cohort width
    # (rep i of each mode ran back-to-back, so the per-rep ratio
    # differences out that weather window's co-tenant load)
    ratios = {}
    for x in OFFLOAD_XS:
        if f"x{x}_resident" not in sweep:
            continue
        for mode in ("host", "discard"):
            paired = sorted(a / b for a, b in
                            zip(reps[f"x{x}_{mode}"],
                                reps[f"x{x}_resident"]))
            ratios[f"x{x}_{mode}_over_resident"] = {
                "paired_median": paired[len(paired) // 2],
                "paired_ratios": paired,
                "best_rates": sweep[f"x{x}_{mode}"]["rounds_per_sec"]
                / sweep[f"x{x}_resident"]["rounds_per_sec"]}

    # ---- N=1M smoke, full-size default model: the host store carries
    # the fleet's C3 params, the device holds (X, D) blocks + (N,)
    # metadata only
    smoke_sim = SimConfig(num_clients=N_SMOKE,
                          rounds=WARMUP + SMOKE_ROUNDS,
                          local_steps=2, batch_size=2, seed=7)
    smoke_fl = FLConfig(num_clients=N_SMOKE, clients_per_round=X_SMOKE,
                        cohort_size=X_SMOKE, dynamics="bernoulli",
                        donate_buffers=True, cache_offload="host")
    engine = FleetEngine(_vec_classification(N_SMOKE, seed=8), smoke_sim,
                         smoke_fl, fleet=Fleet(smoke_sim))
    engine.run(POLICY, rounds=WARMUP, diagnostics=False)      # jit warmup
    engine.transfer_stats.reset()
    with TRACER.span("bench_offload_smoke", n=N_SMOKE) as sp:
        engine.run(POLICY, rounds=SMOKE_ROUNDS,
                   eval_every=10 * SMOKE_ROUNDS, diagnostics=False)
    dt = sp.seconds
    mem = engine.server_step_memory()
    live = int(sum(a.nbytes for a in jax.live_arrays()))
    row = engine.cache_store.row_bytes
    smoke = {"n": N_SMOKE, "cohort_size": X_SMOKE,
             "rounds_run": SMOKE_ROUNDS,
             "rounds_per_sec": SMOKE_ROUNDS / dt,
             "model_hidden": smoke_sim.model_hidden,
             "model_depth": smoke_sim.model_depth,
             "cache_offload": "host", "cache_row_bytes": row,
             "resident_equivalent_cache_bytes": N_SMOKE * row,
             "cache_device_bytes": mem["cache_device_bytes"],
             "cache_host_bytes": mem["cache_host_bytes"],
             "server_step_peak_live_bytes": mem["peak_live_bytes"],
             "live_device_bytes": live,
             "transfer_stats": engine.transfer_stats.snapshot()}
    emit("engine_offload_smoke", dt * 1e6 / SMOKE_ROUNDS,
         f"n={N_SMOKE};x={X_SMOKE};rps={SMOKE_ROUNDS / dt:.3f};"
         f"cache_dev={mem['cache_device_bytes']};"
         f"cache_host={mem['cache_host_bytes']};live_bytes={live}")

    path = os.path.join(RESULTS, "BENCH_engine.json")
    record = {}
    if os.path.exists(path):
        with open(path) as f:
            record = json.load(f)
    record["offload"] = {
        "policy": POLICY, "n": n, "rounds": OFFLOAD_ROUNDS,
        "reps": OFFLOAD_REPS, "dynamics": "bernoulli",
        "donate_buffers": True, "discard_staleness_bound":
            OFFLOAD_STALENESS,
        "note": "cache_offload='host' keeps only the (X, D) cohort "
                "cache slots on device and streams written slots to a "
                "sparse host store (async dispatch, double-buffered "
                "drain — transfer_stats.sync_copies counts the "
                "round-blocking copies the protocol never makes); "
                "'discard' additionally drops caches older than the "
                "staleness bound.  smoke: N=1M with the default "
                "full-size model — the resident-equivalent (N, D) "
                "cache pytree would be resident_equivalent_cache_bytes "
                "(~70 GB), the device footprint stays O(X*D)",
        "sweep": sweep, "ratios": ratios, "smoke_full_model": smoke}
    prior = record.get("cohort", {}).get("sweep", {}).get("512")
    if prior and "x512_resident" in sweep:
        # resident-path regression guard: same config as the cohort
        # sweep's X=512 point, recorded before the offload seam existed
        record["offload"]["resident_x512_over_prior_cohort_x512"] = \
            sweep["x512_resident"]["rounds_per_sec"] \
            / prior["rounds_per_sec"]
    os.makedirs(RESULTS, exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    if ratios:
        emit("engine_offload_summary", 0.0,
             ";".join(f"{k}={v['paired_median']:.3f}x"
                      for k, v in ratios.items()), record=None)
    return record


TEL_ROUNDS = 4 if QUICK else 10
TEL_REPS = 2 if QUICK else 3
TEL_JSONL = "telemetry_run.jsonl"
TEL_TRACE = "telemetry_trace.json"


def run_telemetry():
    """Telemetry overhead: rounds/sec with telemetry off vs "full".

    One pre-compiled engine (N=N_MESH full-scan, device dynamics); each
    rep runs the off and full variants back-to-back so the per-rep
    ratio differences out that window's machine load — the paired
    median is the overhead statistic, best-of rates are recorded too.
    The fused metrics dispatch rides the round ledger's readback (zero
    added host syncs), so the expected overhead is one extra small
    dispatch per round.  Also records a *real* run's artifacts —
    telemetry JSONL + Perfetto trace under results/benchmarks/ — and
    renders the report CLI against them.  Merged into BENCH_engine.json
    under "telemetry"."""
    from repro import obs
    from repro.obs import report as obs_report
    n = N_MESH
    sim, fl, data = _setup(n)
    sim = dataclasses.replace(
        sim, rounds=WARMUP + TEL_ROUNDS * (2 * TEL_REPS + 2))
    fl2 = dataclasses.replace(fl, dynamics="bernoulli")
    engine = FleetEngine(data, sim, fl2, fleet=Fleet(sim))
    engine.run(POLICY, rounds=WARMUP, diagnostics=False)  # round-path jit
    engine.run(POLICY, rounds=WARMUP, diagnostics=False,
               telemetry="full")                          # metrics jit

    reps_off, reps_full = [], []
    for _ in range(TEL_REPS):
        with TRACER.span("bench_tel_off") as sp:
            engine.run(POLICY, rounds=TEL_ROUNDS,
                       eval_every=10 * TEL_ROUNDS, diagnostics=False,
                       telemetry=False)
        reps_off.append(TEL_ROUNDS / sp.seconds)
        with TRACER.span("bench_tel_full") as sp:
            engine.run(POLICY, rounds=TEL_ROUNDS,
                       eval_every=10 * TEL_ROUNDS, diagnostics=False,
                       telemetry="full")
        reps_full.append(TEL_ROUNDS / sp.seconds)
    paired = sorted(off / full for off, full in zip(reps_off, reps_full))
    overhead_pct = (paired[len(paired) // 2] - 1.0) * 100.0

    # real-run artifacts: JSONL + Perfetto trace + report render
    os.makedirs(RESULTS, exist_ok=True)
    jsonl = os.path.join(RESULTS, TEL_JSONL)
    trace = os.path.join(RESULTS, TEL_TRACE)
    if os.path.exists(jsonl):
        os.remove(jsonl)
    tel = obs.Telemetry(level="full", jsonl=jsonl, trace=trace)
    engine.run(POLICY, rounds=TEL_ROUNDS, eval_every=2,
               diagnostics=False, telemetry=tel)
    tel.close()
    assert obs_report.main([jsonl]) == 0

    path = os.path.join(RESULTS, "BENCH_engine.json")
    record = {}
    if os.path.exists(path):
        with open(path) as f:
            record = json.load(f)
    record["telemetry"] = {
        "policy": POLICY, "n": n, "rounds": TEL_ROUNDS,
        "reps": TEL_REPS, "dynamics": "bernoulli",
        "rps_off": max(reps_off), "rps_full": max(reps_full),
        "reps_off": reps_off, "reps_full": reps_full,
        "paired_off_over_full": paired,
        "overhead_pct": overhead_pct,
        "jsonl": TEL_JSONL, "trace": TEL_TRACE,
        "note": "telemetry='full' fuses every registered metric into "
                "one extra jitted dispatch per round whose handles ride "
                "the pipelined round ledger readback (zero added host "
                "syncs); overhead_pct is the paired per-rep median of "
                "off/full - 1.  The JSONL/trace artifacts are a real "
                "instrumented run (report CLI renders the JSONL)",
    }
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    emit("engine_telemetry", 1e6 / max(reps_full),
         f"n={n};rps_off={max(reps_off):.3f};"
         f"rps_full={max(reps_full):.3f};"
         f"overhead_pct={overhead_pct:.2f}")
    return record


DYN_PATHS = (("host_rng", "bernoulli_host"),
             ("device_bernoulli", "bernoulli"),
             ("device_markov", "markov"))


def run_dynamics():
    """Host-RNG vs device-resident fleet-draw round paths, rounds/sec.

    ``bernoulli_host`` draws every round on the host (numpy RNG + three
    place_per_client uploads per round); the device processes produce the
    draw, workload, failure and timing model in jitted dispatches with no
    per-round host→device hand-off.  Same policy, same fleet size —
    merged into BENCH_engine.json under "dynamics"."""
    n = N_MESH
    sim, fl, data = _setup(n)
    paths = {}
    for label, dyn in DYN_PATHS:
        fl2 = dataclasses.replace(fl, dynamics=dyn)
        engine = FleetEngine(data, sim, fl2, fleet=Fleet(sim))
        engine.run(POLICY, rounds=WARMUP, diagnostics=False)  # jit warmup
        with TRACER.span("bench_dynamics", path=label) as sp:
            h = engine.run(POLICY, rounds=ROUNDS, eval_every=ROUNDS,
                           diagnostics=False)
        dt = sp.seconds
        paths[label] = {"dynamics": dyn, "rounds_per_sec": ROUNDS / dt,
                        "final_acc": h.acc[-1]}
        emit(f"engine_dyn_{label}", dt * 1e6 / ROUNDS,
             f"n={n};rps={ROUNDS / dt:.2f}")
    speedup = paths["device_bernoulli"]["rounds_per_sec"] \
        / paths["host_rng"]["rounds_per_sec"]
    path = os.path.join(RESULTS, "BENCH_engine.json")
    record = {}
    if os.path.exists(path):
        with open(path) as f:
            record = json.load(f)
    record["dynamics"] = {
        "policy": POLICY, "n": n, "rounds": ROUNDS,
        "device_over_host_speedup": speedup,
        "note": "host_rng draws availability/failures on host numpy and "
                "uploads (N,) masks per round; device paths produce the "
                "draw + workload + timing on device (repro.fleet), no "
                "per-round place_per_client",
        "paths": paths}
    os.makedirs(RESULTS, exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    emit("engine_dyn_summary", 0.0,
         f"device_over_host={speedup:.2f}x", record=None)
    return record


if __name__ == "__main__":
    if "--mesh" in sys.argv[1:]:
        run_mesh()
    elif "--dynamics" in sys.argv[1:]:
        run_dynamics()
    elif "--pipeline" in sys.argv[1:]:
        run_pipeline()
    elif "--cohort" in sys.argv[1:]:
        run_cohort()
    elif "--offload" in sys.argv[1:]:
        run_offload()
    elif "--telemetry" in sys.argv[1:]:
        run_telemetry()
    else:
        run()
