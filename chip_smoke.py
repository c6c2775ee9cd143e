#!/usr/bin/env python3
"""On-chip smoke test of the FleetEngine round path.

Drives ``FleetEngine(...).run(policy)`` on a TPU with the engine's real
classifier at its default widths (dim 32, hidden 128, depth 2: packed
D = 22,026) and data made in-process from ``--seed``.  Every phase runs
the same fleet twice, once per aggregation path, and holds the Pallas
run to its reference:

* kernels: ``fed_agg_packed`` and ``geometric_median`` with
  ``impl="pallas"`` against float64 numpy oracles on a (512, D) buffer;
* A, the W1-shaped fleet: flude over N = 65,536 clients, 512 per round,
  ``diurnal`` sessions, host-offloaded C3 caches, donated buffers,
  ``pipeline_depth=2``;
* B, robust aggregation: the same fleet under ``sign-flip-20`` with
  ``agg_rule="geometric_median"``.

A and B each run with ``agg_impl="pallas"`` and ``agg_impl="xla"`` on the
same seed: 2 warm-up rounds, then 5 rounds.  ``selected``, ``received``,
``wall_clock`` and ``comm_mb`` must be equal, final accuracies agree to
1e-5, the Pallas server step must hold ``tpu_custom_call`` (the XLA one
none) and the cache stream must make no synchronous copy.

``--chips 4`` runs only the 4-chip client mesh: mifa at N = X = 4096 on
``mesh_shape=(4,)`` against the same run on one device.

    python chip_smoke.py              # kernels, A and B on one chip
    python chip_smoke.py --chips 4    # the client mesh on four chips

Everything but the last line is a report; rounds/s is a smoke figure,
not a benchmark.  The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``,
printed only when every phase passed.  Without a TPU the script exits
non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_FLEET = 65_536          # W1 fleet at the size one chip holds with data
COHORT = 512              # clients_per_round = cohort_size
N_MESH = 4096             # mifa select-all fleet on the client mesh
WARMUP, ROUNDS = 2, 5
ACC_TOL = 1e-5


def log(msg: str) -> None:
    print(msg, flush=True)


def run_engine(data, sim, fl, policy: str, *, label: str):
    """Warm up, then time ``ROUNDS`` rounds; returns (engine, hist)."""
    import jax
    from repro.fl import FleetEngine

    engine = FleetEngine(data, sim, fl)
    t0 = time.perf_counter()
    hist = engine.run(policy, rounds=WARMUP, diagnostics=False)
    jax.block_until_ready(hist.final_params)
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    hist = engine.run(policy, rounds=ROUNDS, diagnostics=False)
    jax.block_until_ready(hist.final_params)
    run_s = time.perf_counter() - t0
    log(f"[{label}] warm-up {warm_s:.2f} s (compile + {WARMUP} rounds, "
        f"~{warm_s - WARMUP * run_s / ROUNDS:.2f} s compile); "
        f"{ROUNDS} rounds {run_s:.3f} s = {ROUNDS / run_s:.3f} rounds/s "
        f"(smoke figure, not a benchmark)")
    log(f"[{label}] acc={hist.acc} selected={hist.selected} "
        f"received={hist.received}")
    check_outputs(engine, hist, label)
    return engine, hist


def check_outputs(engine, hist, label: str) -> None:
    """Finite final model of the template's shapes; sane History rows."""
    import jax
    import numpy as np

    for got, want in zip(jax.tree.leaves(hist.final_params),
                         jax.tree.leaves(engine._template)):
        assert got.shape == want.shape, (label, got.shape, want.shape)
        assert np.isfinite(np.asarray(got)).all(), \
            f"[{label}] non-finite final model"
    assert len(hist.acc) == ROUNDS, (label, len(hist.acc))
    assert all(0.0 <= a <= 1.0 for a in hist.acc), (label, hist.acc)
    assert all(0 <= r <= s for r, s in zip(hist.received, hist.selected)), \
        (label, hist.received, hist.selected)


def compare(ref, got, label: str, *, exact_floats: bool) -> None:
    """Integer History fields equal, final accuracy within ``ACC_TOL``.
    ``exact_floats`` also holds wall clock and comm to equality."""
    import numpy as np

    assert got.selected == ref.selected, (label, got.selected, ref.selected)
    assert got.received == ref.received, (label, got.received, ref.received)
    dt = float(np.max(np.abs(np.subtract(got.wall_clock, ref.wall_clock))))
    dc = float(np.max(np.abs(np.subtract(got.comm_mb, ref.comm_mb))))
    da = np.abs(np.subtract(got.acc, ref.acc))
    log(f"[{label}] max |d wall_clock|={dt} max |d comm_mb|={dc} "
        f"max |d acc| over rounds={float(da.max())} "
        f"final |d acc|={float(da[-1])}")
    if exact_floats:
        assert got.wall_clock == ref.wall_clock, label
        assert got.comm_mb == ref.comm_mb, label
    assert da[-1] <= ACC_TOL, (label, got.acc, ref.acc)


def memory_stat(device, key: str = "peak_bytes_in_use") -> int:
    return int(device.memory_stats()[key])


def phase_kernels(seed: int) -> None:
    """Both Pallas kernels against float64 numpy oracles at (512, D)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.fed_agg.ops import fed_agg_packed
    from repro.kernels.robust_agg.ops import geometric_median
    from repro.kernels.robust_agg.ref import geometric_median_ref

    rng = np.random.RandomState(seed)
    u = rng.randn(COHORT, 22_026).astype(np.float32)
    w = rng.rand(COHORT).astype(np.float32)
    w[::7] = 0.0                                   # clients not received
    t0 = time.perf_counter()
    agg = jax.jit(lambda u, w: fed_agg_packed(u, w, impl="pallas"))
    got = np.asarray(agg(jnp.asarray(u), jnp.asarray(w)))
    want = w.astype(np.float64) @ u.astype(np.float64)
    err = float(np.max(np.abs(got - want)))
    log(f"[kernels] fed_agg (512, 22026) max abs err {err:.3e} "
        f"({time.perf_counter() - t0:.2f} s incl. compile)")
    assert err <= 1e-3, err
    t0 = time.perf_counter()
    gm = jax.jit(lambda u, w: geometric_median(u, w, impl="pallas"))
    got = np.asarray(gm(jnp.asarray(u), jnp.asarray(w)))
    want = geometric_median_ref(u, w)
    err = float(np.max(np.abs(got - want)))
    log(f"[kernels] geometric_median (512, 22026) max abs err {err:.3e} "
        f"({time.perf_counter() - t0:.2f} s incl. compile)")
    assert err <= 1e-4, err


def phase_pair(name: str, data, sim, fl, policy: str, *, device,
               impls=("pallas", "xla")):
    """Run ``fl`` once per ``agg_impl``; the first is checked against the
    second (the XLA control).  Only ``"pallas"`` lowers to a TPU kernel."""
    hists = {}
    for impl in impls:
        label = f"{name}/{impl}"
        engine, hist = run_engine(
            data, sim, dataclasses.replace(fl, agg_impl=impl), policy,
            label=label)
        stats = engine.transfer_stats.snapshot()
        log(f"[{label}] transfer_stats={stats}")
        assert stats["sync_copies"] == 0, (label, stats)
        has = "tpu_custom_call" in engine.compiled_server_step().as_text()
        log(f"[{label}] server step holds tpu_custom_call: {has}")
        assert has == (impl == "pallas"), (label, has)
        log(f"[{label}] peak_bytes_in_use={memory_stat(device)}")
        hists[impl] = hist
        del engine
        gc.collect()
    compare(hists[impls[1]], hists[impls[0]], f"{name} {impls[0]} vs "
            f"{impls[1]}", exact_floats=True)


def fleet_setup(n: int, cohort: int, seed: int):
    """The W1-shaped fleet (phase A's config; B derives from it)."""
    from repro.configs.base import FLConfig
    from repro.data.synthetic import federated_classification
    from repro.fl import SimConfig, apply_scenario

    t0 = time.perf_counter()
    data = federated_classification(n, seed=seed)
    log(f"[setup] federated_classification N={n}: x {data.x.shape} "
        f"({data.x.nbytes / 1e9:.3f} GB) in {time.perf_counter() - t0:.1f} s")
    sim = SimConfig(num_clients=n, rounds=ROUNDS, seed=seed)
    fl = apply_scenario(
        FLConfig(num_clients=n, clients_per_round=cohort,
                 cohort_size=cohort, cache_offload="host",
                 donate_buffers=True, pipeline_depth=2),
        "diurnal")
    return data, sim, fl


def robust_config(fl):
    """Phase B: the same fleet under 20% sign-flip, geometric median."""
    from repro.fl import apply_scenario
    return dataclasses.replace(apply_scenario(fl, "sign-flip-20"),
                               agg_rule="geometric_median")


def one_chip(seed: int, device) -> None:
    phase_kernels(seed)
    data, sim, fl = fleet_setup(N_FLEET, COHORT, seed)
    phase_pair("A", data, sim, fl, "flude", device=device)
    phase_pair("B", data, sim, robust_config(fl), "flude", device=device)


def mesh_phase(seed: int, devices, *, n: int = N_MESH,
               impl: str = "pallas") -> None:
    """mifa on the 4-device client mesh against one device."""
    import jax
    from repro.configs.base import FLConfig
    from repro.data.synthetic import federated_classification
    from repro.fl import SimConfig

    k = 4
    data = federated_classification(n, seed=seed)
    sim = SimConfig(num_clients=n, rounds=ROUNDS, seed=seed)
    base = FLConfig(num_clients=n, clients_per_round=n, cohort_size=n,
                    dynamics="bernoulli", agg_impl=impl,
                    donate_buffers=True)
    _, single = run_engine(data, sim, base, "mifa", label="mesh/1 device")
    gc.collect()
    engine, sharded = run_engine(
        data, sim, dataclasses.replace(base, mesh_shape=(k,)), "mifa",
        label=f"mesh/{k} devices")
    leaves = jax.tree.leaves(engine._last_caches)
    spans = {len(leaf.sharding.device_set) for leaf in leaves}
    log(f"[mesh] fleet cache leaves span {sorted(spans)} device(s)")
    assert spans == {k}, spans
    in_use = [memory_stat(d, "bytes_in_use") for d in devices[:k]]
    log(f"[mesh] bytes_in_use per device {in_use}")
    assert all(b > 0 for b in in_use), in_use
    compare(single, sharded, f"mesh {k} vs 1", exact_floats=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-chip client-mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no backend ({e})", file=sys.stderr)
        return 1
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform} "
              f"({dev.device_kind})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"device(s) visible", file=sys.stderr)
        return 1
    log(f"[device] {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        mesh_phase(args.seed, devices)
    else:
        one_chip(args.seed, dev)
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
