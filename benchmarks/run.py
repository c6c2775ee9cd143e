"""Benchmark harness: one bench per paper table/figure + kernels + roofline.

Usage:  PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
Output: ``name,us_per_call,derived`` CSV rows (also archived under
results/benchmarks/).
"""
import argparse
import os
import sys
import traceback
import types


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer rounds/settings per bench")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    if args.quick:
        os.environ["BENCH_QUICK"] = "1"

    from benchmarks import (bench_ablation_selector, bench_beyond,
                            bench_engine, bench_fig1, bench_fig2,
                            bench_fig5, bench_fig7, bench_fig8, bench_fig9,
                            bench_kernels, bench_robust, bench_roofline,
                            bench_server_step, bench_table1)
    benches = {
        "table1": bench_table1,
        "fig1": bench_fig1,
        "fig2": bench_fig2,
        "fig5": bench_fig5,
        "ablation_selector": bench_ablation_selector,
        "fig7": bench_fig7,
        "fig8": bench_fig8,
        "fig9": bench_fig9,
        "beyond_selection": bench_beyond,
        "kernels": bench_kernels,
        # robust aggregation rules vs Byzantine attack fractions
        "robust": bench_robust,
        "roofline": bench_roofline,
        "server_step": bench_server_step,
        "engine": bench_engine,
        # CPU-only client-mesh sweep (forced-host-device subprocesses, so
        # it works from this single-device parent; refuses on a TPU)
        "engine_mesh": types.SimpleNamespace(run=bench_engine.run_mesh),
        # host-RNG vs device-resident fleet-draw paths (repro.fleet)
        "engine_dynamics": types.SimpleNamespace(
            run=bench_engine.run_dynamics),
        # pipelined device round loop (pipeline_depth 1/2/4)
        "engine_pipeline": types.SimpleNamespace(
            run=bench_engine.run_pipeline),
        # compact-cohort round path (X sweep + N=1M fleet-state smoke)
        "engine_cohort": types.SimpleNamespace(
            run=bench_engine.run_cohort),
        # C3 cache residency (resident vs host vs discard + full-model
        # N=1M smoke)
        "engine_offload": types.SimpleNamespace(
            run=bench_engine.run_offload),
        # telemetry="full" overhead (paired off/full) + real JSONL/trace
        # artifacts rendered by the report CLI
        "engine_telemetry": types.SimpleNamespace(
            run=bench_engine.run_telemetry),
    }
    print("name,us_per_call,derived")
    failed = []
    for name, mod in benches.items():
        if args.only and name != args.only:
            continue
        try:
            mod.run()
        except Exception:
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
