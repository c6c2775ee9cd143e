#!/usr/bin/env python3
"""Run one cell of the FleetEngine benchmark on the chip.

    python3 fleetbench/run.py --workload xdevice-flude.diurnal \\
        --seed 7 --seconds 10 --trace 0

Run from the root of a checkout that holds ``BENCHMARK.json``,
``fleetbench/`` and the program under ``src/``.  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
with ``--trace 1`` a ``breakdown``, and last ``checked``: each number
the correctness check compared, with its limit.  The same numbers end
standard error.  With no TPU, or fewer chips than the cell asks for,
it exits non-zero and prints no result.  JAX's persistent compilation
cache lives in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parent.parent


def fail(msg: str, code: int = 2) -> int:
    print(f"fleetbench: {msg}", file=sys.stderr, flush=True)
    return code


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None,
                    help="directory for trace summaries (default "
                         "<checkout>/.fleetbench_out)")
    ap.add_argument("--keep-trace", action="store_true",
                    help="also keep the window's raw trace, gzipped")
    args = ap.parse_args(argv)

    if not (ROOT / "BENCHMARK.json").is_file():
        return fail(f"no BENCHMARK.json in {ROOT}")
    if not (ROOT / "src" / "repro" / "fl" / "engine.py").is_file():
        return fail(f"the program (src/repro) is not in {ROOT}")
    if not 0 <= args.seed < 2 ** 32:
        return fail(f"--seed must lie in [0, 2**32), got {args.seed}")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from fleetbench import harness

    try:
        cell = harness.resolve(ROOT, args.workload)
    except (KeyError, FileNotFoundError, AttributeError) as e:
        return fail(str(e))

    import jax
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        return fail(f"JAX found no backend ({e})", 1)
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU, JAX found {devices[0].platform}", 1)
    if len(devices) < cell.chips:
        return fail(f"{args.workload} needs {cell.chips} chip(s), "
                    f"{len(devices)} visible", 1)
    devices = devices[:cell.chips]
    out = Path(args.out) if args.out else ROOT / ".fleetbench_out"
    out = out / f"{args.workload}.{args.seed}.{args.trace}"
    out.mkdir(parents=True, exist_ok=True)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    log(f"[device] {devices[0].device_kind} x{len(devices)}; "
        f"jax {jax.__version__}")
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), out, T_START, log=log,
                              devices=devices,
                              keep_trace=args.keep_trace)
    for name, v in result["checked"].items():
        log(f"[check] {name} {v['value']!r} limit {v['limit']!r}")
    log(f"[check] correct {result['correct']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
