#!/usr/bin/env python3
"""Readings of the check's control and faults, for setting its limits.

    python3 fleetbench/control.py --workload xdevice-flude.diurnal \\
        --seeds 101 102 103

For each seed it runs the plain reference at the cell's own size as
the configuration states it (float32) and, put in the program's place
and judged by the same comparison:

* ``control``: the reference computed in bfloat16, the precision below
  the configuration's float32;
* ``half_batch``: the reference with the second half of each round's
  received clients left out of the aggregate, the mean taken over the
  rest.

A state left unchanged reads 1 on ``update_gap`` and ``change_gap`` by
construction and needs no run.  One JSON line per seed and variant goes
to standard output.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def as_run(ref: dict, seed: int) -> dict:
    """A reference's outputs in the shape the check reads of a run."""
    import numpy as np
    from fleetbench.harness import sample_ids
    stamp = ref["stamp"]
    store = {int(c): int(s) for c, s in zip(np.flatnonzero(stamp >= 0),
                                             stamp[stamp >= 0])}
    ids = sorted(ref["cache_after"])
    return dict(ref, theta1=ref["globals"][0], theta_k=ref["globals"][-1],
                store=store, cache_ids=ids, cache_after={
                    c: ref["cache_after"][c] for c in sample_ids(ids, seed)})


def readings(cell, seed: int, variants=("control", "half_batch")) -> list:
    import jax.numpy as jnp
    from fleetbench import checks, harness, reference

    spec = harness.spec_of(cell)
    rounds = spec["model_rounds"]
    data = spec["model_code"].make_data(seed, spec["data"])
    ref = reference.simulate(spec, data, seed, rounds,
                             numeric_rounds=rounds)
    out = []
    for v in variants:
        t0 = time.perf_counter()
        kw = {"dtype": jnp.bfloat16} if v == "control" \
            else {"fault": v}
        got = as_run(reference.simulate(spec, data, seed, rounds,
                                        numeric_rounds=rounds, **kw), seed)
        nums = checks.compare(got, ref, cell.limits)
        out.append({"workload": cell.name, "seed": seed, "variant": v,
                    "seconds": time.perf_counter() - t0,
                    "numbers": {k: x for k, (x, _) in nums.items()},
                    "fails": not checks.passed(nums)})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from fleetbench import harness
    cell = harness.resolve(ROOT, args.workload)
    for seed in args.seeds:
        for row in readings(cell, seed):
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
