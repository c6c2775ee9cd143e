"""Bit-exact snapshots of the reference's model outputs on tiny cells.

``snapshot(ref, data)`` reduces a ``reference.simulate`` result and the
data it ran on to a JSON-able record: every array as the SHA-256 of its
bytes, its shape and an evenly strided sample of 64 of its values, the
losses and fleet columns as plain numbers.  Two records are equal
only where every array is equal bit for bit.
"""
from __future__ import annotations

import hashlib

import numpy as np

SAMPLE = 64
CELLS = [("xdevice-flude", "diurnal"), ("xdevice-flude-gm", "signflip20")]
SEED = 5
ROUNDS = 3


def digest(a) -> dict:
    a = np.ascontiguousarray(np.asarray(a))
    flat = a.reshape(-1)
    return {"dtype": str(a.dtype), "shape": list(a.shape),
            "sha256": hashlib.sha256(a.tobytes()).hexdigest(),
            "sample": flat[::max(1, flat.size // SAMPLE)][:SAMPLE].tolist()}


def snapshot(ref: dict, data) -> dict:
    f32 = lambda a: np.asarray(a, np.float32)
    return {
        "data": {k: digest(getattr(data, k))
                 for k in ("x", "y", "test_x", "test_y")},
        "theta0": digest(f32(ref["theta0"])),
        "globals": [digest(f32(g)) for g in ref["globals"]],
        "losses": [float(v) for v in ref["losses"]],
        "cache_after": {str(c): digest(f32(r))
                        for c, r in sorted(ref["cache_after"].items())},
        "leaf_sizes": [int(n) for n in ref["leaf_sizes"]],
        "selected": [int(v) for v in ref["selected"]],
        "received": [int(v) for v in ref["received"]],
        "wall_clock": [float(v) for v in ref["wall_clock"]],
    }
