"""The comparison that decides ``correct``.

Each number is compared with a limit of its own (``limits/<cell>.json``):

* ``mismatches`` counts every exact disagreement between the run and the
  reference's fleet simulation: per round of the window the History's
  selected and received counts, cumulative comm and wall clock; the
  first rounds' selection and receive masks; after the window the cache
  metadata (progress, round stamp), the host cache store's rows and
  stamps, and FLUDE's participation counts.  Limit 0.
* ``loss_gap``: over the rounds the check follows (``model_rounds``),
  the largest relative gap of the round's mean local training loss over
  the selected clients.
* ``update_gap`` and ``change_gap``: by the worst leaf, the gap between
  the program's and the reference's norm of the global model's change
  after round 1 (the first aggregated update) and after the last round
  the check follows, as a share of the larger of that leaf's reference
  norm and the median leaf's.  Leaves whose reference update is under a
  thousandth of the median leaf's move by round-off alone and are left
  out.
* ``cache_gap``: the same worst-leaf measure over a sample, drawn from
  the seed, of the cache rows the store holds after those rounds
  (cached local models of interrupted clients); which clients hold a
  row is compared in full under ``mismatches``.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

ROUNDOFF = 1e-3


def split(vec: np.ndarray, sizes: List[int]) -> List[np.ndarray]:
    out, off = [], 0
    for n in sizes:
        out.append(vec[..., off:off + n])
        off += n
    return out


def leaf_norms(vec: np.ndarray, sizes: List[int]) -> np.ndarray:
    return np.array([float(np.linalg.norm(np.asarray(p, np.float64)))
                     for p in split(vec, sizes)])


def worst_leaf_gap(got: np.ndarray, ref: np.ndarray, sizes: List[int],
                   moved: Optional[np.ndarray] = None) -> float:
    """max over leaves |‖got‖ - ‖ref‖| / max(‖ref‖, median leaf ‖ref‖)."""
    g, r = leaf_norms(got, sizes), leaf_norms(ref, sizes)
    keep = np.ones(len(sizes), bool) if moved is None else moved
    if not keep.any():
        return 0.0
    scale = np.maximum(r, np.median(r[keep]))
    scale = np.where(scale > 0, scale, 1.0)
    return float(np.max(np.abs(g - r)[keep] / scale[keep]))


def count_mismatches(got: dict, ref: dict) -> int:
    n = 0
    for key in ("selected", "received", "comm_mb", "wall_clock"):
        a, b = got[key], ref[key]
        n += abs(len(a) - len(b))
        n += sum(1 for x, y in zip(a, b) if x != y)
    for g, r in zip(got["first"], ref["first"]):
        for key in ("selected", "received"):
            n += int(np.sum(np.asarray(g[key]) != np.asarray(r[key])))
    n += abs(len(got["first"]) - len(ref["first"]))
    n += int(np.sum(got["progress"] != ref["progress"]))
    n += int(np.sum(got["stamp"] != ref["stamp"]))
    if got.get("store") is not None:
        want = {int(c): int(s) for c, s in
                zip(np.flatnonzero(ref["stamp"] >= 0),
                    ref["stamp"][ref["stamp"] >= 0])}
        have = got["store"]
        n += len(set(have) ^ set(want))
        n += sum(1 for c in set(have) & set(want) if have[c] != want[c])
    if got.get("part_count") is not None and ref["part_count"] is not None:
        n += int(np.sum(got["part_count"] != ref["part_count"]))
    n += len(set(got.get("cache_ids", got["cache_after"]))
             ^ set(ref["cache_after"]))
    return n


def compare(got: dict, ref: dict, limits: dict) -> Dict[str, Tuple]:
    """{name: (value, limit)} for every number the limits file names."""
    sizes = ref["leaf_sizes"]
    theta0 = ref["theta0"]
    d1_ref = ref["globals"][0] - theta0
    moved = leaf_norms(d1_ref, sizes)
    moved = moved >= ROUNDOFF * np.median(moved)
    values = {"mismatches": float(count_mismatches(got, ref))}
    values["loss_gap"] = float(max(
        abs(a - b) / max(abs(b), 1e-30)
        for a, b in zip(got["losses"], ref["losses"])))
    values["update_gap"] = worst_leaf_gap(got["theta1"] - theta0, d1_ref,
                                          sizes, moved)
    values["change_gap"] = worst_leaf_gap(
        got["theta_k"] - theta0, ref["globals"][-1] - theta0, sizes, moved)
    common = sorted(set(got["cache_after"]) & set(ref["cache_after"]))
    if common:
        a = np.stack([got["cache_after"][c] for c in common])
        b = np.stack([ref["cache_after"][c] for c in common])
        values["cache_gap"] = worst_leaf_gap(a, b, sizes)
    out = {}
    for name, lim in limits["limits"].items():
        if name in values:
            out[name] = (values[name], float(lim))
    return out


def passed(numbers: Dict[str, Tuple]) -> bool:
    return all(v <= lim for v, lim in numbers.values())


def summary(numbers: Dict[str, Tuple]) -> dict:
    return {k: {"value": v, "limit": lim} for k, (v, lim) in numbers.items()}
