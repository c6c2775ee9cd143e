"""Profiler trace of a window, and its reduction to per-layer numbers.

``traced(fn, out_dir)`` runs ``fn`` under ``jax.profiler`` inside a
host annotation that marks the window, reads the ``.xplane.pb`` back
with ``jax.profiler.ProfileData`` and reduces it:

* device busy time is the union of the intervals of the device's
  operations (the ``XLA Ops`` line of each ``/device:TPU:n`` plane),
  clipped to the window and averaged over the chips that ran any;
* device time per XLA module comes from the ``XLA Modules`` line, by
  the module's name (``jit_<function>``);
* kernel time is the summed duration of the operations whose name
  names the kernel;
* each long idle gap is named by the innermost host event (a Python
  frame or a runtime annotation) that spans its middle.

The reduction functions take plain lists of ``(name, start_ns,
duration_ns)`` so that tests can drive them with synthetic events.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import re
import shutil
from pathlib import Path
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

WINDOW = "fleetbench_window"
Event = Tuple[str, float, float]          # name, start_ns, duration_ns


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader sees."""
    trace: dict           # reduced trace (see ``reduce``)
    rounds: int           # rounds in the traced window
    window_s: float       # host-clock length of the traced window
    spec: dict            # the cell's configuration and traffic blocks
    device_kind: str
    counters: dict        # program counters read over the window


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------

def merge(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Sorted, disjoint union of [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    return sum(e - s for s, e in merge(
        (s, s + d) for _, s, d in clip(events, lo, hi)))


def gaps(events: Iterable[Event], lo: float, hi: float) -> List[Tuple]:
    """Idle [start, end) intervals of the window, longest first."""
    busy = merge((s, s + d) for _, s, d in clip(events, lo, hi))
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return sorted(out, key=lambda g: g[0] - g[1])


def total_by(events: Iterable[Event], pattern: str) -> float:
    """Summed duration (ns) of events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(d for n, _, d in events if rx.search(n))


def top(events: Iterable[Event], k: int = 10) -> List[Tuple[str, float]]:
    acc: Dict[str, float] = {}
    for n, _, d in events:
        acc[n] = acc.get(n, 0.0) + d
    return sorted(acc.items(), key=lambda kv: -kv[1])[:k]


def frames_at(host: Sequence[Event], times: Sequence[float]) -> List[str]:
    """For each time, the innermost Python frame of the host that spans
    it (``"no python frame"`` where none does)."""
    frames = [(n, s, d) for n, s, d in host if n.startswith("$")]
    if not frames:
        return ["no python frame"] * len(times)
    names = [n for n, _, _ in frames]
    st = np.array([s for _, s, _ in frames], np.float64)
    du = np.array([d for _, _, d in frames], np.float64)
    out = []
    for t in times:
        hit = np.flatnonzero((st <= t) & (st + du > t))
        out.append(names[hit[np.argmin(du[hit])]] if hit.size
                   else "no python frame")
    return out


def op_label(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def module_label(name: str) -> str:
    """``jit_step(1728...)`` -> ``jit_step``."""
    return name.split("(", 1)[0]


def label_ops(ops: Sequence[Event], modules: Sequence[Event]) -> List[Event]:
    """Each op renamed ``<module>/<op>`` by the module that spans it."""
    mods = sorted(modules, key=lambda e: e[1])
    starts = [s for _, s, _ in mods]
    out = []
    for n, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = module_label(mods[i][0]) if i >= 0 and \
            s < mods[i][1] + mods[i][2] else "?"
        out.append((f"{mod}/{op_label(n)}", s, d))
    return out


# ---------------------------------------------------------------------------
# Reading the profiler's file
# ---------------------------------------------------------------------------

def _device_index(name: str) -> Optional[int]:
    m = re.fullmatch(r"/device:TPU:(\d+)", name)
    return int(m.group(1)) if m else None


def read_xplane(path: str) -> dict:
    """{"ops": {dev: [Event]}, "modules": {dev: [Event]},
    "host": [Event], "window": (lo, hi) or None}."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    window = None
    for plane in pd.planes:
        dev = _device_index(plane.name)
        for line in plane.lines:
            if dev is not None and line.name == "XLA Ops":
                ops[dev] = [(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
            elif dev is not None and line.name == "XLA Modules":
                modules[dev] = [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    else:
                        host.append((e.name, e.start_ns, e.duration_ns))
    return {"ops": ops, "modules": modules, "host": host, "window": window}


def reduce(raw: dict, k: int = 10) -> dict:
    """Busy and idle time, per-module and per-op device time, breakdown."""
    lo, hi = raw["window"]
    devs = sorted(d for d, evs in raw["ops"].items() if evs)
    ops = {d: clip(raw["ops"][d], lo, hi) for d in devs}
    modules = {d: clip(raw["modules"].get(d, []), lo, hi) for d in devs}
    busy = [busy_ns(ops[d], lo, hi) for d in devs]
    all_ops = [e for d in devs for e in ops[d]]
    labelled = [e for d in devs for e in label_ops(ops[d], modules[d])]
    idle = gaps(ops[devs[0]], lo, hi) if devs else [(lo, hi)]
    idle = idle[:500]
    idle_named: Dict[str, float] = {}
    for (s, e), name in zip(idle, frames_at(raw["host"],
                                            [0.5 * (s + e) for s, e in idle])):
        idle_named[name] = idle_named.get(name, 0.0) + (e - s)
    n_dev = max(len(devs), 1)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": (sum(busy) / n_dev) * 1e-9,
        "devices": len(devs),
        "ops": all_ops,
        "modules": [e for d in devs for e in modules[d]],
        "breakdown": {
            "device_ops": [[n, d * 1e-9 / n_dev]
                           for n, d in top(labelled, k)],
            "idle_gaps": [[n, d * 1e-9] for n, d in sorted(
                idle_named.items(), key=lambda kv: -kv[1])[:k]]},
    }


def summarize(raw: dict, k: int = 25) -> dict:
    """A small description of a trace, for looking at one by hand."""
    return {
        "window": raw["window"],
        "devices": sorted(raw["ops"]),
        "ops_top": {d: top(evs, k) for d, evs in raw["ops"].items()},
        "modules_top": {d: top(evs, k) for d, evs in raw["modules"].items()},
        "host_top": top(raw["host"], k),
        "counts": {"host": len(raw["host"]),
                   "ops": {d: len(v) for d, v in raw["ops"].items()}},
    }


def traced(fn: Callable, out_dir: Path, keep: bool = False):
    """Run ``fn`` under the profiler; returns (fn's first result, the
    host-clock seconds of the window, the reduced trace)."""
    import jax
    trace_dir = Path(out_dir) / "trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    trace_dir.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(trace_dir))
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            res, window_s = fn()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(str(trace_dir / "**" / "*.xplane.pb"), recursive=True)
    raw = read_xplane(paths[0])
    info = reduce(raw)
    info["host_window_s"] = window_s
    with open(Path(out_dir) / "trace_summary.json", "w") as f:
        json.dump(summarize(raw), f, indent=1, default=str)
    if keep:
        with open(paths[0], "rb") as src, \
                gzip.open(Path(out_dir) / "window.xplane.pb.gz", "wb") as dst:
            shutil.copyfileobj(src, dst)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return res, window_s, info
