"""Host span tracing for the fleet round path.

The engine's per-round host work is a short sequence of *dispatch* seams
— plan, fused trainer, round cut, server step, ledger resolve, cache
stream, eval — and :class:`Tracer` wraps each in a lightweight span
(``time.perf_counter`` pairs, one appended tuple per span).  Because the
round path is asynchronous, a span measures the *host-side* cost of its
seam (argument prep + dispatch + any blocking read it performs), which
is exactly the budget the zero-per-round-host-sync invariant protects.

Each span also opens a ``jax.profiler.TraceAnnotation("fl.<name>")``
for its lifetime, with its args as annotation arguments, so a
``jax.profiler`` trace of the run holds every seam on the profiler's
own clock, in the host plane beside the device's operations (outside a
profiler session the annotation records nothing).  ``steps`` marks
each round with a ``StepTraceAnnotation("fl.<name>", step_num=i)``, the
marker XProf's step view groups by.

Spans export as Chrome ``trace_event`` JSON (``save``) loadable in
Perfetto / ``chrome://tracing``, and aggregate into a per-name summary
(``summary``) that the report CLI renders as the round-time breakdown.
The tracer keeps the stack of open spans, so each recorded span knows
its parent and its self time (its own time less its children's).

``NULL_TRACER`` is the disabled path: ``span`` returns a shared no-op
context manager and ``steps`` a plain ``range``, so instrumented code
needs no branches and the default (telemetry off) path pays a single
attribute lookup per seam, with no annotation and no allocation.
"""
from __future__ import annotations

import json
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

from jax.profiler import StepTraceAnnotation, TraceAnnotation

PREFIX = "fl."


class Span:
    """One timed section; also usable as ``with tracer.span(..) as sp``
    for its ``seconds`` reading (the benchmark clock)."""

    __slots__ = ("_tracer", "name", "args", "t0", "t1", "parent",
                 "child_s", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, args: Optional[dict]):
        self._tracer = tracer
        self.name = name
        self.args = args
        self.t0 = 0.0
        self.t1 = 0.0
        self.parent: Optional[Span] = None
        self.child_s = 0.0

    def __enter__(self) -> "Span":
        stack = self._tracer._stack
        self.parent = stack[-1] if stack else None
        stack.append(self)
        self._annotation = TraceAnnotation(PREFIX + self.name,
                                           **(self.args or {}))
        self._annotation.__enter__()
        self.t0 = self._tracer._clock()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = self._tracer._clock()
        self._annotation.__exit__(*exc)
        self._tracer._stack.pop()
        if self.parent is not None:
            self.parent.child_s += self.seconds
        self._tracer._record(self)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Tracer:
    """Append-only span recorder with a perf_counter clock; timestamps
    are relative to tracer construction (reset)."""

    def __init__(self):
        self._clock = time.perf_counter
        self.reset()

    def reset(self) -> None:
        self._epoch = self._clock()
        self._stack: List[Span] = []
        # (name, ts_us, dur_us, args, parent name or None, self_us)
        self.events: List[Tuple[str, float, float, Any, Optional[str],
                                float]] = []

    # -- recording ----------------------------------------------------------

    def span(self, name: str, **args) -> Span:
        return Span(self, name, args or None)

    def steps(self, name: str, n: int) -> Iterator[int]:
        """``range(n)``, each step's loop body inside a profiler step
        marker ``fl.<name>`` numbered by the step."""
        for i in range(n):
            with StepTraceAnnotation(PREFIX + name, step_num=i):
                yield i

    def _record(self, sp: Span) -> None:
        self.events.append((
            sp.name, (sp.t0 - self._epoch) * 1e6, sp.seconds * 1e6,
            sp.args, None if sp.parent is None else sp.parent.name,
            (sp.seconds - sp.child_s) * 1e6))

    # -- aggregation / export -----------------------------------------------

    def summary(self) -> Dict[str, dict]:
        """Per-span-name aggregate: count, total/self/mean/max seconds."""
        out: Dict[str, dict] = {}
        for name, _ts, dur, _args, _parent, self_us in self.events:
            s = out.setdefault(name, {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0, "max_s": 0.0})
            s["count"] += 1
            s["total_s"] += dur * 1e-6
            s["self_s"] += self_us * 1e-6
            s["max_s"] = max(s["max_s"], dur * 1e-6)
        for s in out.values():
            s["mean_s"] = s["total_s"] / s["count"]
        return out

    def to_chrome(self) -> dict:
        """Chrome ``trace_event`` JSON (Perfetto-loadable)."""
        evs = [{"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
                "args": {"name": "fleet-engine host"}}]
        for name, ts, dur, args, _parent, _self in self.events:
            ev = {"name": name, "pid": 0, "tid": 0, "ts": ts, "cat": "fl",
                  "ph": "X", "dur": dur}
            if args:
                ev["args"] = args
            evs.append(ev)
        return {"traceEvents": evs, "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    seconds = 0.0


_NULL_SPAN = _NullSpan()


class NullTracer:
    """Disabled tracer: every operation is a no-op (shared span)."""

    events: List = []

    def span(self, name: str, **args) -> _NullSpan:
        return _NULL_SPAN

    def steps(self, name: str, n: int) -> range:
        return range(n)

    def summary(self) -> dict:
        return {}

    def reset(self) -> None:
        pass


NULL_TRACER = NullTracer()
