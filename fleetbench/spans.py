"""The program's host spans in a profiler trace, and the device's idle
time laid against them.

A run with ``telemetry="spans"`` writes each engine seam into the
profiler's host plane as an ``fl.<seam>`` annotation
(``repro.obs.trace``), on the same clock as the device's operations.
From the ``host`` events and one device's ``ops`` of a window
(``tracing.read_xplane``) this module gives:

* ``host_spans``: the ``fl.*`` events clipped to the window, each with
  its parent by containment (the seams nest on one host thread);
* ``idle_by_span``: the device's idle time split exactly among the
  innermost span covering each idle instant, by interval
  intersection (``NO_SPAN`` where no span covers it);
* ``exposed_ns``: the idle time that lies under any span of a set, the
  host time of those seams that the device waits through;
* ``self_ns``: the host time of a set of spans less their children's;
* ``seams``: three per-round numbers built from these, over the span
  sets ``CACHE_STREAM``, ``LEDGER`` and ``DISPATCH``.

Times are in ns, events are ``(name, start_ns, duration_ns)`` as in
``fleetbench.tracing``.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Sequence, Tuple

from fleetbench import tracing

PREFIX = "fl."
NO_SPAN = "no program span"

# The seams each per-round number reads (``seams``).
CACHE_STREAM = ("fl.cache_fetch", "fl.cache_stage", "fl.cache_flush")
LEDGER = ("fl.ledger_resolve", "fl.eval_readback")
DISPATCH = ("fl.dynamics_step", "fl.cache_expire", "fl.plan",
            "fl.cohort_index", "fl.trainer", "fl.round_cut", "fl.metrics",
            "fl.server_step", "fl.observe", "fl.eval")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float
    parent: int           # index into the list, -1 for a root


def host_spans(host: Iterable[tracing.Event], lo: float,
               hi: float) -> List[Span]:
    """The ``fl.*`` host events clipped to [lo, hi), sorted by start
    (an enclosing span before the spans it holds), each with the
    innermost span that contains it as its parent."""
    evs = sorted(((s, s + d, n) for n, s, d in
                  tracing.clip((e for e in host if e[0].startswith(PREFIX)),
                               lo, hi)),
                 key=lambda e: (e[0], -e[1]))
    out: List[Span] = []
    stack: List[int] = []
    for s, e, n in evs:
        while stack and not (out[stack[-1]].start <= s
                             and e <= out[stack[-1]].end):
            stack.pop()
        out.append(Span(n, s, e, stack[-1] if stack else -1))
        stack.append(len(out) - 1)
    return out


def idle_intervals(ops: Sequence[tracing.Event], lo: float,
                   hi: float) -> List[Tuple[float, float]]:
    """The window's idle [start, end) intervals, in time order."""
    return sorted(tracing.gaps(ops, lo, hi))


def intersect_ns(a: Sequence[Sequence[float]],
                 b: Sequence[Sequence[float]]) -> float:
    """Length of the intersection of two sorted, disjoint interval
    lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def exposed_ns(idle: Sequence[Tuple[float, float]], spans: Sequence[Span],
               names: Iterable[str]) -> float:
    """Idle time under the union of the spans named ``names``."""
    names = set(names)
    cover = tracing.merge((sp.start, sp.end) for sp in spans
                          if sp.name in names)
    return intersect_ns(idle, cover)


def self_ns(spans: Sequence[Span], names: Iterable[str]) -> float:
    """Summed time of the spans named ``names`` less that of their
    direct children."""
    names = set(names)
    total = sum(sp.end - sp.start for sp in spans if sp.name in names)
    for sp in spans:
        if sp.parent >= 0 and spans[sp.parent].name in names:
            total -= sp.end - sp.start
    return total


def innermost(spans: Sequence[Span], lo: float,
              hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi) cut at every span boundary, in time order, each piece
    named by the innermost span covering it (``NO_SPAN`` where none
    does).  ``spans`` nest, as ``host_spans`` gives them."""
    out: List[Tuple[float, float, str]] = []
    stack: List[Span] = []
    t = lo

    def upto(until):
        nonlocal t
        if until > t:
            out.append((t, until, stack[-1].name if stack else NO_SPAN))
            t = until

    for sp in spans:
        while stack and stack[-1].end <= sp.start:
            upto(stack[-1].end)
            stack.pop()
        upto(sp.start)
        stack.append(sp)
    while stack:
        upto(stack[-1].end)
        stack.pop()
    upto(hi)
    return out


def idle_by_span(idle: Sequence[Tuple[float, float]],
                 spans: Sequence[Span], lo: float,
                 hi: float) -> Dict[str, float]:
    """Idle time (ns) by the innermost span covering it; the values sum
    to the idle time of the window."""
    out: Dict[str, float] = {}
    pieces = innermost(spans, lo, hi)
    i = j = 0
    while i < len(idle) and j < len(pieces):
        a, b, name = pieces[j]
        s, e = max(idle[i][0], a), min(idle[i][1], b)
        if e > s:
            out[name] = out.get(name, 0.0) + (e - s)
        if idle[i][1] < b:
            i += 1
        else:
            j += 1
    return out


def seams(idle: Sequence[Tuple[float, float]], spans: Sequence[Span],
          rounds: int) -> Dict[str, float]:
    """Per round, in ms: device idle under the cache stream's seams,
    under the round ledger's reads, and the host self time of the
    round's dispatch seams."""
    per = 1e-6 / rounds
    return {"cache_stream_exposed_ms":
            exposed_ns(idle, spans, CACHE_STREAM) * per,
            "ledger_exposed_ms": exposed_ns(idle, spans, LEDGER) * per,
            "dispatch_host_ms": self_ns(spans, DISPATCH) * per}
