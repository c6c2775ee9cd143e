"""Telemetry session object threaded through ``FleetEngine.run``.

``Telemetry`` bundles the three observability layers behind one handle:

* ``level`` — which device metrics compile in (``"basic"`` |
  ``"full"``, see ``repro.obs.metrics``).  The engine fuses them into
  one extra jitted dispatch per round whose scalar outputs ride the
  pipelined round ledger — no per-round host sync is added.
  ``"spans"`` compiles no metrics in: the run's compiled programs are
  those of a telemetry-off run, and only the tracer is on.
* ``tracer`` — host span tracing of the dispatch seams
  (``repro.obs.trace``), also written into any ``jax.profiler`` trace
  as ``fl.<seam>`` annotations; ``trace=`` saves the Chrome/Perfetto
  ``trace_event`` JSON at run end.
* ``sink`` — the event stream (``run_start`` / ``round`` / ``run_end``
  dicts).  ``jsonl=`` appends to a JSONL file (the
  ``python -m repro.obs.report`` input); events are always buffered in
  ``last_events`` too.

``profile_dir`` + ``profile_rounds=(start, stop)`` additionally capture
a ``jax.profiler`` device trace for that round window.

Typical use::

    tel = Telemetry(level="full", jsonl="run.jsonl",
                    trace="run.trace.json")
    hist = engine.run("flude", telemetry=tel)
    # -> python -m repro.obs.report run.jsonl
"""
from __future__ import annotations

from typing import Optional, Tuple

from repro.obs.sink import JsonlSink, MemorySink, TeeSink
from repro.obs.trace import Tracer
from repro.obs import metrics as _metrics

# "spans": the tracer alone, no device metrics
LEVELS = ("spans",) + _metrics.LEVELS


class Telemetry:
    def __init__(self, level: str = "full", jsonl: Optional[str] = None,
                 trace: Optional[str] = None,
                 profile_dir: Optional[str] = None,
                 profile_rounds: Optional[Tuple[int, int]] = None):
        if level not in LEVELS:
            raise ValueError(
                f"telemetry level must be one of {LEVELS}, got "
                f"{level!r}")
        self.level = level
        self.tracer = Tracer()
        self.trace_path = trace
        self._memory = MemorySink()
        self.sink = TeeSink(self._memory,
                            JsonlSink(jsonl) if jsonl else None)
        self.profile_dir = profile_dir
        self.profile_rounds = profile_rounds
        self._profiling = False
        self._run_mark = 0

    @property
    def last_events(self):
        """Events of the most recent run (memory buffer)."""
        return self._memory.events[self._run_mark:]

    # -- engine protocol ----------------------------------------------------

    def open_run(self, meta: dict) -> None:
        self._run_mark = len(self._memory.events)
        self.tracer.reset()
        self.sink.emit({"kind": "run_start", "level": self.level, **meta})

    def record_round(self, row: dict) -> None:
        self.sink.emit({"kind": "round", **row})

    def maybe_profile(self, rnd: int) -> None:
        """Start/stop the optional ``jax.profiler`` window at ``rnd``."""
        if self.profile_dir is None or self.profile_rounds is None:
            return
        start, stop = self.profile_rounds
        if rnd == start and not self._profiling:
            import jax
            jax.profiler.start_trace(self.profile_dir)
            self._profiling = True
        elif rnd > stop and self._profiling:
            self._stop_profile()

    def _stop_profile(self) -> None:
        if self._profiling:
            import jax
            jax.profiler.stop_trace()
            self._profiling = False

    def close_run(self, summary: dict) -> None:
        self._stop_profile()
        self.sink.emit({"kind": "run_end",
                        "spans": self.tracer.summary(), **summary})
        if self.trace_path is not None:
            self.tracer.save(self.trace_path)

    def close(self) -> None:
        self._stop_profile()
        self.sink.close()
