"""Host-stage timer: named spans on the profiler's clock, with running totals.

`EngineCore` and `SNNRunner` each own one `Stages` and wrap the host work
of a step in its spans (``engine.step``, ``engine.admit``, ``snn.input``,
``snn.dispatch``, ...). A span does two things:

* it opens a `jax.profiler.TraceAnnotation` of that name, so a profiler
  trace shows the span on the same clock as the device's ops (the
  annotation costs well under a microsecond when nothing is recording);
* it adds its `time.perf_counter` duration to the stage's cumulative
  ``seconds``, ``calls`` and ``max_s``.

It is always on, and it reads only the host's performance counter: never
the engine clock, an RNG or a `Result`, so served results are the same
with or without anyone reading the totals. `EngineCore.stats()` exports
them under ``host_stages``; `Observability.attach_engine` publishes them
as ``stage_<name>_seconds`` / ``stage_<name>_calls`` counters.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax

clock = time.perf_counter


class _Span:
    """One call of a stage: the profiler annotation around it, and its
    ``perf_counter`` duration added to the stage's totals on exit."""

    __slots__ = ("totals", "annotation", "t0")

    def __init__(self, totals: List[float], annotation):
        self.totals = totals
        self.annotation = annotation

    def __enter__(self) -> "_Span":
        self.annotation.__enter__()
        self.t0 = clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        seconds = clock() - self.t0
        self.annotation.__exit__(exc_type, exc, tb)
        totals = self.totals
        totals[0] += seconds
        totals[1] += 1
        if seconds > totals[2]:
            totals[2] = seconds


class Stages:
    """Cumulative seconds, calls and longest call per named host stage."""

    def __init__(self):
        self._totals: Dict[str, List[float]] = {}   # name -> [s, calls, max_s]

    def span(self, name: str, step_num: Optional[int] = None) -> _Span:
        """A context manager timing one call of stage ``name``; with
        ``step_num`` the annotation is a `StepTraceAnnotation`, which the
        profiler's step view reads."""
        totals = self._totals.get(name)
        if totals is None:
            totals = self._totals[name] = [0.0, 0, 0.0]
        if step_num is None:
            annotation = jax.profiler.TraceAnnotation(name)
        else:
            annotation = jax.profiler.StepTraceAnnotation(name, step_num=step_num)
        return _Span(totals, annotation)

    def snapshot(self) -> Dict[str, Dict[str, float]]:
        """``{name: {"seconds", "calls", "max_s"}}`` of the stages entered
        so far, JSON-able."""
        return {name: {"seconds": s, "calls": calls, "max_s": max_s}
                for name, (s, calls, max_s) in self._totals.items()}
