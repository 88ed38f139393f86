"""Step phases: what one thread is doing right now, by name, on two clocks.

The request traces of `tracing.py` answer "where did THIS request's
latency go" and are off unless `tracing_enabled`. A `PhaseClock` answers
the other question, "what does the scheduler thread do with each step",
and is always on: the thread is in at most one named phase at a time
(entering a phase leaves the one before, so phases cannot nest or
overlap), and every phase is read two ways:

- an accumulator of `time.perf_counter()` seconds per phase name
  (`seconds`), which the engine publishes in `stats()["steps"]`;
- a `jax.profiler.TraceAnnotation(name)` held open for the phase. While
  a profile is being taken (`jax.profiler.start_trace`, the dashboard's
  capture, `benchmarks/run.py --trace 1`) it puts the phase on the host
  plane of the profiler's own trace, on the clock of the device ops, so
  an idle gap of the device can be named by the phase that overlaps it.
  With no profile running it is one check of a flag.

One clock belongs to one thread. Cost: ~1 us a phase (measured in
`tests/test_engine_steps.py`), ten phases to an engine step.
"""

from __future__ import annotations

import time
from typing import Dict, Iterable, Optional


class PhaseClock:
    def __init__(self, names: Iterable[str]):
        # Here, not at the top: importing the engine must not import jax.
        from jax.profiler import TraceAnnotation

        self._annotate = TraceAnnotation
        self.seconds: Dict[str, float] = {name: 0.0 for name in names}
        self._name: Optional[str] = None
        self._since = 0.0
        self._annotation = None

    def enter(self, name: str) -> None:
        """Leave the current phase, if any, and enter `name` (one of the
        names the clock was made with) at the same instant."""
        now = time.perf_counter()
        self._close(now)
        self._name, self._since = name, now
        self._annotation = self._annotate(name)
        self._annotation.__enter__()

    def leave(self) -> None:
        """Leave the current phase; the thread is then in none."""
        self._close(time.perf_counter())
        self._name = self._annotation = None

    def _close(self, now: float) -> None:
        if self._name is not None:
            self._annotation.__exit__(None, None, None)
            self.seconds[self._name] += now - self._since
