"""The measured window: fit units back to back, closed at the first unit
boundary at or after ``seconds``.

The rate taken from it is all rows of all completed units over the clock from
the window's start to that boundary: all the work over all the time, stalls
included, never quantised by a unit cut off at the edge. The harness does
nothing of its own inside: ``fit_unit`` is the system's call, ``keep`` only
stores what it returned.
"""

from __future__ import annotations

import dataclasses
import time


@dataclasses.dataclass
class Window:
    start: float
    ends: list  # clock reading at each unit's boundary
    results: list  # what each unit returned

    @property
    def units(self) -> int:
        return len(self.ends)

    @property
    def seconds(self) -> float:
        return self.ends[-1] - self.start

    def unit_seconds(self) -> list:
        return [b - a for a, b in zip([self.start] + self.ends[:-1], self.ends)]


def run_window(fit_unit, seconds: float, clock=time.perf_counter, keep=None) -> Window:
    """Repeat ``fit_unit()`` (which returns only when its outputs are ready)
    until the first boundary at or after ``seconds``; always at least one
    unit. ``keep(result)`` may thin a result before it is stored."""
    start = clock()
    ends, results = [], []
    while True:
        result = fit_unit()
        now = clock()
        ends.append(now)
        results.append(result if keep is None else keep(result))
        if now - start >= seconds:
            return Window(start=start, ends=ends, results=results)


class CompileMeter:
    """Counts XLA backend compiles, persistent-cache misses and jaxpr traces
    from ``jax.monitoring``: inside the window all three must stay 0 (a cache
    hit still traces, so traces catch a new shape even when nothing
    compiles)."""

    _EVENTS = {
        "/jax/core/compile/backend_compile_duration": "compiles",
        "/jax/core/compile/jaxpr_trace_duration": "traces",
    }

    def __init__(self):
        import jax

        self.counts = {"compiles": 0, "traces": 0, "cache_misses": 0}
        self.compile_seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_kw):
        key = self._EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1
            if key == "compiles":
                self.compile_seconds += duration

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.counts["cache_misses"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)

    def since(self, before: dict) -> dict:
        return {k: self.counts[k] - before[k] for k in self.counts}
