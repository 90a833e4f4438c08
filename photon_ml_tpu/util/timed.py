"""Spans and counters: the program's one instrumentation primitive.

Parity target: photon-lib util/Timed.scala:34-77 — ``Timed("phase") { ... }``
blocks used ~40x across the drivers (GameTrainingDriver.scala:350-480,
CoordinateDescent.scala:178-196). Here a context manager / decorator that logs
"<name> took <t> s" at exit and exposes the elapsed seconds — and is, besides,
the span of the training path:

- it enters ``jax.profiler.TraceAnnotation("photon:" + name, **attrs)``, so
  under a profiler session (``--profile-output-directory``) every span lies on
  the host line of the same xplane as the device operations;
- it stamps ``time.time_ns()`` at entry and exit — the clock the xplane's host
  lines run on (a trace read back through ``jax.profiler.ProfileData`` counts
  it from the session's start: same rate, another origin) — and appends one
  ``Record`` to the process-wide bounded recorder that ``records()`` reads;
- ``count(name, value, **attrs)`` appends a counter record, stamped the same
  way, to the same recorder; ``summary()`` gives name -> (count, total seconds)
  of every span since the process started;
- JAX's making of each program lands in the same recorder as spans: importing
  this module registers the process's one ``jax.monitoring`` listener, which
  turns the three stages JAX reports into ``jit.trace`` (jaxpr tracing),
  ``jit.lower`` (jaxpr to MLIR) and ``jit.compile`` (backend compile or
  persistent-cache load), each with ``fun=`` the name JAX gives. Traces nest
  (an inner ``jit``, and every ``jnp`` function that is itself jitted, fires
  its own event inside the outer one): only the outermost becomes a record,
  with ``nested=`` the count of the trace events inside it, so a warm-up adds
  records in proportion to its programs, not its primitives.
  ``trace_events()`` counts every trace event, nested ones included.

Import the names (``from photon_ml_tpu.util.timed import Timed, count,
records, summary``): the package re-exports the decorator ``timed`` under this
module's own name, so ``photon_ml_tpu.util.timed`` reached as an attribute is
that function.

Always on: there is no switch, no environment variable and no exporter. A span
costs two clock reads, one deque append and a disabled ``TraceMe`` (a few
microseconds); it never touches the device. The listener runs only when JAX
traces, lowers or compiles, which a warmed program does not.
"""

from __future__ import annotations

import collections
import functools
import logging
import threading
import time
from typing import Callable, NamedTuple, Optional

import jax

_default_logger = logging.getLogger("photon.timed")

TRACE_PREFIX = "photon:"
# a fit unit of the benchmark's cell records ~60 spans and ~10 counters; the
# bound keeps hours of back-to-back fits while a long-lived process (serving,
# continuous training) cannot grow without limit
MAX_RECORDS = 65536


class Record(NamedTuple):
    """One span (``value`` None) or one counter (``start_ns == end_ns``)."""

    name: str
    start_ns: int
    end_ns: int
    attrs: dict
    value: Optional[float] = None

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_totals: dict = {}  # span name -> [count, seconds]; distinct names are few
_totals_lock = threading.Lock()


def records(
    since_ns: Optional[int] = None,
    until_ns: Optional[int] = None,
    name: Optional[str] = None,
) -> list:
    """The recorder's spans and counters, oldest first: those that START at or
    after ``since_ns`` and END at or before ``until_ns``, under ``name``."""
    return [
        r
        for r in tuple(_records)
        if (name is None or r.name == name)
        and (since_ns is None or r.start_ns >= since_ns)
        and (until_ns is None or r.end_ns <= until_ns)
    ]


def count(name: str, value, **attrs) -> None:
    """Publish a counter value a layer already holds on the host (a tracker
    that materialised, a dataset that was built): never a device read."""
    now = time.time_ns()
    _records.append(Record(name, now, now, attrs, float(value)))


def _keep_span(record: Record) -> None:
    _records.append(record)
    with _totals_lock:
        total = _totals.setdefault(record.name, [0, 0.0])
        total[0] += 1
        total[1] += record.seconds


# jax.monitoring's stages of making a program -> the recorder's span names
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_STAGES = {
    _TRACE_EVENT: "jit.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jit.lower",
    "/jax/core/compile/backend_compile_duration": "jit.compile",
}


class _CompileListener:
    """The process's one ``jax.monitoring`` listener. JAX stamps each stage
    with ``time.time()``, the recorder's clock: it reports the start as a
    scalar (``stage_started``) and the whole stage as a time span
    (``stage_ended``). Depth is per thread, since a trace nests only inside
    a trace of the same thread."""

    def __init__(self):
        self._open = threading.local()  # .depth, .nested: the thread's open traces
        self._lock = threading.Lock()
        self.trace_events = 0

    def stage_started(self, event: str, value, **kwargs) -> None:
        if event == _TRACE_EVENT:
            self._open.depth = getattr(self._open, "depth", 0) + 1

    def stage_ended(self, event: str, start: float, end: float, fun_name: str = "", **kwargs) -> None:
        name = _STAGES.get(event)
        if name is None:
            return
        attrs = {"fun": fun_name}
        if event == _TRACE_EVENT:
            with self._lock:
                self.trace_events += 1
            # a trace this listener did not see start (it was registered
            # meanwhile) ends as an outermost one
            depth = getattr(self._open, "depth", 1) - 1
            nested = getattr(self._open, "nested", 0)
            self._open.depth = max(depth, 0)
            if depth > 0:
                self._open.nested = nested + 1
                return
            self._open.nested = 0
            attrs["nested"] = nested
        _keep_span(Record(name, round(start * 1e9), round(end * 1e9), attrs))


_listener = _CompileListener()
jax.monitoring.register_scalar_listener(_listener.stage_started)
jax.monitoring.register_event_time_span_listener(_listener.stage_ended)


def trace_events() -> int:
    """Jaxpr traces since this module was imported, nested ones included: a
    jit cache hit traces nothing, a persistent-cache load still traces."""
    return _listener.trace_events


def summary() -> dict:
    """Span name -> (count, total seconds) since the process started."""
    with _totals_lock:
        return {name: (n, seconds) for name, (n, seconds) in _totals.items()}


class Timed:
    """Context manager measuring one named section.

    >>> with Timed("ingest") as t: ...
    >>> t.seconds

    ``attrs`` (coordinate id, kind, iteration...) ride on the trace annotation
    and on the record.
    """

    def __init__(self, name: str, logger=None, level: int = logging.INFO, **attrs):
        self.name = name
        self.attrs = attrs
        self.seconds: Optional[float] = None
        self._logger = logger if logger is not None else _default_logger
        self._level = level

    def __enter__(self) -> "Timed":
        self._annotation = jax.profiler.TraceAnnotation(TRACE_PREFIX + self.name, **self.attrs)
        self._annotation.__enter__()
        self._start_ns = time.time_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        record = Record(self.name, self._start_ns, time.time_ns(), self.attrs)
        self._annotation.__exit__(exc_type, exc, tb)
        self.seconds = record.seconds
        _keep_span(record)
        status = "" if exc_type is None else " (failed)"
        log = getattr(self._logger, "info", None)
        if hasattr(self._logger, "log"):
            self._logger.log(self._level, "%s took %.3f s%s", self.name, self.seconds, status)
        elif log is not None:
            log(f"{self.name} took {self.seconds:.3f} s{status}")


def span(name: str, **attrs) -> Timed:
    """A span below the drivers (the estimator, the descent loop, ingest):
    recorded and annotated like every ``Timed``, logged at DEBUG so the hot
    loop prints nothing. It never reads the device."""
    return Timed(name, level=logging.DEBUG, **attrs)


def timed(name: Optional[str] = None, logger=None) -> Callable:
    """Decorator flavor: @timed("train") def train(...)."""

    def wrap(fn):
        label = name or fn.__name__

        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with Timed(label, logger=logger):
                return fn(*args, **kwargs)

        return inner

    return wrap
