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
  of every span since the process started.

Import the names (``from photon_ml_tpu.util.timed import Timed, count,
records, summary``): the package re-exports the decorator ``timed`` under this
module's own name, so ``photon_ml_tpu.util.timed`` reached as an attribute is
that function.

Always on: there is no switch, no environment variable and no exporter. A span
costs two clock reads, one deque append and a disabled ``TraceMe`` (a few
microseconds); it never touches the device.
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
        self._start = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.seconds = time.perf_counter() - self._start
        end_ns = time.time_ns()
        self._annotation.__exit__(exc_type, exc, tb)
        _records.append(Record(self.name, self._start_ns, end_ns, self.attrs))
        with _totals_lock:
            total = _totals.setdefault(self.name, [0, 0.0])
            total[0] += 1
            total[1] += self.seconds
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
