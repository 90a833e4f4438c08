"""The program's own spans and counters (``photon_ml_tpu.util.timed``), cut to
the traced window, for the per-layer metric readers.

After ``entry.py`` this is the second chipbench module that imports the
program. Why the recorder and not the trace file: ``trace.load_xplane`` keeps
only the host events named ``chipbench:*`` and the trace directory is gone
before the readers run, so a reader cannot see the ``photon:*`` events in the
file; it can see the program's records, in the same process.

The clocks: the recorder stamps ``time.time_ns()``; the xplane's host lines
run on the same clock, but read back (``run["trace"]``) it is counted from the
profiler session's start. ``Window.to_trace`` shifts by the one constant that
lays the program's first ``fit`` span of the window on the harness's first
``chipbench:fit`` span (the harness enters its span, then calls ``fit``: a
few microseconds apart).

A program without the recorder (the parent of the PR that added it) gives
``window(run) is None`` and every reader built on it returns None.
"""

from __future__ import annotations


class Window:
    """The window's ``fit`` spans of the program, and what lies inside and
    before them."""

    def __init__(self, records, fits: list, offset_ns: int):
        self._records = records
        self.fits = fits
        self.start_ns, self.end_ns = fits[0].start_ns, fits[-1].end_ns
        self.offset_ns = offset_ns

    def inside(self, name: str, **attrs) -> list:
        """Records of that name (and those attributes) inside the window."""
        return [
            r
            for r in self._records(since_ns=self.start_ns, until_ns=self.end_ns, name=name)
            if all(r.attrs.get(k) == v for k, v in attrs.items())
        ]

    def before(self, name: str) -> list:
        """Records of that name that ended before the window: set-up's."""
        return self._records(until_ns=self.start_ns, name=name)

    def to_trace(self, records: list) -> list:
        """``[start, end]`` intervals of spans on the trace's clock."""
        return [[r.start_ns - self.offset_ns, r.end_ns - self.offset_ns] for r in records]


def window(run: dict) -> Window | None:
    """The traced window in the program's records: its last ``run["units"]``
    ``fit`` spans, which are the harness's ``chipbench:fit`` spans of
    ``run["trace"]["spans"]`` (set-up's warm-up units come before them)."""
    try:
        from photon_ml_tpu.util.timed import records
    except ImportError:  # a program that has no recorder
        return None
    harness_fits = run["trace"]["spans"].get("fit") or []
    units = int(run["units"])
    fits = records(name="fit")[-units:]
    if not harness_fits or len(fits) < units or len(harness_fits) != units:
        return None
    return Window(records, fits, fits[0].start_ns - harness_fits[0][0])


def seconds(records: list) -> float:
    return sum(r.seconds for r in records)


def seconds_inside(run: dict, name: str) -> float | None:
    """Seconds inside the window's spans of that name; None where there are none."""
    found = window(run)
    spans = found.inside(name) if found else []
    return seconds(spans) if spans else None


def seconds_before(run: dict, *names: str) -> float | None:
    """Seconds inside set-up's spans of those names; None where there are none."""
    found = window(run)
    spans = [r for name in names for r in found.before(name)] if found else []
    return seconds(spans) if spans else None


def weighted_mean(counters: list, weight: str) -> float | None:
    """Mean of counter values weighted by one of their attributes."""
    total = sum(r.attrs.get(weight, 0) for r in counters)
    return sum(r.value * r.attrs.get(weight, 0) for r in counters) / total if total else None
