"""The window-closing rule, on a fake clock."""

from chipbench.window import run_window


class FakeClock:
    def __init__(self):
        self.now = 100.0

    def __call__(self):
        return self.now


def _unit(clock, seconds, log):
    def fit_unit():
        clock.now += seconds
        log.append(clock.now)
        return len(log)

    return fit_unit


def test_closes_at_first_boundary_at_or_after_seconds():
    clock, log = FakeClock(), []
    w = run_window(_unit(clock, 4.0, log), seconds=10.0, clock=clock)
    # boundaries at 4, 8, 12: the first at or after 10 s is the third
    assert w.units == 3 and w.seconds == 12.0
    assert w.unit_seconds() == [4.0, 4.0, 4.0]
    assert w.results == [1, 2, 3]


def test_boundary_exactly_at_seconds_closes():
    clock, log = FakeClock(), []
    w = run_window(_unit(clock, 5.0, log), seconds=10.0, clock=clock)
    assert w.units == 2 and w.seconds == 10.0


def test_a_unit_longer_than_the_window_still_completes():
    clock, log = FakeClock(), []
    w = run_window(_unit(clock, 61.5, log), seconds=30.0, clock=clock)
    # never cut at the edge: one whole unit, and the rate's time is its whole length
    assert w.units == 1 and w.seconds == 61.5


def test_rate_is_all_rows_over_all_time_stalls_included():
    clock = FakeClock()
    durations = iter([2.0, 7.0, 2.0])  # the second unit stalls

    def fit_unit():
        clock.now += next(durations)
        return None

    w = run_window(fit_unit, seconds=10.0, clock=clock)
    assert w.units == 3 and w.seconds == 11.0
    rows_per_unit = 100
    assert w.units * rows_per_unit / w.seconds == 300 / 11.0


def test_keep_thins_what_is_stored():
    clock, log = FakeClock(), []
    w = run_window(_unit(clock, 6.0, log), seconds=10.0, clock=clock, keep=lambda r: r * 10)
    assert w.results == [10, 20]
