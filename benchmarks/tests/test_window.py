"""The window's rule on a fake clock: a rate and a time per item are all the
work over all the time to the last completion, and neither moves in steps of
one item as the window's length changes."""

import pytest

from benchmarks.window import Item, Window, run_window


class FakeClock:
    def __init__(self) -> None:
        self.now = 100.0

    def __call__(self) -> float:
        return self.now


def drive(seconds: float, item_s: float, fail_every: int = 0) -> Window:
    clock = FakeClock()

    def one(i: int) -> Item:
        start = clock.now
        clock.now += item_s
        return Item(start=start, end=clock.now, ok=not (fail_every and i % fail_every == 0))

    return run_window(seconds, one, clock=clock)


def test_started_work_is_finished_and_the_denominator_is_the_time_that_passed():
    w = drive(seconds=10.0, item_s=1.83)
    # items start at 0, 1.83, ... 9.15 (the sixth starts before 10 s): six items
    assert w.attempted == 6
    assert w.elapsed == pytest.approx(6 * 1.83)
    assert w.closed == w.items[-1].end


@pytest.mark.parametrize("item_s", [1.83, 8.0, 26.0])
def test_rate_and_seconds_per_item_do_not_step_with_the_windows_length(item_s):
    """Under 'items completed inside a fixed window' the rate at 51 s and at
    51 s plus one item's time would differ by one item in the count; here one
    more item adds its own time, and both statistics stay where they are."""
    lengths = [10.0, 10.0 + 0.4 * item_s, 10.0 + item_s, 51.0, 51.0 + 0.99 * item_s]
    windows = [drive(s, item_s) for s in lengths]
    assert len({w.attempted for w in windows}) > 1  # the count does move
    for w in windows:
        assert w.rate() == pytest.approx(1.0 / item_s, rel=1e-9)
        assert w.seconds_per_item() == pytest.approx(item_s, rel=1e-9)


def test_at_least_one_item_runs_and_a_long_item_is_not_cut():
    w = drive(seconds=1.0, item_s=30.0)
    assert w.attempted == 1 and w.elapsed == pytest.approx(30.0)


def test_failed_items_take_time_and_earn_no_rate():
    w = drive(seconds=10.0, item_s=2.0, fail_every=2)  # items 0, 2, 4 fail
    assert (w.attempted, w.failed) == (5, 3)
    assert w.rate() == pytest.approx(2 / 10.0)
    assert w.seconds_per_item() == pytest.approx(2.0)


def test_the_tail_is_the_tail_of_every_item():
    items = [Item(start=0.0, end=float(k), ok=True) for k in range(1, 21)]
    w = Window(opened=0.0, closed=20.0, items=items)
    assert w.quantile(0.95) == 19.0  # nearest rank: the 19th of 20
    assert w.quantile(0.5) == 10.0
    assert Window(opened=0.0, closed=0.0, items=[]).quantile(0.95) is None


def test_a_traced_window_closes_after_its_limit_of_items():
    assert drive_limited(seconds=51.0, item_s=1.0, limit=3).attempted == 3
    assert drive_limited(seconds=2.5, item_s=1.0, limit=30).attempted == 3  # the length still holds


def drive_limited(seconds, item_s, limit):
    clock = FakeClock()

    def one(i):
        start = clock.now
        clock.now += item_s
        return Item(start=start, end=clock.now, ok=True)

    return run_window(seconds, one, clock=clock, limit=limit)
