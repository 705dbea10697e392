"""The rate arithmetic on synthetic stamps: the end-to-end rate is over
the whole window; the segment median stands beside it as ``steady_rate``."""

import itertools

import pytest

from benchmarks import rates


def stamps_of(periods):
    return [0.0] + list(itertools.accumulate(periods))


def test_even_steps_give_the_plain_rate():
    stamps = stamps_of([0.5] * 50)
    assert rates.segment_median_rate(stamps, 16384) == pytest.approx(32768)
    assert rates.whole_window_rate(stamps, 16384) == pytest.approx(32768)
    assert rates.stall_share(stamps) == pytest.approx(0.0, abs=1e-12)


@pytest.mark.parametrize("where", [0, 17, 49])
def test_one_stalled_step_moves_the_rate_and_not_the_steady_rate(where):
    periods = [0.5] * 50
    periods[where] *= 10           # one period ten times the others
    stamps = stamps_of(periods)
    even = rates.segment_median_rate(stamps_of([0.5] * 50), 16384)
    assert rates.segment_median_rate(stamps, 16384) == pytest.approx(
        even, rel=1e-3)
    # the end-to-end rate is all the work over all the time: it moves by
    # the stall's whole size, wherever in the window the stall falls
    assert rates.whole_window_rate(stamps, 16384) == pytest.approx(
        even * 25 / 29.5, rel=1e-9)
    # and the tail is kept where it belongs
    assert rates.stall_share(stamps) == pytest.approx(4.5 / 29.5, rel=1e-6)


def test_a_recurring_stall_shows_in_full():
    periods = [0.5, 0.5, 0.5, 0.5, 1.0] * 10      # every fifth doubled
    even = rates.segment_median_rate(stamps_of([0.5] * 50), 16384)
    got = rates.segment_median_rate(stamps_of(periods), 16384)
    assert got == pytest.approx(even * 5 / 6, rel=1e-9)   # lower by a sixth
    assert rates.whole_window_rate(stamps_of(periods), 16384) \
        == pytest.approx(even * 5 / 6, rel=1e-9)


def test_the_remainder_is_dropped_by_the_segments_alone():
    stamps = stamps_of([0.5] * 50 + [9.0] * 4)    # 54 steps: 5 x 10, 4 over
    assert rates.segment_rates(stamps, 1) == pytest.approx([2.0] * 5)
    # PR 23's first attempt reported the segment median end to end, and a
    # stall in the remainder touched nothing; the whole window counts it
    assert rates.whole_window_rate(stamps, 1) == pytest.approx(54 / 61.0)


def test_too_few_steps_is_an_error():
    with pytest.raises(ValueError, match="measure for longer"):
        rates.segment_rates(stamps_of([0.5] * 4), 1)
