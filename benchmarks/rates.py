"""Rates from per-step stamps.

``stamps[0]`` is the host-clock reading taken when the last warm-up step's
loss arrived and ``stamps[i]`` the one taken when the window's i-th step's
did, always with the following step already dispatched: successive stamps
are one step period apart and the device never waits for the host.

The END-TO-END rate is ``whole_window_rate``: all the work of the window
over all its time, first stamp to last, nothing dropped.  A stall inside the
window lowers it by the stall's whole size, as it lowers what a user trains
in that time.

``segment_median_rate`` stands beside it as a per-layer metric
(``steady_rate``): the window's steps are cut into five consecutive segments
of equal step count (the remainder at the end is dropped), a segment's rate
is its work over the time from the stamp before its first step to the stamp
of its last, and the median of the five is taken.  One stalled step spoils
one segment and leaves that median where it was; a stall that recurs every
few steps is in every segment and shows in full.  It says how fast the
steps are when nothing is in their way; ``stall_share`` says what was.
"""

from __future__ import annotations

import statistics

SEGMENTS = 5


def periods(stamps: list[float]) -> list[float]:
    return [b - a for a, b in zip(stamps, stamps[1:])]


def segment_rates(stamps: list[float], units_per_step: float,
                  segments: int = SEGMENTS) -> list[float]:
    """Work per second of each of ``segments`` equal runs of steps."""
    per = (len(stamps) - 1) // segments
    if per < 1:
        raise ValueError(
            f"{len(stamps) - 1} steps in the window cannot be cut into "
            f"{segments} segments: measure for longer")
    return [per * units_per_step / (stamps[(i + 1) * per] - stamps[i * per])
            for i in range(segments)]


def segment_median_rate(stamps: list[float], units_per_step: float) -> float:
    return statistics.median(segment_rates(stamps, units_per_step))


def whole_window_rate(stamps: list[float], units_per_step: float) -> float:
    return (len(stamps) - 1) * units_per_step / (stamps[-1] - stamps[0])


def stall_share(stamps: list[float]) -> float:
    """Share of the window spent beyond ``steps x median period``: what the
    long steps cost together.  0 for perfectly even steps; may come out a
    hair negative when a few steps are shorter than the median."""
    p = periods(stamps)
    return 1.0 - len(p) * statistics.median(p) / (stamps[-1] - stamps[0])


def segment_means(values: list[float], segments: int = SEGMENTS
                  ) -> list[float]:
    """Mean of ``values`` (one per step) over the same segments."""
    per = len(values) // segments
    return [statistics.fmean(values[i * per:(i + 1) * per])
            for i in range(segments)]
