"""What the ``*_windowed`` readers share: the measured window on the clock of
the program's spans.

A traced run's tracer is on from warm-up to drain, so the spans of a standing
population submitted at once before the window, or of the drain after it,
would own every tail.  These readers take only spans that *start inside the
window*.  ``ctx.t_process + opening_after_s`` is ``time.perf_counter()`` at
the window's opening (``setup_s`` leaves the backend's start out of it); the spans are on ``time.monotonic``.  Where the two are one
clock (Linux: both ``clock_gettime(CLOCK_MONOTONIC)``) the sum is the opening
on the spans' clock; where they are not, there is no window to filter by and
the metric is left out."""

import time


def window(ctx):
    """``(opening, close)`` on ``time.monotonic``'s clock, or None."""
    after = ctx.scalars.get("opening_after_s")
    info = time.get_clock_info
    if after is None or info("perf_counter").implementation != \
            info("monotonic").implementation:
        return None
    opening = ctx.t_process + after
    return opening, opening + ctx.seconds


def spans_starting_in_window(ctx, name: str):
    """The spans of one name that start inside the window (None: there is
    no window on their clock)."""
    w = window(ctx)
    if w is None:
        return None
    return [s for s in ctx.spans if s.name == name and w[0] <= s.t0 < w[1]]
