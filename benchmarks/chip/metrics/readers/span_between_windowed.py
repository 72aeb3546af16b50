"""From the end of one ``after`` span to the start of the next ``before``
span on the same thread, in ms, for the ``after`` spans that start inside the
measured window (``_window.py``): what a thread does between two phases, such
as the scheduler between two ticks (harvest, admissions, the loop)."""

import bisect

from ...stats import percentile
from ._window import spans_starting_in_window


def read(ctx, after: str, before: str, q: float):
    ends = spans_starting_in_window(ctx, after)
    if not ends:
        return None
    starts = {}
    for s in ctx.spans:
        if s.name == before:
            starts.setdefault(s.thread, []).append(s.t0)
    for t0s in starts.values():
        t0s.sort()
    gaps = []
    for s in ends:
        t0s = starts.get(s.thread, [])
        i = bisect.bisect_left(t0s, s.t0 + s.dur)
        if i < len(t0s):
            gaps.append((t0s[i] - (s.t0 + s.dur)) * 1e3)
    return percentile(gaps, q) if gaps else None
