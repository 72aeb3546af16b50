"""What the program's ``serve.device`` spans say: the time each admission's
and each tick's launch took ON THE DEVICE, over the whole measured window.

The program records one such span a watched launch (its registry's watcher
thread: ``CompiledProgramRegistry.watch``), on ``time.monotonic``: ``t0`` the
later of the launch's dispatch and its predecessor's completion, ``dur`` to its
own completion, ``program`` in its args and, of an admission, ``padded`` (the
tokens its passes computed).  Spans of one server never overlap, so the gaps
between them are when the device waited for a launch.  ``what``:

- ``percentile_ms``: percentile ``q`` of ``dur``, in ms, of the spans whose
  ``program`` matches the regular expression ``program``;
- ``us_per``: the sum of their ``dur`` over the sum of their arg ``per``, in
  us (device time a computed prompt token, whatever lengths the seed dealt);
- ``window_share``: the sum of their ``dur`` over the window's length, in %;
- ``idle_window_share``: the sum of the gaps between consecutive spans (of any
  program) over the window's length, in %;
- ``trace_mismatch``: the guard that the spans hold: ``100 * abs(1 - S / T)``,
  ``S`` the seconds of the spans whose ``program`` matches, clipped to the
  traced slice, ``T`` the slice's device seconds of the same programs by the
  op maps' join (``device_time_share``).

All but the last take only spans that start inside the window
(``_window.py``).  A program that records no such span (the parent of the PR
that added it) has nothing to read and the metric is left out; nothing here
raises."""

import re

from ...kinds import _serving
from ...stats import percentile
from ...trace.reduce import clip, total
from . import device_time_share
from ._window import spans_starting_in_window, window

SPAN = "serve.device"


def _matching(spans, program):
    return [s for s in spans if s.args and re.search(
        program, str(s.args.get("program", "")))]


def gaps_s(spans) -> float:
    """Seconds between consecutive spans: each one's start less the end of
    the one before it, where that is positive."""
    ordered = sorted(spans, key=lambda s: s.t0)
    return sum(max(0.0, b.t0 - (a.t0 + a.dur))
               for a, b in zip(ordered, ordered[1:]))


def clipped_s(spans, lo: float, hi: float) -> float:
    """Seconds of ``spans`` inside ``[lo, hi]``."""
    return total(clip(((s.t0, s.t0 + s.dur) for s in spans), lo, hi))


def slice_on_spans_clock(ctx):
    """``(lo, hi)`` of the traced slice on the spans' clock, or None: it
    ends where the kind asked the profiler to stop (to the few ms its poll
    and the stop's own thread take) and is as long as the trace says."""
    w, r = window(ctx), ctx.reduced
    if w is None or r is None:
        return None
    at, length = _serving.slice_of(ctx)
    hi = w[0] + at + length
    return hi - r.window_s, hi


def read(ctx, what: str, program: str = "", q: float = 50.0, per: str = ""):
    if what == "trace_mismatch":
        return _trace_mismatch(ctx, program)
    spans = spans_starting_in_window(ctx, SPAN)
    if not spans:
        return None
    if what == "idle_window_share":
        return 100.0 * gaps_s(spans) / ctx.seconds
    spans = _matching(spans, program)
    if not spans:
        return None
    took = sum(s.dur for s in spans)
    if what == "percentile_ms":
        return percentile([s.dur * 1e3 for s in spans], q)
    if what == "window_share":
        return 100.0 * took / ctx.seconds
    if what == "us_per":
        n = sum(s.args.get(per, 0) for s in spans)
        return 1e6 * took / n if n > 0 else None
    raise ValueError(f"device_spans reads no {what!r}")


def _trace_mismatch(ctx, program: str):
    where = slice_on_spans_clock(ctx)
    if where is None:
        return None
    spans = _matching([s for s in ctx.spans if s.name == SPAN], program)
    share = device_time_share.read(ctx, program=program)
    if not spans or not share:
        return None
    by_trace = ctx.reduced.busy_s * share / 100.0
    return 100.0 * abs(1.0 - clipped_s(spans, *where) / by_trace)
