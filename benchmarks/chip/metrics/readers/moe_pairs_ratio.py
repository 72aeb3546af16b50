"""One counter of the expert layers over another, over the window: the
growth of ``counter`` over the growth of ``over`` between the first and the
last ``serve.moe_pairs`` record of the program's tracer that starts inside
the window (cumulative counts, prompt passes and ticks alike), as a
percentage.  A program whose records lack a counter (the parent of the PR
that added it) has nothing to read there and the metric is left out;
nothing here raises."""

from ._window import spans_starting_in_window

SPAN = "serve.moe_pairs"


def read(ctx, counter: str, over: str):
    spans = spans_starting_in_window(ctx, SPAN)
    if not spans or len(spans) < 2:
        return None
    first = min(spans, key=lambda s: s.t0).args
    last = max(spans, key=lambda s: s.t0).args
    if counter not in last or over not in last:
        return None
    below = last[over] - first.get(over, 0)
    if below <= 0:
        return None
    return 100.0 * (last[counter] - first.get(counter, 0)) / below
