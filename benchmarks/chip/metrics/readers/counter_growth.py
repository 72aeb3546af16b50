"""One cumulative counter of the program over others, over the window: the
growth of ``counter`` over the summed growth of the counters ``over`` names,
between the first and the last record of the tracer's span ``span`` that
starts inside the window, as a percentage (``moe_pairs_ratio.py`` with the
span's name an argument and a sum below).  A program whose records lack a
counter, or that writes no such span (the parent of the PR that added it),
has nothing to read there and the metric is left out; nothing here
raises."""

from ._window import spans_starting_in_window


def read(ctx, span: str, counter: str, over):
    spans = spans_starting_in_window(ctx, span)
    if not spans or len(spans) < 2:
        return None
    first = min(spans, key=lambda s: s.t0).args or {}
    last = max(spans, key=lambda s: s.t0).args or {}
    if counter not in last or any(name not in last for name in over):
        return None
    grown = lambda name: last[name] - first.get(name, 0)
    below = sum(grown(name) for name in over)
    if below <= 0:
        return None
    return 100.0 * grown(counter) / below
