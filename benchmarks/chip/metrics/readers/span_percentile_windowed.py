"""A percentile of the durations, in ms, of the program's spans of one name
that start inside the measured window (``_window.py``)."""

from ...stats import percentile
from ._window import spans_starting_in_window


def read(ctx, span: str, q: float):
    spans = spans_starting_in_window(ctx, span)
    return percentile([s.dur * 1e3 for s in spans], q) if spans else None
