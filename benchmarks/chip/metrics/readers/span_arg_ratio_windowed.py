"""``sum(num) / sum(den)`` of two args of the program's spans of one name
that start inside the measured window (``_window.py``), in %; with
``complement`` 100 minus it: for counts a span carries per call
(``serve.prefill``: real and computed tokens)."""

from ._window import spans_starting_in_window


def read(ctx, span: str, num: str, den: str, complement: bool = False):
    spans = spans_starting_in_window(ctx, span) or []
    pairs = [(s.args[num], s.args[den]) for s in spans
             if s.args and num in s.args and den in s.args]
    total = sum(d for _, d in pairs)
    if total <= 0:
        return None
    share = 100.0 * sum(n for n, _ in pairs) / total
    return 100.0 - share if complement else share
