"""How unevenly the held experts were loaded over the window: the busiest
one's pairs over the mean, from the first and the last ``serve.moe_pairs``
record of the program's tracer that starts inside the window."""

from ._window import spans_starting_in_window


def read(ctx):
    spans = spans_starting_in_window(ctx, "serve.moe_pairs")
    if not spans or len(spans) < 2:
        return None
    first = min(spans, key=lambda s: s.t0).args["per_expert"]
    last = max(spans, key=lambda s: s.t0).args["per_expert"]
    took = [b - a for a, b in zip(first, last)]
    if sum(took) <= 0:
        return None
    return max(took) * len(took) / sum(took)
