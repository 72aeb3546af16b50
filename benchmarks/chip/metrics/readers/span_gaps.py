"""The gap between a request's tokens: a live row gets one token per tick,
so the gaps are the distances between the ends of consecutive ticks (the
admissions between two ticks included), in ms."""

from ...stats import percentile


def read(ctx, span: str, q: float):
    ends = sorted(s.t0 + s.dur for s in ctx.spans if s.name == span)
    gaps = [(b - a) * 1e3 for a, b in zip(ends, ends[1:])]
    return percentile(gaps, q) if gaps else None
