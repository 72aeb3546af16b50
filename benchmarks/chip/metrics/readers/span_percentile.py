"""A percentile of the durations of the host spans of one name, in ms: the
program's tracer (``serve.tick``, ``serve.admit``) or the benchmark's own."""

from ...stats import percentile


def read(ctx, span: str, q: float):
    values = [s.dur * 1e3 for s in ctx.spans if s.name == span]
    return percentile(values, q) if values else None
