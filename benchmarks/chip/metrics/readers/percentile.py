"""A percentile of one of the run's sample lists."""

from ...stats import percentile


def read(ctx, samples: str, q: float):
    values = ctx.samples.get(samples)
    return percentile(values, q) if values else None
