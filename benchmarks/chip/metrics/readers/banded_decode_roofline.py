"""The single-token sweeps of a family whose layers keep two pools (whole
rows, and rings of a window): their share of their roofline in the traced
slice, and the cached tokens they streamed for each one a query saw.

The sweep's calls are the custom calls ``decode_roofline`` tells: one result
row a slot (rank 3 with a middle dimension of 1).  What they had to read
comes from the program's own counters BY POOL, the ``serve.kv_sweep``
records of its tracer (cumulative ``<pool>_tokens_live`` and
``<pool>_tokens_streamed``, counted on the host from the sweep's plan with
each tick's pull), read at both ends of the slice (the ratio of streamed to
seen, a counter's alone, at both ends of the window): a ring's call is credited
with the tokens its ring holds (at most the window), never with the
context's, which would read over 100%.  Operations and bytes of a cached
token of one layer from the counting function the metric file names.  A
program without such records (one pool: the parent of the PR that added
them) has nothing to read and the metric is left out."""

from ...builders import resolve
from ...flops import parse_shapes
from ...harness import log
from ...kinds import _serving
from ._window import window

SPAN = "serve.kv_sweep"


def counters_at(ctx, t: float):
    """``args`` of the last ``serve.kv_sweep`` record at or before ``t`` on
    the spans' clock, or None."""
    last = None
    for s in ctx.spans:
        if s.name == SPAN and s.t0 <= t and (last is None or s.t0 >= last.t0):
            last = s
    return last.args if last is not None else None


def _is_sweep(o) -> bool:
    dims = [d for _, d in parse_shapes(o.shape)]
    return o.is_kernel and len(dims) == 1 and len(dims[0]) == 3 \
        and dims[0][1] == 1


def read(ctx, what: str = "roofline", count: str = ""):
    r = ctx.reduced
    w = window(ctx)
    if w is None:
        return None
    if what == "streamed_over_live":    # a counter's: over the whole window
        inside = sorted((s for s in ctx.spans
                         if s.name == SPAN and w[0] <= s.t0 < w[1]),
                        key=lambda s: s.t0)
        a, b = (inside[0].args, inside[-1].args) if len(inside) > 1 \
            else (None, None)
    elif r is None or r.busy_s <= 0:
        return None
    else:
        # the slice ends where the kind asked the profiler to stop, and is
        # as long as the trace says
        at, length = _serving.slice_of(ctx)
        stop = w[0] + at + length
        a, b = counters_at(ctx, stop - r.window_s), counters_at(ctx, stop)
    if a is None or b is None:
        return None
    grown = lambda end: {k[:-len(end)]: b[k] - a.get(k, 0) for k in b
                         if k.endswith(end)}
    live, streamed = grown("_tokens_live"), grown("_tokens_streamed")
    if sum(live.values()) <= 0:
        return None
    if what == "streamed_over_live":
        return sum(streamed.values()) / sum(live.values())
    took = r.ops_time(_is_sweep)
    if took <= 0:
        return None
    ops, nbytes = resolve(count)(ctx.model_config, sum(live.values()))
    least = max(ops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    log("roofline", kernel="banded_decode_attention", bound="memory",
        least_s=round(least, 6), took_s=round(took, 6),
        **{f"{k}_tokens_live": v for k, v in live.items()},
        **{f"{k}_calls": v for k, v in grown("_calls").items()})
    return 100.0 * least / took
