"""A selecting family's single-token passes in the traced slice: what the
index, the selected sweep and the window sweep took against the least each
could take, and the ratios of its counters.

What the passes had to do comes from the program's own counters, the
``serve.sparse_select`` records of its tracer (cumulative over single-token
passes, counted on the device and pulled with each tick's tokens: cached
tokens a full layer's query could have chosen from, those it attended to,
latent rows the sweep's copies moved, ring cells a window layer's query
saw).  A ``ratio`` is one counter's growth over another's, first to last
record inside the window.  A ``roofline`` reads the counter named at both
ends of the slice, takes operations and bytes from the counting function the
metric file names, and divides the least time by the device time of the
tick program's ops under the named scope (``scope``, less ``exclude``:
regular expressions searched in the scope path, joined as
``device_time_share`` joins them).  A program without such records or
scopes (the parent of the PR that added them) has nothing to read and the
metric is left out; nothing here raises."""

import re

from ...builders import resolve
from ...harness import log
from ...kinds import _serving
from ._window import spans_starting_in_window, window

SPAN = "serve.sparse_select"


def counters_at(ctx, t: float):
    """``args`` of the last record at or before ``t`` on the spans' clock,
    or None."""
    last = None
    for s in ctx.spans:
        if s.name == SPAN and s.t0 <= t and (last is None or s.t0 >= last.t0):
            last = s
    return last.args if last is not None else None


def scope_time(table, program: str, scope: str, exclude: str = "") -> float:
    """Seconds of ``table`` (``(program, scope) -> seconds``) under
    ``program`` whose scope path holds ``scope`` and not ``exclude``."""
    return sum(s for (p, sc), s in table.items()
               if re.search(program, p) and re.search(scope, sc)
               and not (exclude and re.search(exclude, sc)))


def read(ctx, what: str, counter: str = "", over: str = "", count: str = "",
         scope: str = "", exclude: str = "", program: str = "tick"):
    if what == "ratio":
        spans = spans_starting_in_window(ctx, SPAN)
        if not spans or len(spans) < 2:
            return None
        first = min(spans, key=lambda s: s.t0).args
        last = max(spans, key=lambda s: s.t0).args
        below = last.get(over, 0) - first.get(over, 0)
        if below <= 0:
            return None
        return (last.get(counter, 0) - first.get(counter, 0)) / below
    r = ctx.reduced
    w = window(ctx)
    if r is None or r.busy_s <= 0 or w is None:
        return None
    # the slice ends where the kind asked the profiler to stop, and is as
    # long as the trace says
    at, length = _serving.slice_of(ctx)
    stop = w[0] + at + length
    a, b = counters_at(ctx, stop - r.window_s), counters_at(ctx, stop)
    if a is None or b is None or counter not in b:
        return None
    try:
        from .device_time_share import _joined
        joined = _joined(r)
    except Exception as e:      # a metric is never worth a run
        log("sparse_select", error=repr(e)[:200].replace(" ", "_"))
        return None
    if joined is None:
        return None
    took = scope_time(joined[0], program, scope, exclude)
    work = b[counter] - a.get(counter, 0)
    if took <= 0 or work <= 0:
        return None
    ops, nbytes = resolve(count)(ctx.model_config, work)
    least = max(ops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    log("roofline", kernel=scope.replace("/", "."),
        bound="memory" if nbytes / ctx.peaks["hbm_bytes_per_s"] >= least
        else "compute", work=work, least_s=round(least, 6),
        took_s=round(took, 6))
    return 100.0 * least / took
