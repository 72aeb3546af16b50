"""The routed experts' grouped matmuls in the traced slice: their share of
the device's busy time, or of their roofline.

Their calls are the Pallas custom calls with a rank-2 result (``[pairs,
width]``; the attention kernels return rank 3).  What they must do comes
from the program's own counters, the ``serve.moe_pairs`` records of its
tracer (cumulative pairs held here and expert visits, counted on the
device and pulled with each tick's tokens), read at both ends of the slice;
operations and bytes from the counting function the metric file names."""

from ...builders import resolve
from ...flops import parse_shapes
from ...harness import log
from ...kinds import _serving
from ._window import window

SPAN = "serve.moe_pairs"


def _is_grouped_matmul(o) -> bool:
    dims = [d for _, d in parse_shapes(o.shape)]
    return o.is_kernel and len(dims) == 1 and len(dims[0]) == 2


def counters_at(ctx, t: float):
    """``args`` of the last ``serve.moe_pairs`` record at or before ``t``
    on the spans' clock, or None."""
    last = None
    for s in ctx.spans:
        if s.name == SPAN and s.t0 <= t and (last is None or s.t0 >= last.t0):
            last = s
    return last.args if last is not None else None


def read(ctx, what: str, count: str = ""):
    r = ctx.reduced
    if r is None or r.busy_s <= 0:
        return None
    took = r.ops_time(_is_grouped_matmul)
    if took <= 0:
        return None
    if what == "time_share":
        return 100.0 * took / r.busy_s
    w = window(ctx)
    if w is None:
        return None
    # the slice ends where the kind asked the profiler to stop, and is as
    # long as the trace says
    at, length = _serving.slice_of(ctx)
    stop = w[0] + at + length
    a, b = counters_at(ctx, stop - r.window_s), counters_at(ctx, stop)
    if a is None or b is None:
        return None
    ops, nbytes = resolve(count)(ctx.model_config, b["held"] - a["held"],
                                 b["visits"] - a["visits"])
    least = max(ops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    log("roofline", kernel="expert_ffn",
        bound="memory" if nbytes / ctx.peaks["hbm_bytes_per_s"] >= least
        else "compute", pairs=b["held"] - a["held"],
        visits=b["visits"] - a["visits"], least_s=round(least, 6),
        took_s=round(took, 6))
    return 100.0 * least / took
