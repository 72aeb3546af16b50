"""The state-space kernels in the traced slice: the decode step's and the
chunk scan's shares of their rooflines, and both over the device's busy
time.

Their calls are the Pallas custom calls that return the state stack beside
their result (a tuple with a rank-4 float32 array: the stack is aliased
through the call).  The decode step's other result is one row a slot
(``f32[B, 1, channels]``); the chunk scan's is a chunk of rows.  What they
must do comes from the program's own counters, the ``serve.state_steps``
records of its tracer (cumulative state rows stepped and tokens scanned,
counted on the device and pulled with each tick's tokens), read at both ends
of the slice; operations and bytes from the counting function the metric
file names.  A program without such kernels or counters has nothing to read
and the metric is left out."""

from ...builders import resolve
from ...flops import parse_shapes
from ...harness import log
from ...kinds import _serving
from ._window import window

SPAN = "serve.state_steps"
#: kernel -> the counters whose growth over the slice is its work
COUNTERS = {"decode_step": ("ssm_rows_stepped",),
            "chunk_scan": ("scan_tokens_real", "scan_tokens_padded")}


def kernel_of(o):
    """``"decode_step"``, ``"chunk_scan"`` or None for a device op."""
    if not o.is_kernel:
        return None
    shapes = parse_shapes(o.shape)
    rows = [d for t, d in shapes if t == "f32" and len(d) == 3]
    if len(shapes) != 2 or len(rows) != 1 or not any(
            t == "f32" and len(d) == 4 for t, d in shapes):
        return None
    return "decode_step" if rows[0][1] == 1 else "chunk_scan"


def counters_at(ctx, t: float):
    """``args`` of the last ``serve.state_steps`` record at or before ``t``
    on the spans' clock, or None."""
    last = None
    for s in ctx.spans:
        if s.name == SPAN and s.t0 <= t and (last is None or s.t0 >= last.t0):
            last = s
    return last.args if last is not None else None


def read(ctx, what: str, kernel: str = "", count: str = ""):
    r = ctx.reduced
    if r is None or r.busy_s <= 0:
        return None
    if what == "time_share":
        took = r.ops_time(lambda o: kernel_of(o) is not None)
        return 100.0 * took / r.busy_s if took > 0 else None
    took = r.ops_time(lambda o: kernel_of(o) == kernel)
    w = window(ctx)
    if took <= 0 or w is None:
        return None
    # the slice ends where the kind asked the profiler to stop, and is as
    # long as the trace says
    at, length = _serving.slice_of(ctx)
    stop = w[0] + at + length
    a, b = counters_at(ctx, stop - r.window_s), counters_at(ctx, stop)
    if a is None or b is None:
        return None
    work = sum(b[k] - a[k] for k in COUNTERS[kernel])
    ops, nbytes = resolve(count)(ctx.model_config, work)
    least = max(ops / ctx.peaks["bf16_flops"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    log("roofline", kernel=kernel,
        bound="memory" if nbytes / ctx.peaks["hbm_bytes_per_s"] >= least
        else "compute", work=work, least_s=round(least, 6),
        took_s=round(took, 6))
    return 100.0 * least / took
