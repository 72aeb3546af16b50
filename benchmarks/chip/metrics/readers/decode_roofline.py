"""The decode-attention kernel's share of its roofline in the tick.

The kernel's calls are the custom calls whose result is one query row per
head (``bf16[B*H, 1, D]``).  What a call must read is the cached keys and
values of the tokens the batch holds, taken from the run's own count of live
context at both ends of the slice; it is memory bound.  The operations and
bytes of a cached token come from the counting function the metric file's
``args`` name (``count``, resolved inside ``benchmarks/chip``; by default
``flops.decode_call_dense``: ``2 H D`` elements a token), so a family with
another cache row brings a function and a metric file, not a reader."""

from ...builders import resolve
from ...flops import parse_shapes
from ...harness import log


def read(ctx, count: str = "flops.decode_call_dense"):
    r = ctx.reduced
    a = ctx.scalars.get("context_tokens_at_slice_start")
    b = ctx.scalars.get("context_tokens_at_slice_end")
    if r is None or a is None or b is None:
        return None
    cfg = ctx.model_config
    took, calls = 0.0, 0
    for o in r.ops:
        dims = [d for _, d in parse_shapes(o.shape)]
        if o.is_kernel and len(dims) == 1 and len(dims[0]) == 3 \
                and dims[0][1] == 1:
            took += o.dur
            calls += 1
    if took <= 0:
        return None
    ops, nbytes = resolve(count)(cfg, (a + b) / 2.0)
    least = calls * max(ops / ctx.peaks["bf16_flops"],
                        nbytes / ctx.peaks["hbm_bytes_per_s"])
    log("roofline", kernel="decode_attention", bound="memory", calls=calls,
        context_tokens=round((a + b) / 2.0), least_s=round(least, 6),
        took_s=round(took, 6))
    return 100.0 * least / took
