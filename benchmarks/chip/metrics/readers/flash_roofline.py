"""The flash-attention kernels' share of their roofline in the train step.

Operations and bytes of every flash custom call in the slice come from its
result shape (``flops.flash_call``); the least time the chip could take is
the larger of operations over peak FLOP/s and bytes over peak bytes/s, summed
over the calls, over the time the calls took."""

from ...flops import flash_call
from ...harness import log


def read(ctx, causal: bool = True):
    r = ctx.reduced
    if r is None:
        return None
    took = least_flops = least_bytes = 0.0
    for o in r.ops:
        if not o.is_kernel:
            continue
        ops, nbytes = flash_call(o.shape, causal)
        took += o.dur
        least_flops += ops / ctx.peaks["bf16_flops"]
        least_bytes += nbytes / ctx.peaks["hbm_bytes_per_s"]
    if took <= 0:
        return None
    log("roofline", kernel="flash", bound=(
        "compute" if least_flops >= least_bytes else "memory"),
        least_s=round(max(least_flops, least_bytes), 6), took_s=round(took, 6))
    return 100.0 * max(least_flops, least_bytes) / took
