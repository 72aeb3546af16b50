"""Collective time (all-gather, all-reduce, reduce-scatter, on the compute
stream or in flight beside it) over the traced window; ``exposed`` keeps
only the part during which the device computes nothing else."""


def read(ctx, exposed: bool = False):
    r = ctx.reduced
    if r is None:
        return None
    t = r.exposed_collective_s() if exposed else r.collective_s()
    return 100.0 * t / r.window_s
