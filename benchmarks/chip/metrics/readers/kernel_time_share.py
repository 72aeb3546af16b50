"""Time in Pallas kernels (``tpu_custom_call``) over the device's busy
time."""


def read(ctx):
    r = ctx.reduced
    if r is None or r.busy_s <= 0:
        return None
    return 100.0 * r.ops_time(lambda o: o.is_kernel) / r.busy_s
