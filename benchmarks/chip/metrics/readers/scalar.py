"""A number the run counted or clocked itself."""


def read(ctx, name: str, scale: float = 1.0):
    value = ctx.scalars.get(name)
    return None if value is None else value * scale
