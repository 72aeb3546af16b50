"""The single-token sweeps of a traced program, read off its jaxpr: which
kernel, the block its body is built for, and the block its work list was
built for.  Test code only."""


def _deep(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            v = getattr(v, "jaxpr", v)
            if hasattr(v, "eqns"):
                yield from _deep(v)


def sweep_calls(jaxpr, slots, smax, width):
    """``(kernel, block of the kernel, block of its work list)`` of every
    single-token sweep in a traced program: the kernel's block is the rows
    of the largest ``width``-wide buffer its body is handed (a pipelined
    block or its own copy buffers), the list's follows from its length,
    ``slots * smax / block`` entries."""
    found = []
    for eqn in _deep(jaxpr):
        name = eqn.params.get("name") or ""
        if eqn.primitive.name != "pallas_call" or \
                not name.endswith("decode_attention"):
            continue
        # (a rank-4 operand is the pool itself, handed over whole)
        block = max(v.aval.shape[-2] for v in eqn.params["jaxpr"].invars
                    if len(v.aval.shape) in (2, 3)
                    and v.aval.shape[-1] == width)
        # the list leads the operands (behind the grid's bound, a scalar,
        # where the lowering is the chip's and the bound dynamic)
        entries = next(v.aval.shape[0] for v in eqn.invars
                       if len(v.aval.shape) == 1)
        found.append((name, block, slots * smax // entries))
    return found
