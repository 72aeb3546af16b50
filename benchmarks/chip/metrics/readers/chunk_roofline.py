"""The chunk-attention kernel's share of its roofline in the traced slice:
the prompt passes of the admissions.

Its calls are the Pallas custom calls whose one result is a chunk of query
rows per head: rank 3, ``[B * heads, chunk, head_dim]``, or, where a step
takes a key-value head's whole group, rank 4, ``[B * kv heads, group, chunk,
head_dim]``; the dimension before the last is the chunk's (a single-token
sweep's is 1).  What a call must do depends on its layer's kind and on where its
chunk stands in the prompt, which a trace does not say; the program's own
``serve.prefill`` spans do (``start``, ``chunks`` and ``chunk`` of every
admission, one call a layer a chunk).  So the least time of the slice's
calls is their number times the mean over the calls of ALL the window's
admissions, operations and bytes from the counting function the metric file
names: ``f(config, [(start, chunks, chunk), ...]) -> (operations, bytes,
calls)``."""

from ...builders import resolve
from ...flops import parse_shapes
from ...harness import log
from ._window import spans_starting_in_window


def _is_chunk_pass(o) -> bool:
    dims = [d for _, d in parse_shapes(o.shape)]
    return o.is_kernel and len(dims) == 1 and len(dims[0]) in (3, 4) \
        and dims[0][-2] > 1


def read(ctx, count: str):
    r = ctx.reduced
    spans = spans_starting_in_window(ctx, "serve.prefill")
    if r is None or r.busy_s <= 0 or not spans:
        return None
    took = r.ops_time(_is_chunk_pass)
    if took <= 0:
        return None
    found = sum(_is_chunk_pass(o) for o in r.ops) / len(r.devices)
    ops, nbytes, calls = resolve(count)(ctx.model_config, [
        (s.args["start"], s.args["chunks"], s.args["chunk"]) for s in spans])
    share = found / calls
    least = share * max(ops / ctx.peaks["bf16_flops"],
                        nbytes / ctx.peaks["hbm_bytes_per_s"])
    log("roofline", kernel="chunk_attention",
        bound="compute" if ops / ctx.peaks["bf16_flops"]
        >= nbytes / ctx.peaks["hbm_bytes_per_s"] else "memory",
        calls=round(found), admissions=len(spans), least_s=round(least, 6),
        took_s=round(took, 6))
    return 100.0 * least / took
