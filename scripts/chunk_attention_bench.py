#!/usr/bin/env python3
"""The chunk-attention kernel alone, on the chip, at the serving cells'
admission shapes: device time a call (the kernel's custom calls in a profiler
trace) for several tiles in one process.  What ``chunk_block_k``'s docstring
and PERF.md 6 (PR 49) quote was measured with it.

    chiprun -- python scripts/chunk_attention_bench.py [--cells code,agent]
        [--tiles rule,2048x256,1024x1024] [--parent DIR]
    chiprun -- python scripts/chunk_attention_bench.py \
        --cells docqa,reason,think,longgen [--tiles rule,h1,h4] [--parent DIR]

``--tiles``: ``rule`` is the tree's own ``chunk_block_q`` / ``chunk_block_k``;
``ROWSxKEYS`` overrides them for the run (the most query rows a step and the
keys a block; a slot the keys do not tile takes the next size down).
``--parent DIR``: also time ``DIR``'s ``decode_attention.py`` (another
checkout's kernel) on the same inputs and compare the results.

``--in-place`` (PR 63; the cells of ``CELLS``): a chunk's call as an
admission's layer makes it, on the STACKED banks: layer 1 of a batch-1 row
cache ``[2, 1, Smax, Hkv*D]`` (the layer sliced out and re-laid heads-major
around the kernel: the row-cache path) against ``(layer 1, row 2)`` of a pool
``[2, 4, Smax, Hkv*D]`` read where it lies (``cached_attention(row=)``), with
``--lanes 1,2,8``: the lane blocks a step of ``_row_chunk`` takes, and
``--keys 512,256``: the keys of its blocks (0: the tree's rule).  Beside
the kernel's own time each line gives the program's whole device time a
call: what the relay around the kernel costs is their difference.

The LATENT cells (``docqa``, ``reason``, ``think``, ``longgen``: ``LATENT``)
time the latent chunk kernel's one custom call at a sublayer's shapes over a
prompt's chunk positions: un-absorbed queries and the layer's ``W_kvb``
through ``latent_cached_attention(up=)``, whose form the call's shape picks.
``longgen`` is two rows, the selecting family's two kinds of layer, each
call under its bias: ``longgen-full`` under an exact selection of 2,048 keys
a query (``topk_bias`` of random scores), ``longgen-window`` the tree's own
``latent_ring_attention`` over a ring of 640 cells and the chunk under the
band of 513.  A tile ``h<n>`` holds ``n`` heads a step of the up-projected
kernel (``latent_up_tiles``; ``h<n>q<rows>`` also scores ``rows`` positions
at a time); a tree with no ``LatentUp`` (a ``--parent`` from before PR 58,
and a ring's call of one from before PR 60) is handed the absorbed queries
and up-projects its result outside, as its model did, and only its kernel is
timed (a tree from before PR 60 absorbs a selection's queries itself).

Chip only: a time is a chip's (``utils.platform.require_tpu``)."""

import argparse
import glob
import importlib.util
import inspect
import json
import os
import sys
import tempfile
from typing import NamedTuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.pallas import decode_attention as da

REPS = 8


def _calls(chunk, smax, positions, ring=None):
    """A prompt's chunk calls as ``(weight, kwargs)``: over whole rows at
    each position and, with ``ring = (R, window layers a full layer)``, over
    the unrolled ring beside the chunk."""
    out = []
    for p in positions:
        out.append((1, dict(Smax=smax, pos=p)))
        if ring:
            R, share = ring
            out.append((share, dict(Smax=R + chunk, pos=R, window=R,
                                    valid_from=max(R - p, 0))))
    return out


#: cell -> (B, Sq, H, Hkv, D), its calls
CELLS = {
    # mellum2-serve-code-sat: 7 full layers of 8,192, 21 rings of 1,024
    "code": ((1, 1024, 32, 4, 128),
             _calls(1024, 8192, (0, 1024, 2048, 3072), ring=(1024, 3))),
    # nemotron3n-serve-agent-sat: 16 query heads a key-value head
    "agent": ((1, 1024, 32, 2, 128),
              _calls(1024, 16384, (0, 1024, 2048, 3072, 4096, 5120))),
    # granite4h-serve-rag-sat
    "rag": ((1, 512, 32, 8, 128), _calls(512, 5120, (0, 512, 1024))),
    # gpt2-medium, both serving cells: ungrouped heads of 64
    "gpt2m": ((1, 128, 16, 16, 64), _calls(128, 1024, (0, 128))),
    # ... and its ladder's wide pass over a document's positions
    "gpt2m-wide": ((1, 256, 16, 16, 64), _calls(256, 1024, (0, 256, 512))),
    # a verify's few positions under grouped heads: 8-row tiles of bf16
    "verify": ((1, 8, 32, 4, 128), _calls(8, 2048, (1024, 1531))),
}


class Widths(NamedTuple):
    """A layer's latent attention: rank, a head's key part as the head-major
    copy has it (whole lane rows), rotary part, value, the row's lanes."""
    rank: int = 512
    nope: int = 128
    rope: int = 64
    v: int = 128
    row: int = 640


#: cell -> (Sq, heads, slot length), its chunk positions (with a weight each
#: where they are not equally many), its widths, the bias of its calls
LATENT = {
    # longcat-serve-docqa-sat: 2,560-3,584 tokens in chunks of 512
    "docqa": ((512, 64, 6144), (0, 512, 1024, 1536, 2048, 2560, 3072),
              Widths(), None),
    # kimik2-serve-reason-sat: 1,024-4,000 tokens in chunks of 512
    "reason": ((512, 64, 8192), (0, 512, 1024, 1536, 2048, 2560), Widths(),
               None),
    # kimilin-serve-think-sat: 1,536-2,560 tokens in chunks of 1,024
    "think": ((1024, 32, 8192), (0, 1024, 2048), Widths(), None),
    # dots3n-serve-longgen-sat: 5,120-7,168 tokens in chunks of 1,024, a full
    # layer's call under the selection of 2,048
    "longgen-full": ((1024, 128, 16384),
                     (0, 1024, 2048, 3072, 4096, 5120, 6144), Widths(),
                     "selection"),
    # ... and a window layer's over its ring of 640 cells and the chunk,
    # under the band of 513: the first chunk meets an empty ring, the five
    # or six that follow a full one
    "longgen-window": ((1024, 64, 640), ((1, 0), (5, 1024)),
                       Widths(1024, 256, 64, 128, 1152), "band"),
}
#: a cell of more than one kind of layer -> its rows of ``LATENT``
KINDS = {"longgen": ("longgen-full", "longgen-window")}
_TOPK, _WINDOW = 2048, 513


def _latent_program(mod, geo, pos, widths=Widths(), bias=None):
    """One sublayer's chunk call at prompt position ``pos``: layer 1 of a
    pool of two through layer 1 of a stack of two.  ``bias``: None; a
    ``"selection"`` of ``_TOPK`` keys a query (``topk_bias`` of random
    scores); or a ``"band"``: the pool is a ring of ``Smax`` cells, and the
    call the tree's own ``latent_ring_attention`` over it and the chunk's
    rows (how long it unrolls them is the tree's)."""
    Sq, H, Smax = geo
    rank, nope, rope, v, row = widths
    keys = jax.random.split(jax.random.PRNGKey(Smax + pos), 4)
    q = jax.random.normal(keys[0], (1, Sq, H, nope + rope), jnp.bfloat16)
    bank = jax.random.normal(keys[1], (2, 1, Smax, row), jnp.bfloat16)
    bank = bank.at[..., rank + rope:].set(0)
    w = (jax.random.normal(keys[2], (2, H, rank, nope + v), jnp.float32)
         * rank ** -0.5).astype(jnp.bfloat16)
    scale = (nope + rope) ** -0.5
    up = hasattr(mod, "LatentUp")
    if bias == "band":
        fresh = jax.random.normal(keys[3], (1, Sq, row), jnp.bfloat16)
        fresh = fresh.at[..., rank + rope:].set(0)
        up = "up" in inspect.signature(mod.latent_ring_attention).parameters

        def attend(queries, bank, pos, **up):
            return mod.latent_ring_attention(queries, bank, fresh, pos,
                                             _WINDOW, 1, scale, rank, **up)
    else:
        biased = {}
        if bias == "selection":
            scores = jax.random.normal(keys[3], (1, Sq, Smax), jnp.float32)
            biased = {"bias": da.topk_bias(
                scores, pos + jnp.arange(Sq)[None], _TOPK)}

        def attend(queries, bank, pos, **up):
            return mod.latent_cached_attention(queries, bank, pos, scale,
                                               rank, layer=1, **biased, **up)

    def up_projected(q, bank, w, pos):
        return attend(q, bank, pos, up=mod.LatentUp(w, jnp.int32(1), nope))

    def absorbed(q, bank, w, pos):
        q_abs = jnp.einsum("bshe,hre->bshr", q[..., :nope], w[1, ..., :nope])
        queries = jnp.pad(jnp.concatenate([q_abs, q[..., nope:]], -1),
                          ((0, 0),) * 3 + ((0, row - rank - rope),))
        return jnp.einsum("bshr,hre->bshe", attend(queries, bank, pos),
                          w[1, ..., nope:])

    fn = jax.jit(up_projected if up else absorbed)
    return fn, (q, bank, w, jnp.full((1,), pos, jnp.int32))


def _other_tree(path):
    """``decode_attention.py`` of another checkout as a module of its own
    (its ``.utils`` is this tree's)."""
    spec = importlib.util.spec_from_file_location(
        "deepspeed_tpu.ops.pallas._other_decode_attention",
        os.path.join(path, "deepspeed_tpu/ops/pallas/decode_attention.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def _program(mod, geo, call):
    B, Sq, H, Hkv, D = geo
    keys = jax.random.split(jax.random.PRNGKey(call["Smax"] + call["pos"]), 3)
    q = jax.random.normal(keys[0], (B, Sq, H, D), jnp.bfloat16)
    k, v = (jax.random.normal(key, (B, call["Smax"], Hkv, D), jnp.bfloat16)
            for key in keys[1:])
    first = jnp.full((B,), call.get("valid_from", 0), jnp.int32)
    fn = jax.jit(lambda q, k, v, pos, first: mod.cached_attention(
        q, k, v, pos, window=call.get("window"), kv_heads=Hkv,
        valid_from=first if "valid_from" in call else None))
    return fn, (q, k, v, jnp.full((B,), call["pos"], jnp.int32), first)


def _stacked_program(geo, call, row):
    """A layer's chunk call on stacked banks: ``row`` None: layer 1 of a
    batch-1 row cache; else ``(layer 1, row)`` of a pool of four rows."""
    B, Sq, H, Hkv, D = geo
    keys = jax.random.split(jax.random.PRNGKey(call["Smax"] + call["pos"]), 3)
    q = jax.random.normal(keys[0], (B, Sq, H, D), jnp.bfloat16)
    # every row of the pool holds the row cache's row: one result to compare
    k, v = (jnp.tile(jax.random.normal(key, (2, 1, call["Smax"], Hkv * D),
                                       jnp.bfloat16),
                     (1, 1 if row is None else 4, 1, 1)) for key in keys[1:])
    at = {} if row is None else {"row": jnp.int32(row)}
    fn = jax.jit(lambda q, k, v, pos: da.cached_attention(
        q, k, v, pos, kv_heads=Hkv, layer=jnp.int32(1), **at))
    return fn, (q, k, v, jnp.full((B,), call["pos"], jnp.int32))


def _kernel_ms(programs):
    """Median device milliseconds of each program's one Pallas call, the
    call's result, and the mean milliseconds of ALL a program's device ops a
    call (the relay around the kernel with it)."""
    from benchmarks.chip.trace.reduce import _OPS_LINE, read_device_ops
    with tempfile.TemporaryDirectory() as logdir:
        jax.profiler.start_trace(logdir)
        for fn, args in programs:
            for _ in range(REPS):
                out = fn(*args)
            out.block_until_ready()
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(
            logdir, "plugins/profile/*/*.xplane.pb"))[-1]
        every = sorted((o for o in read_device_ops(path)
                        if o.line == _OPS_LINE), key=lambda o: o.start)
    ops = [o for o in every if o.is_kernel]
    assert len(ops) == REPS * len(programs), len(ops)
    ms = [1e3 * float(np.median([o.dur for o in ops[i:i + REPS]]))
          for i in range(0, len(ops), REPS)]
    # a program's ops: those since the kernel call before its first
    edges = [0.0] + [ops[i - 1].end for i in range(REPS, len(ops), REPS)] \
        + [float("inf")]
    total = [1e3 * sum(o.dur for o in every if lo <= o.start < hi) / REPS
             for lo, hi in zip(edges, edges[1:])]
    return ms, ops[0].shape, total


def _in_place(cells, lanes, keys):
    """``--in-place``: see the module's docstring."""
    rule, rule_k = da.row_chunk_blocks, da.chunk_block_k
    for cell in cells:
        geo, calls = CELLS[cell]
        want = {}
        for n, k in [(None, None)] + [(n, k) for n in lanes for k in keys]:
            name = "row_cache" if n is None else f"pool_lanes{n}_keys{k}"
            da.row_chunk_blocks = rule if not n else (
                lambda lanes, kw, *a, n=n: min(n, lanes // kw))
            da.chunk_block_k = rule_k if not k else (lambda Smax, k=k: k)
            programs = [_stacked_program(geo, call, None if n is None else 2)
                        for _, call in calls if "window" not in call]
            worst = 0.0
            for i, (fn, a) in enumerate(programs):
                got = np.asarray(fn(*a), np.float32)
                worst = max(worst, float(np.abs(
                    got - want.setdefault(i, got)).max()))
            ms, shape, total = _kernel_ms(programs)
            print(json.dumps({
                "cell": cell, "path": name, "result": shape,
                "max_err": round(worst, 5),
                "kernel_ms_a_call": round(float(np.mean(ms)), 4),
                "program_ms_a_call": round(float(np.mean(total)), 4),
                "calls": [[c["Smax"], c["pos"], round(t, 4), round(w, 4)]
                          for (_, c), t, w in zip(
                              [x for x in calls if "window" not in x[1]],
                              ms, total)]}), flush=True)
    da.row_chunk_blocks, da.chunk_block_k = rule, rule_k


def main():
    from deepspeed_tpu.utils.platform import require_tpu
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cells", default=",".join(CELLS), help="of " + ", ".join(
        list(CELLS) + list(LATENT) + list(KINDS)))
    ap.add_argument("--tiles", default="rule")
    ap.add_argument("--parent")
    ap.add_argument("--in-place", action="store_true")
    ap.add_argument("--lanes", default="1,2,8")
    ap.add_argument("--keys", default="0", help="key blocks of the pool's "
                    "call (0: the tree's rule)")
    args = ap.parse_args()
    require_tpu()
    if args.in_place:
        return _in_place(args.cells.split(","),
                         [int(n) for n in args.lanes.split(",")],
                         [int(n) for n in args.keys.split(",")])
    rule_k, rule_q = da.chunk_block_k, da.chunk_block_q
    variants = [("parent", _other_tree(args.parent))] if args.parent else []
    variants += [(tile, da) for tile in args.tiles.split(",")]
    rule_up = da.latent_up_tiles
    far = False     # a variant's result further than 0.05 from the first's
    for cell in (row for cell in args.cells.split(",")
                 for row in KINDS.get(cell, (cell,))):
        latent = cell in LATENT
        if latent:
            geo, calls, widths, bias = LATENT[cell]
            calls = [(w, dict(Smax=geo[2], pos=p)) for w, p in (
                p if isinstance(p, tuple) else (1, p) for p in calls)]
        else:
            geo, calls = CELLS[cell]
        want = {}
        for name, mod in variants:
            da.chunk_block_k, da.chunk_block_q = rule_k, rule_q
            da.latent_up_tiles = rule_up
            if latent and name.startswith("h"):
                n, _, rows = name[1:].partition("q")
                da.latent_up_tiles = lambda *a, n=int(n), rows=rows, **kw: \
                    (n, int(rows) if rows else rule_up(*a, **kw)[1])
            elif "x" in name:
                rows, keys = (int(n) for n in name.split("x"))
                da.chunk_block_k = lambda Smax: next(
                    b for b in (2048, 1024, 512, 256, 128)
                    if b <= keys and Smax % b == 0)
                da.chunk_block_q = lambda Sq, G, block_k: next(
                    b for b in (256, 128, 64, 32, 16, 8)
                    if Sq % b == 0 and (G * b <= rows or b == 8))
            programs = [_latent_program(mod, geo, call["pos"], widths, bias)
                        if latent else _program(mod, geo, call)
                        for _, call in calls]
            worst = 0.0
            for i, (fn, a) in enumerate(programs):    # compile, and compare
                got = np.asarray(fn(*a), np.float32)
                worst = max(worst, float(np.abs(
                    got - want.setdefault(i, got)).max()))
            far |= worst >= 0.05
            ms, shape, _ = _kernel_ms(programs)
            weights = [w for w, _ in calls]
            print(json.dumps({
                "cell": cell, "tile": name, "result": shape,
                "max_err": round(worst, 5),
                "ms_a_call": round(float(np.average(ms, weights=weights)), 4),
                "calls": [[c["Smax"], c["pos"], c.get("valid_from"),
                           round(t, 4)] for (_, c), t in zip(calls, ms)]}),
                flush=True)
    sys.exit(1 if far else 0)


if __name__ == "__main__":
    main()
