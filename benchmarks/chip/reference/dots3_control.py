#!/usr/bin/env python3
"""Negative controls of the logits check for the selected-latent /
window-latent family (``dots3_note``), at a serving cell's own sizes: the
server runs with a fault planted, the reference as it is on the weights as
drawn, and the two readings of ``compare.py`` say whether ``correct`` would
turn false.

    python3 benchmarks/chip/reference/dots3_control.py \
        --workload <cell> --seed <n> [<n> ...] --fault <name> [<name> ...] \
        [--ticks <n>]
    python3 benchmarks/chip/reference/dots3_control.py \
        --workload <cell> --seed <n> --in-common <prompt length>

It is ``hybrid_ssm_moe_control.py`` (its process, its ``readings``) with this
family's faults, planted by replacing a function of the program for the
server's lifetime.  In the selection: ``latest``: a query attends to the
latest ``index_topk`` tokens, whatever the index says; ``no_relu``: the
index's scores without their ``relu``; ``no_w``: without the heads' weights
(every head counts one); ``bf16_scores``: the index's float32 scores rounded
to bf16 before the top-k (bf16 where float32 is stated).  In the layers:
``no_gate``: the heads' gate left out; ``window_512`` / ``window_514``: the
window off by one, either way; ``ring_other``: a window layer reads the next
window layer's ring.  In the weights: ``zero``: the routed product left out;
``int8``: every matrix of every layer on 255 levels a channel (the nearest
precision below the bf16 the configuration states).  The last line of each
fault is ``CONTROL {...}``.

``UNSEEN`` names what the cell's check does NOT see at the published widths,
kept runnable so that the reading can be made again: the window off by one
(one key of 513), the index's scores rounded to bf16 (it flips a thousandth
of a query's set) and, at this cell's scales, the routed product left out;
what holds the first and the last to the token is
``tests/unit/models/test_sparse_latent_moe.py`` at the tiny size.

``--in-common``: the share of the sets ``S_t`` that the program (bf16,
through its cache: chunked prefill of the prompt, every chunk's selection
recorded) and the reference (float32, a full sort) have in common, over the
queries past ``index_topk`` of one seeded prompt: ``IN_COMMON {...}`` with
the mean and the least over (layer, query), against ``IN_COMMON_LIMIT``.
bf16 index keys and queries flip neighbours at the ``index_topk``-th rank,
so the share is under 1 in a sound run; a selection that went wrong reads
far under the limit (``latest`` reads the window's share of the context).
"""

import contextlib
import dataclasses
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

from benchmarks.chip.reference import hybrid_ssm_moe_control as base  # noqa: E402
from benchmarks.chip.reference.nemotron_h_control import _each_part  # noqa: E402

#: the least share of a query's set the program and the reference must have
#: in common, mean over (layer, query)
IN_COMMON_LIMIT = 0.97


def _int8(params):
    """``base._int8`` where this family stores matrices other than ``[...,
    in, out]``: the up-projections ``[layers, rank, heads, e]`` put out a
    (head, element), ``W_o`` ``[layers, heads, e, d]`` a model dimension."""
    import jax.numpy as jnp

    def rounded(w, over):
        f = w.astype(jnp.float32)
        scale = jnp.maximum(jnp.abs(f).max(axis=over, keepdims=True) / 127.0,
                            1e-30)
        return (jnp.round(f / scale) * scale).astype(w.dtype)

    own = {"wq_b": -3, "wkv_b": -3, "wi_q": -3, "wo": (-3, -2)}
    run = params["runs"][0]
    rest = base._int8({"runs": [{k: v for k, v in run.items()
                                 if k not in own}]})["runs"][0]
    return {**params, "runs": [{**rest, **{
        k: rounded(run[k], over) for k, over in own.items() if k in run}}]}


def _zero_routed(params):
    """The routed product left out, on the runs that hold one."""
    return {**params, "runs": [
        {**run, "w_down": run["w_down"] * 0} if "router" in run else run
        for run in params["runs"]]}


WEIGHTS = {"zero": _each_part(_zero_routed), "int8": _each_part(_int8)}
#: each but ``none`` must read not ``correct`` in the cell's own check (on
#: the chip, seed 2147489003, largest / rms against 0.03 / 0.018: ``none``
#: 0.0108 / 0.0099, ``latest`` 0.143 / 0.124, ``no_relu`` 0.080 / 0.068,
#: ``no_gate`` 0.276 / 0.260, ``ring_other`` 0.077 / 0.071, ``int8`` 0.0333 /
#: 0.0308; ``no_w`` was not run there: PERF.md 6, PR 51)
FAULTS = ("none", "latest", "no_relu", "no_w", "no_gate", "ring_other",
          "int8")
#: planted the same way, and not seen by the cell's check (module
#: docstring): ``zero`` reads 0.0131 / 0.0122 (8 held experts of 256 at an
#: eighth of the scale carry too little of a position's logits),
#: ``bf16_scores`` 0.0105 / 0.0098 with 0.9990 of the sets in common
UNSEEN = ("window_512", "window_514", "bf16_scores", "zero")


def _patches(fault: str):
    """``[(module, name, replacement)]`` of the program's functions."""
    import jax.numpy as jnp
    from jax import lax
    from deepspeed_tpu.models import sparse_latent_moe as model
    from deepspeed_tpu.models import sparse_latent_moe_inference as served
    from deepspeed_tpu.ops.pallas import decode_attention as da
    scores, topk = da.index_scores, da.topk_bias
    if fault == "latest":
        def latest(s, q_pos, k):
            back = jnp.asarray(q_pos, jnp.int32)[..., None] \
                - jnp.arange(s.shape[-1], dtype=jnp.int32)
            return jnp.broadcast_to(jnp.where(
                (back >= 0) & (back < k), 0.0, -jnp.inf), s.shape
            ).astype(jnp.float32)
        return [(da, "topk_bias", latest)]
    if fault == "no_relu":      # x = relu(x) - relu(-x)
        return [(da, "index_scores", lambda q, w, *a, **k:
                 scores(q, w, *a, **k) - scores(-q, w, *a, **k))]
    if fault == "no_w":
        return [(da, "index_scores", lambda q, w, *a, **k:
                 scores(q, jnp.ones_like(w), *a, **k))]
    if fault == "bf16_scores":
        # an explicit rounding: a convert pair is one the compiler may drop
        return [(da, "topk_bias", lambda s, *a, **k: topk(
            lax.reduce_precision(s, exponent_bits=8, mantissa_bits=7),
            *a, **k))]
    if fault == "no_gate":
        return [(model, "head_gate", lambda h, w_gate, cdt: jnp.ones(
            h.shape[:2] + w_gate.shape[-1:], jnp.float32))]
    if fault in ("window_512", "window_514"):
        by = -1 if fault == "window_512" else 1
        bias, ring = da.ring_bias, da.latent_ring_attention
        return [
            (da, "ring_bias", lambda pos, R, window: bias(
                pos, R, window + by)),
            (da, "latent_ring_attention",
             lambda q, r, fresh, pos, window, *a, **k: ring(
                 q, r, fresh, pos, window + by, *a, **k))]
    if fault == "ring_other":
        attend = served.FAMILY.attend_cached

        def other(q, cache, pos, config, idx, *a, ring=False, **k):
            if ring:
                idx = (idx + 1) % cache.ring[0].shape[0]
            return attend(q, cache, pos, config, idx, *a, ring=ring, **k)

        def fresh(q, rows, cache, config, idx, ring=False):
            return other(q, cache, jnp.zeros((), jnp.int32), config, idx,
                         ring=ring, fresh=rows)
        return [(served, "FAMILY", dataclasses.replace(
            served.FAMILY, attend_cached=other, attend_fresh=fresh))]
    return []


@contextlib.contextmanager
def planted(fault: str):
    patches = _patches(fault)
    kept = [(module, name, getattr(module, name))
            for module, name, _ in patches]
    for module, name, fn in patches:
        setattr(module, name, fn)
    try:
        yield
    finally:
        for module, name, fn in kept:
            setattr(module, name, fn)


def program_selections(cfg, params, tokens, chunk: int, max_len: int):
    """The sets the PROGRAM's full layers choose for one row of tokens
    ``[S]``, through its cache: a chunked prefill (``extend`` after
    ``prefill``, as an admission runs them) with every call of ``topk_bias``
    recorded.  ``[full layers, S, max_len]`` bool, row ``t`` of a layer the
    keys query ``t`` attends to."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models import cache_family
    from deepspeed_tpu.ops.pallas import decode_attention as da
    fam = cache_family(cfg)
    S = len(tokens)
    n_full = cfg.cache_layers
    out = np.zeros((n_full, S, max_len), bool)
    calls = []
    topk = da.topk_bias

    def recorded(s, q_pos, k):
        bias = topk(s, q_pos, k)
        jax.debug.callback(
            lambda pos, chosen: calls.append((int(pos[0, 0]),
                                              np.asarray(chosen[0]))),
            jnp.asarray(q_pos), bias == 0, ordered=True)
        return bias

    padded = -(-S // chunk) * chunk
    toks = np.zeros((1, padded), np.int32)
    toks[0, :S] = tokens
    da.topk_bias = recorded
    try:
        cache = fam.init_cache(cfg, 1, max_len)
        first = jax.jit(lambda p, t, c: fam.prefill(p, t, cfg, c)[1])
        more = jax.jit(lambda p, t, c, l: fam.extend(p, t, cfg, c,
                                                     lengths=l)[1])
        for at in range(0, padded, chunk):
            piece = jnp.asarray(toks[:, at:at + chunk])
            cache = first(params, piece, cache) if at == 0 else \
                more(params, piece, cache, jnp.asarray([at], jnp.int32))
        jax.block_until_ready(cache)
        jax.effects_barrier()
    finally:
        da.topk_bias = topk
    # a chunk's calls come in depth order, one a full layer
    seen = {}
    for pos, chosen in calls:
        layer = seen.get(pos, 0)
        seen[pos] = layer + 1
        n = min(chunk, S - pos)
        out[layer, pos:pos + n] = chosen[:n]
    return out


def in_common(cell, seed: int, n: int) -> dict:
    """The share of ``S_t`` in common, program against reference, over the
    queries past ``index_topk`` of one seeded prompt of ``n`` tokens."""
    import jax
    import jax.numpy as jnp
    from benchmarks.chip.builders import resolve
    cfg = resolve(cell.config["builder"])(cell.config)
    init = resolve(cell.config["init"])
    params = jax.jit(lambda key: init(cfg, key, jnp.bfloat16))(
        jax.random.PRNGKey(seed))
    tokens = np.random.default_rng(seed + 7).integers(
        0, cfg.vocab_size, n).astype(np.int32)
    serving = cell.traffic["serving"]
    max_len = -(-n // int(serving["prefill_chunk"])) \
        * int(serving["prefill_chunk"])
    got = program_selections(cfg, params, tokens,
                             int(serving["prefill_chunk"]), max_len)
    want = np.asarray(jax.jit(lambda p, t: resolve(
        cell.config["reference"]).selections(cell.config, p, t))(
            params, tokens[None]))
    return shares(got[:, :, :n], want, cfg.index_topk)


def shares(got, want, topk: int) -> dict:
    """``mean`` and ``least`` over (layer, query past ``topk``) of ``|got &
    want| / |want|``; 1.0 both where no query is past it."""
    rows = slice(topk, None)
    both = (got[:, rows] & want[:, rows]).sum(-1)
    size = np.maximum(want[:, rows].sum(-1), 1)
    share = both / size
    if share.size == 0:
        return {"mean": 1.0, "least": 1.0, "queries": 0}
    return {"mean": float(share.mean()), "least": float(share.min()),
            "queries": int(share.size)}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--in-common" in argv:
        import argparse
        ap = argparse.ArgumentParser()
        ap.add_argument("--workload", required=True)
        ap.add_argument("--seed", type=int, nargs="+", required=True)
        ap.add_argument("--in-common", type=int, required=True)
        ap.add_argument("--fault", default="none",
                        choices=FAULTS + UNSEEN)
        args = ap.parse_args(argv)
        from benchmarks.chip import harness
        from deepspeed_tpu.utils.platform import (enable_compile_cache,
                                                  require_tpu)
        require_tpu()
        enable_compile_cache()
        cell = harness.load_cell(ROOT, args.workload)
        for seed in args.seed:
            with planted(args.fault):
                out = in_common(cell, seed, args.in_common)
            print("IN_COMMON " + json.dumps({
                "workload": args.workload, "seed": seed,
                "fault": args.fault, "prompt": args.in_common, **out,
                "limit": IN_COMMON_LIMIT,
                "agree": out["mean"] >= IN_COMMON_LIMIT}), flush=True)
        return 0
    # this process plants this family's faults only
    base.WEIGHTS, base.FAULTS, base.planted = WEIGHTS, FAULTS + UNSEEN, \
        planted
    return base.main(argv)


if __name__ == "__main__":
    sys.exit(main())
