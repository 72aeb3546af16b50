#!/usr/bin/env python3
"""Negative controls of the logits check for the shortcut-connected
double-layer family (LongCat-Flash), at a serving cell's own sizes: the
server runs with a fault planted, the reference as it is on the weights as
drawn, and the two readings of ``compare.py`` say whether ``correct`` would
turn false.

    python3 benchmarks/chip/reference/longcat_flash_control.py \
        --workload <cell> --seed <n> [<n> ...] --fault <name> [<name> ...]
    python3 benchmarks/chip/reference/longcat_flash_control.py \
        --workload <cell> --seed <n> --in-common <prompt length> \
        [--fault <name>]

It is ``hybrid_ssm_moe_control.py``'s process and ``readings`` (draw the
weights, plant the fault, build the cell's server, take the check's logits
through its slot path, free the server, draw the weights again and run the
reference) with this family's faults: in the weights, or planted by
replacing a function or a constant of ``models/shortcut_latent_moe.py``,
``moe/held_experts.py`` or ``ops/pallas/decode_attention.py`` for the
server's lifetime; a run of the benchmark plants nothing.  The last line of
each fault is ``CONTROL {...}``.

Faults (``FAULTS``): ``none``; ``zero``: the held experts' down-projections
zeroed (the routed product left out); ``no_identity``: the zero-compute
experts' part left out (their pairs weigh nothing); ``permute``: each
layer's held experts hand their down-projection to the next one (a wrong
group of the grouped product); ``shortcut_early``: the expert branch joins
the stream after the FIRST dense FFN instead of the second;
``no_kv_scale``: ``mla_scale_kv_lora`` left out (keys and values from the
unscaled latent); ``cache_other``: sublayer 1's attention reads sublayer 0's
cache layer; ``int8``: every matrix of every layer, both attentions, both
dense FFNs, the held experts and the router, rounded to 255 levels of its
largest magnitude per output channel (the nearest precision below the bf16
the configuration states); ``bf16_router``: the router's logits, softmax and
choice in bf16 where the file says float32.

``--in-common``: the share of the 12 router outputs a token's gate chooses
that the program (bf16 through its cache: a chunked prefill of one seeded
prompt, every gate's choice recorded) and the reference (float32) have in
common: ``IN_COMMON {...}`` with the mean over (layer, token), each layer's
mean and the least, the FIRST layer's held to ``IN_COMMON_LIMIT``.  The
gate's scores are crowded (12 of 768 outputs a hundredth of a probability
apart) and the stream the router reads is a bf16 program's, so a sound run
is under 1 and falls with depth; the first layer's router reads a stream
one attention old, where what is left is the gate's own precision.

``--router-std`` / ``--routed-down-scale`` / ``--up-std`` / ``--embed-std``
draw the weights at other scales than ``longcat_flash_family``'s (program
and reference alike): how its four scales were chosen (PERF.md 6, PR 57).

READINGS
"""

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

from benchmarks.chip.reference import hybrid_ssm_moe_control as base  # noqa: E402
from benchmarks.chip.reference.latent_moe_control import _rounded  # noqa: E402


def _routed_down(fn):
    """``fn`` on the held experts' down-projections ``[layers, held, f,
    d]``."""
    def planted(params):
        blocks = params["blocks"]
        moe = blocks["moe"]
        return {**params, "blocks": {**blocks, "moe": {
            **moe, "w_down": fn(moe["w_down"])}}}
    return planted


def _roll_experts(w):
    import jax.numpy as jnp
    return jnp.roll(w, 1, 1)


def _int8(params):
    """Every matrix of the layers ``[layers, ..., in, out]`` (the
    up-projections and ``wo`` by ``latent_moe_control._INPUT_AXES``) on 255
    levels a channel; norms and the selection bias are no matrices."""
    return {**params, "blocks": {
        part: {k: _rounded(k, v) if v.ndim >= 3 else v
               for k, v in stack.items()}
        for part, stack in params["blocks"].items()}}


#: fault -> what it does to the weights (the others replace functions)
WEIGHTS = {"zero": _routed_down(lambda w: w * 0),
           "permute": _routed_down(_roll_experts), "int8": _int8}
#: each but ``none`` and ``UNSEEN``'s reads not ``correct`` in the cell's own
#: check (on the chip, largest / rms against 0.03 / 0.018, my chip runs, PR
#: 57; seed 2147612057: ``none`` 0.0087 / 0.0074, ``zero`` 0.058 / 0.046,
#: ``no_identity`` 0.105 / 0.107, ``permute`` 0.074 / 0.046,
#: ``shortcut_early`` 0.065 / 0.057, ``no_kv_scale`` 0.078 / 0.072,
#: ``cache_other`` 0.076 / 0.073; seeds 2147640013 and 2147641027: ``none``
#: 0.0086 / 0.0078 and 0.0185 / 0.0096, ``int8`` 0.0331 / 0.0309 and 0.0370 /
#: 0.0315: PERF.md 6)
FAULTS = ("none", "zero", "no_identity", "permute", "shortcut_early",
          "no_kv_scale", "cache_other", "int8", "bf16_router")
#: runnable, and read as a sound run by the cell's check at the published
#: widths (0.0073 / 0.0075 beside a sound 0.0087 / 0.0074: my chip runs, PR
#: 57): what ``correct`` does not judge, and ``--in-common`` does
UNSEEN = ("bf16_router",)
#: the least share of a token's 12 choices the program's FIRST layer and the
#: reference's must have in common, mean over the prompt's tokens.  On the
#: chip (1,100 tokens, seed 2147643041; by layer, then the mean): a sound run
#: 0.99977 0.99561 0.99379 0.99212, 0.99532; ``bf16_router`` 0.98970 0.98932
#: 0.98720 0.98682, 0.98826: a sound first layer flips 2 choices in 10,000
#: and a bf16 gate 103, the limit 50.
IN_COMMON_LIMIT = 0.995


def _bf16_router(h, w_router, bias, k, scale):
    """``held_experts.route_softmax_all`` with every step in bf16: the
    logits from rounded inputs, the softmax, the sum with the bias and the
    choice."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from deepspeed_tpu.moe.held_experts import Routing
    # an explicit rounding: a convert to bf16 and back is one the compiler
    # may drop (``xla_allow_excess_precision``), and on the chip it does
    rounded = lambda t: lax.reduce_precision(t.astype(jnp.float32),
                                             exponent_bits=8, mantissa_bits=7)
    logits = rounded(jnp.dot(rounded(h), rounded(w_router),
                             precision=lax.Precision.HIGHEST))
    p = rounded(jax.nn.softmax(logits, axis=-1))
    _, experts = lax.top_k(rounded(p + rounded(bias)), k)
    return Routing(experts.astype(jnp.int32),
                   jnp.take_along_axis(p, experts, axis=-1) * scale)


def _patches(fault: str) -> dict:
    """``(module, name) -> replacement``."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import latent_moe
    from deepspeed_tpu.models import shortcut_latent_moe as model
    from deepspeed_tpu.moe import held_experts
    from deepspeed_tpu.ops.pallas import decode_attention
    if fault == "no_identity":
        weight = held_experts.zero_weight

        def nothing(routing, n_experts):
            w, n = weight(routing, n_experts)
            return jnp.zeros_like(w), n
        return {(held_experts, "zero_weight"): nothing}
    if fault == "shortcut_early":
        return {(model, "SHORTCUT_JOINS_AFTER"): 0}
    if fault == "no_kv_scale":
        return {(model, "project"):
                lambda x, p, config, positions: latent_moe.latent_project(
                    x, p, config, positions, config.q_scale, 1.0)}
    if fault == "cache_other":
        attention = decode_attention.cached_attention

        def other(q, k, v, pos, *, layer, **kw):
            return attention(q, k, v, pos, layer=layer - layer % 2, **kw)
        return {(decode_attention, "cached_attention"): other}
    if fault == "bf16_router":
        return {(model, "route_softmax_all"): _bf16_router}
    return {}


@contextlib.contextmanager
def planted(fault: str):
    patches = _patches(fault)
    kept = {at: getattr(*at) for at in patches}
    for (module, name), fn in patches.items():
        setattr(module, name, fn)
    try:
        yield
    finally:
        for (module, name), fn in kept.items():
            setattr(module, name, fn)


def program_choices(cfg, params, tokens, chunk: int):
    """``[layers, S, k]`` int32: the router outputs the PROGRAM's gates
    choose for one row of tokens ``[S]``, through its cache: a chunked
    prefill (``extend`` after ``prefill``, as an admission runs them) with
    every call of the gate recorded (whatever fault is planted on it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deepspeed_tpu.models import cache_family
    from deepspeed_tpu.models import shortcut_latent_moe as model
    fam = cache_family(cfg)
    S = len(tokens)
    padded = -(-S // chunk) * chunk
    calls = []
    gate = model.route_softmax_all

    def recorded(h, *a):
        routing = gate(h, *a)
        jax.debug.callback(lambda e: calls.append(np.asarray(e)),
                           routing.experts, ordered=True)
        return routing

    toks = np.zeros((1, padded), np.int32)
    toks[0, :S] = tokens
    model.route_softmax_all = recorded
    try:
        cache = fam.init_cache(cfg, 1, padded)
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
        model.route_softmax_all = gate
    # a chunk's calls come in depth order, one a layer
    L = cfg.n_layer
    assert len(calls) == L * (padded // chunk), len(calls)
    return np.concatenate(
        [np.stack(calls[c * L:(c + 1) * L])
         for c in range(padded // chunk)], axis=1)[:, :S]


def shares(got, want) -> dict:
    """``mean`` over (layer, token) of ``|got & want| / k`` for two ``[layers,
    S, k]`` sets of ids, each layer's mean, and the least."""
    import numpy as np
    both = (got[..., :, None] == want[..., None, :]).any(-1).mean(-1)
    by_layer = [float(v) for v in both.mean(-1)]
    return {"mean": float(both.mean()), "least": float(both.min()),
            "by_layer": by_layer, "tokens": int(both.shape[-1]),
            "limit": IN_COMMON_LIMIT, "agree": by_layer[0] >= IN_COMMON_LIMIT}


def in_common(cell, seed: int, n: int, fault: str) -> dict:
    """The share of the gates' choices in common, the program (``fault``
    planted) against the reference, over one seeded prompt of ``n``
    tokens."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.chip.builders import resolve
    cfg = resolve(cell.config["builder"])(cell.config)
    init = resolve(cell.config["init"])
    params = jax.jit(lambda key: init(cfg, key, jnp.bfloat16))(
        jax.random.PRNGKey(seed))
    tokens = np.random.default_rng(seed + 7).integers(
        0, cfg.vocab_size, n).astype(np.int32)
    with planted(fault):
        got = program_choices(cfg, params, tokens,
                              int(cell.traffic["serving"]["prefill_chunk"]))
    want = np.asarray(jax.jit(lambda p, t: resolve(
        cell.config["reference"]).choices(cell.config, p, t))(
            params, tokens[None]))[0]
    return shares(got, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--fault", nargs="+", choices=FAULTS, required=True)
    ap.add_argument("--router-std", type=float, default=None)
    ap.add_argument("--routed-down-scale", type=float, default=None)
    ap.add_argument("--up-std", type=float, default=None)
    ap.add_argument("--embed-std", type=float, default=None)
    ap.add_argument("--in-common", type=int, default=None,
                    help="prompt length: read the gates' choices in common "
                         "instead of the logits")
    args = ap.parse_args(argv)
    from benchmarks.chip import harness, longcat_flash_family as family
    from benchmarks.chip.reference import compare
    from deepspeed_tpu.utils.platform import enable_compile_cache, require_tpu
    require_tpu()
    enable_compile_cache()
    if args.router_std is not None:
        family.ROUTER_STD = args.router_std
    if args.routed_down_scale is not None:
        family.ROUTED_DOWN_SCALE = args.routed_down_scale
    if args.up_std is not None:
        family.UP_STD = args.up_std
    if args.embed_std is not None:
        family.EMBED_STD = args.embed_std
    cell = harness.load_cell(ROOT, args.workload)
    if args.in_common is not None:
        for seed in args.seed:
            for fault in args.fault:
                print("IN_COMMON " + json.dumps({
                    "workload": args.workload, "seed": seed, "fault": fault,
                    "prompt": args.in_common,
                    **in_common(cell, seed, args.in_common, fault)}),
                    flush=True)
        return 0
    # this process plants this family's faults only
    base.WEIGHTS, base.planted = WEIGHTS, planted
    for seed in args.seed:
        for fault in args.fault:
            worst, rms = base.readings(cell, seed, fault)
            print("CONTROL " + json.dumps({
                "workload": args.workload, "seed": seed, "fault": fault,
                "router_std": family.ROUTER_STD,
                "routed_down_scale": family.ROUTED_DOWN_SCALE,
                "up_std": family.UP_STD, "embed_std": family.EMBED_STD,
                "relative_error": worst, "tolerance": compare.TOLERANCE,
                "rms_error": rms, "rms_tolerance": compare.RMS_TOLERANCE,
                "logits_agree": bool(worst <= compare.TOLERANCE
                                     and rms <= compare.RMS_TOLERANCE)}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
