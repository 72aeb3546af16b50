#!/usr/bin/env python3
"""Negative controls of the logits check for the gated-short-convolution
family (``lfm2_moe``), at a serving cell's own sizes: the server runs with a
fault planted, the reference as it is on the weights as drawn, and the two
readings of ``compare.py`` say whether ``correct`` would turn false.

    python3 benchmarks/chip/reference/lfm2_control.py \
        --workload <cell> --seed <n> [<n> ...] --fault <name> [<name> ...] \
        [--ticks <n>]
    python3 benchmarks/chip/reference/lfm2_control.py \
        --workload <cell> --seed <n> --fault <name> [<name> ...] \
        --in-common <prompt length>

It is ``hybrid_ssm_moe_control.py`` (its process, its ``readings``) with this
family's faults.  In the mixer, planted by replacing a function of the
program for the server's lifetime: ``no_gate_c`` / ``no_gate_b``: the gate
``C`` / ``B`` left out (ones in its place); ``chunk_edge``: a prompt's
second and later passes start from a zero tail (ticks keep theirs);
``pad_end``: the tail kept after a padded chunk is that of the chunk's END,
padding included, not of its last real token.  In attention:
``no_qk_norm``: the per-head norm of q and k left out.  In the gate:
``bias_weights``: ``expert_bias`` added to the weights too, not to the choice
alone; ``bf16_router``: the router's scores and choice in bf16 where the
file says float32.  In the weights: ``zero``: the routed product left out
(the dense layers' stays); ``taps_reversed``: the taps in the other order
(``w_0`` on the current token); ``int8``: every matrix of every layer on 255
levels a channel (the nearest precision below the bf16 the file states).
The last line of each fault is ``CONTROL {...}``.

``UNSEEN`` names what the cell's check does NOT see at the published widths
(PERF.md 6, PR 61), kept runnable so that the reading can be made again;
``tests/unit/models/test_family_conformance.py`` holds each on the CPU on
loud weights.

``--in-common`` reads the gate itself, which the logits at a routed
down-projection of a sixteenth barely do: one seeded prompt through the
program's chunked prefill (``fault`` planted) and through the reference,
every expert layer's choices and weights recorded on both sides;
``in_common`` is the share of a token's choices the two have in common and
``weights_off`` the mean ``|w - w_reference|`` over the tokens whose choices
agree, each by layer.  The layers' mean ``in_common`` is held to
``IN_COMMON_LIMIT`` and the FIRST expert layer's ``weights_off`` to
``WEIGHTS_OFF_LIMIT`` (deeper layers read a stream that bf16 has already
moved); this is the reading that sees the two ``UNSEEN`` faults.  Faults in
the weights are the logits' to read (a second copy of the weights does not
fit the chip).  Each line is ``IN_COMMON {...}``.
"""

import argparse
import contextlib
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

from benchmarks.chip.reference import hybrid_ssm_moe_control as base  # noqa: E402
from benchmarks.chip.reference import mellum_control  # noqa: E402
from benchmarks.chip.reference.nemotron_h_control import _each_part  # noqa: E402


def _int8(params):
    """An attention part's matrices as ``mellum_control._int8`` rounds them
    (head-major), every other part's as ``base._int8`` (``[..., in,
    out]``)."""
    part = params["runs"][0]
    return (mellum_control._int8 if "wq" in part else base._int8)(params)


def _taps_reversed(params):
    part = params["runs"][0]
    return {**params, "runs": [{**part, "conv_w": part["conv_w"][:, ::-1]}]}


WEIGHTS = {"zero": _each_part(base._zero_routed, only="router"),
           "taps_reversed": _each_part(_taps_reversed, only="conv_w"),
           "int8": _each_part(_int8)}
#: each but ``none`` must read not ``correct`` in the cell's own check
FAULTS = ("none", "zero", "no_gate_c", "no_gate_b", "taps_reversed",
          "chunk_edge", "pad_end", "no_qk_norm", "int8")
#: planted the same way, and not seen by the cell's check (module docstring)
UNSEEN = ("bias_weights", "bf16_router")


def _bf16_router(h, w_router, bias, k, scale, normalize=True):
    """``held_experts.route`` with every step in bf16: the scores from
    rounded inputs, the sum with the bias and the choice."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from deepspeed_tpu.moe.held_experts import Routing
    # an explicit rounding: a convert to bf16 and back is one the compiler
    # may drop (``xla_allow_excess_precision``), and on the chip it does
    rounded = lambda t: lax.reduce_precision(t.astype(jnp.float32),
                                             exponent_bits=8, mantissa_bits=7)
    scores = rounded(jax.nn.sigmoid(rounded(jnp.dot(
        rounded(h), rounded(w_router), precision=lax.Precision.HIGHEST))))
    _, experts = lax.top_k(rounded(scores + rounded(bias)), k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return Routing(experts.astype(jnp.int32), weights * scale)


def _patches(fault: str) -> dict:
    """``(object, name) -> replacement``."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import conv_moe as model
    from deepspeed_tpu.moe.held_experts import Routing
    mix, route = model.conv_mix, model.route
    if fault == "no_gate_c":
        return {(model, "conv_mix"): lambda b, c, *a, **k: mix(
            b, jnp.ones_like(c), *a, **k)}
    if fault == "no_gate_b":
        return {(model, "conv_mix"): lambda b, *a, **k: mix(
            jnp.ones_like(b), *a, **k)}
    if fault == "chunk_edge":
        return {(model, "conv_mix"): lambda b, c, u, tail, *a, **k: mix(
            b, c, u, tail * (u.shape[1] == 1), *a, **k)}
    if fault == "pad_end":
        # ticks keep their liveness (one token a call); chunks lose theirs
        return {(model, "conv_mix"):
                lambda b, c, u, tail, p, config, valid=None: mix(
                    b, c, u, tail, p, config,
                    valid if u.shape[1] == 1 else None)}
    if fault == "no_qk_norm":
        class NoNorm(model.ConvMoEConfig):
            qk_norm = False
        project = model.attention_project
        return {(model, "attention_project"):
                lambda x, p, config, positions: project(
                    x, p, NoNorm(**{f.name: getattr(config, f.name) for f in
                                    dataclasses.fields(config)}), positions)}
    if fault == "bias_weights":
        def biased(h, w_router, bias, k, scale, normalize=True):
            r = route(h, w_router, bias, k, 1.0, normalize=False)
            w = r.weights + bias.astype(jnp.float32)[r.experts]
            return Routing(r.experts, w / (jnp.sum(w, -1, keepdims=True)
                                           + 1e-20) * scale)
        return {(model, "route"): biased}
    if fault == "bf16_router":
        return {(model, "route"): _bf16_router}
    return {}


@contextlib.contextmanager
def planted(fault: str):
    patches = _patches(fault)
    kept = {at: getattr(*at) for at in patches}
    for (owner, name), fn in patches.items():
        setattr(owner, name, fn)
    try:
        yield
    finally:
        for (owner, name), fn in kept.items():
            setattr(owner, name, fn)


#: What a sound run's gates must keep (module docstring; my chip runs, PR
#: 61, 1,100 tokens, seeds 2147493001 / 2147494003 / 2147495009).  The
#: choices in common, MEAN over the 12 expert layers: sound 0.9933 0.9920
#: 0.9931, ``bf16_router`` 0.9884 0.9862 (355-420 flipped choices of 52,800
#: against 610-730; the first layer alone counts 17-27 of 4,400 against
#: 38-47, too few to draw a line through).  The weights' distance where the
#: choices agree, FIRST expert layer: sound 1.461e-4 1.447e-4 1.464e-4 (the
#: bf16 of the two layers before it), ``bf16_router`` 3.06e-4 2.99e-4 (the
#: scores' own rounding), ``bias_weights`` 2.27e-3 1.80e-3: the limit is the
#: geometric mean of the two nearest.
IN_COMMON_LIMIT = 0.990
WEIGHTS_OFF_LIMIT = 2.1e-4


def program_gates(cfg, params, tokens, chunk: int):
    """``(experts, weights)``, ``[expert layers, S, k]`` each: what the
    program's gates give every token of ``tokens`` [S] in a chunked prefill
    of ``chunk`` tokens a pass through the family's cache, whatever
    ``route`` a planted fault has put in place."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deepspeed_tpu.models import cache_family
    from deepspeed_tpu.models import conv_moe as model
    fam = cache_family(cfg)
    S = len(tokens)
    padded = -(-S // chunk) * chunk
    calls = []
    gate = model.route

    def recorded(*a, **k):
        routing = gate(*a, **k)
        jax.debug.callback(
            lambda e, w: calls.append((np.asarray(e), np.asarray(w))),
            routing.experts, routing.weights, ordered=True)
        return routing

    toks = np.zeros((1, padded), np.int32)
    toks[0, :S] = tokens
    model.route = recorded
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
        model.route = gate
    # a chunk's calls come in depth order, one an expert layer
    L = cfg.n_layer - cfg.n_dense
    assert len(calls) == L * (padded // chunk), len(calls)
    return tuple(np.concatenate(
        [np.stack([c[i] for c in calls[at * L:(at + 1) * L]])
         for at in range(padded // chunk)], axis=1)[:, :S] for i in (0, 1))


def gate_readings(got, want) -> dict:
    """Two ``(experts, weights)`` pairs ``[layers, S, k]`` against each
    other, by layer: ``in_common`` the mean over tokens of ``|got & want| /
    k``, ``weights_off`` the mean ``|w - w'|`` over the (token, expert)
    pairs of the tokens whose choices agree; ``agree``: the layers' mean
    ``in_common`` and the first layer's ``weights_off`` inside their
    limits."""
    import numpy as np
    (ge, gw), (we, ww) = got, want
    both = (ge[..., :, None] == we[..., None, :]).any(-1).mean(-1)
    same = both == 1.0                                    # [layers, S]
    by_id = lambda e, w: np.take_along_axis(w, np.argsort(e, -1), -1)
    off = np.abs(by_id(ge, gw) - by_id(we, ww)).mean(-1)
    in_common = [float(v) for v in both.mean(-1)]
    weights_off = [float(o[s].mean()) if s.any() else float("inf")
                   for o, s in zip(off, same)]
    return {"in_common": in_common, "weights_off": weights_off,
            "tokens": int(both.shape[-1]),
            "limits": [IN_COMMON_LIMIT, WEIGHTS_OFF_LIMIT],
            "agree": bool(np.mean(in_common) >= IN_COMMON_LIMIT
                          and weights_off[0] <= WEIGHTS_OFF_LIMIT)}


def in_common(cell, seed: int, n: int, fault: str) -> dict:
    """:func:`gate_readings` of the program (``fault`` planted) against the
    reference over one seeded prompt of ``n`` tokens at the cell's chunk."""
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
        got = program_gates(cfg, params, tokens,
                            int(cell.traffic["serving"]["prefill_chunk"]))
    want = jax.jit(lambda p, t: resolve(cell.config["reference"]).gates(
        cell.config, p, t))(params, tokens[None])
    return gate_readings(got, tuple(np.asarray(a)[0] for a in want))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    # this process plants this family's faults only
    base.WEIGHTS, base.FAULTS, base.planted = WEIGHTS, FAULTS + UNSEEN, \
        planted
    if "--in-common" not in argv:
        return base.main(argv)
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    # a fault in the weights is the logits' to read: one copy fits the chip
    ap.add_argument("--fault", nargs="+", required=True, choices=[
        f for f in FAULTS + UNSEEN if f not in WEIGHTS])
    ap.add_argument("--in-common", type=int, required=True,
                    help="prompt length: read the gates, not the logits")
    args = ap.parse_args(argv)
    from benchmarks.chip import harness
    from deepspeed_tpu.utils.platform import enable_compile_cache, require_tpu
    require_tpu()
    enable_compile_cache()
    cell = harness.load_cell(ROOT, args.workload)
    for seed in args.seed:
        for fault in args.fault:
            print("IN_COMMON " + json.dumps({
                "workload": args.workload, "seed": seed, "fault": fault,
                "prompt": args.in_common,
                **in_common(cell, seed, args.in_common, fault)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
