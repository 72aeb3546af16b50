#!/usr/bin/env python3
"""Negative controls of the logits check for the linear-attention /
latent-attention family (``kimi_linear``), at a serving cell's own sizes: the
server runs with a fault planted, the reference as it is on the weights as
drawn, and the two readings of ``compare.py`` say whether ``correct`` would
turn false.

    python3 benchmarks/chip/reference/kimi_linear_control.py \
        --workload <cell> --seed <n> [<n> ...] --fault <name> [<name> ...] \
        [--ticks <n>]

It is ``hybrid_ssm_moe_control.py``'s process and ``readings`` (draw the
weights, plant the fault, build the cell's server, take the check's logits
through its slot path, free the server, draw the weights again and run the
reference) with this family's faults.  What is new here is the delta rule,
so most faults are departures from it, planted by replacing a function of
``ops/pallas/delta_rule.py``, ``ops/pallas/ssm.py`` or
``models/linear_latent_moe.py`` for the server's lifetime; a run of the
benchmark plants nothing.  The last line of each fault is ``CONTROL {...}``.

Faults (``FAULTS``): ``none``; ``no_delta``: the delta term left out (``u =
beta v``: what the state already reads for ``k`` is not subtracted; the
recurrence token by token, no kernel); ``mean_decay``: a head's decay the
mean of its channels' (the state-space families' one decay a head);
``no_conv``: the convolution on ``k`` left out (``k~`` straight through
SiLU); ``no_gate``: the output gate left out; ``state_other``: a KDA layer
starts every call from the NEXT KDA layer's state; ``zero``: the routed
experts' down-projections zeroed (the routed product left out);
``bf16_state``: a layer's ``S`` rounded to bf16 after every chunk and every
tick; ``int8``: every matrix of every layer, routed experts and router
included, rounded to 255 levels of its largest magnitude per output channel.

``--ticks`` (default: the traffic file's ``check.ticks``) lengthens the
decode the readings are taken over: a state kept in bf16 is rounded once a
token served, and the traffic serves thousands a request where the check
reads 6.

Read at ``kimilin-serve-think-sat``'s own sizes (my chip run, PR 55, seed
2147485005; largest / rms against 0.03 / 0.018): ``none`` 0.0098 / 0.0084;
``zero`` 0.0270 / 0.0240 and ``int8`` 0.0325 / 0.0296, not correct;
``no_delta`` 0.585 / 0.541, ``mean_decay`` 0.743 / 0.699, ``no_conv`` 1.090 /
0.914, ``no_gate`` 0.752 / 0.750, ``state_other`` 1.055 / 0.913, not correct by
both limits.  ``UNSEEN``: ``bf16_state`` 0.0111 / 0.0087 over 6 ticks and
0.0150 / 0.0119 over 512 (a sound run's 0.0113 / 0.0076 there): under the
limits both times.
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


def _each_part(fn):
    """``fn`` (one dict of stacks -> one dict) on every position's stacks
    of every run."""
    return lambda params: {**params, "runs": [
        fn(run) if isinstance(run, dict) else [fn(part) for part in run]
        for run in params["runs"]]}


def _zero_routed(part):
    # the routed experts' stack has an expert axis; a dense FFN's has none
    return {k: v * 0 if k == "w_down" and v.ndim == 4 else v
            for k, v in part.items()}


#: fault -> what it does to the weights (the others replace functions)
WEIGHTS = {
    "zero": _each_part(_zero_routed),
    "int8": _each_part(lambda part: base._int8({"runs": [part]})["runs"][0])}
FAULTS = ("none", "no_delta", "mean_decay", "no_conv", "no_gate",
          "state_other", "zero", "bf16_state", "int8")
#: runnable, and read as a sound run at the published widths (the numbers
#: are above): what ``correct`` does not judge
UNSEEN = ("bf16_state",)


def _no_delta(state, layer, q, k, v, g, beta, valid=None, active=None, **_):
    """``S = alpha S + k (beta v)^T``, ``o = S^T q`` token by token over
    ``[B, S, H, *]`` inputs from layer ``layer`` of the stack; a row that
    is not ``active`` keeps its state."""
    import jax.numpy as jnp
    from jax import lax
    L, B, K, HV = state.shape
    S, H = q.shape[1:3]
    if valid is not None:
        real = jnp.arange(S)[None] < jnp.asarray(valid)[:, None]
        g = jnp.where(real[..., None, None], g, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
    s0 = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    s0 = s0.reshape(B, K, H, HV // H)

    def token(s, xs):
        q, k, v, g, b = xs
        s = jnp.exp(g).transpose(0, 2, 1)[..., None] * s \
            + jnp.einsum("bhk,bhv->bkhv", k, b[..., None] * v)
        return s, jnp.einsum("bkhv,bhk->bhv", s, q)

    s, o = lax.scan(token, s0, tuple(
        jnp.moveaxis(t.astype(jnp.float32), 1, 0)
        for t in (q, k, v, g, beta)))
    if active is not None:
        s = jnp.where(active[:, None, None, None], s, s0)
    return (jnp.moveaxis(o, 0, 1).reshape(B, S, HV),
            lax.dynamic_update_slice(state, s.reshape(1, B, K, HV),
                                     (layer, 0, 0, 0)))


def _patches(fault: str) -> dict:
    """``(module, name) -> replacement``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from deepspeed_tpu.models import linear_latent_moe as model
    from deepspeed_tpu.ops.pallas import delta_rule, ssm
    scan, step = delta_rule.kda_chunk_scan, delta_rule.kda_decode_step
    # an explicit rounding: a convert to bf16 and back is one the compiler
    # may drop (``xla_allow_excess_precision``), and on the chip it does
    rounded = lambda h: lax.reduce_precision(h, exponent_bits=8,
                                             mantissa_bits=7)
    if fault == "no_delta":
        def one(state, layer, q, k, v, g, beta, active=None, work=None):
            return _no_delta(state, layer, *(t[:, None] for t in (
                q, k, v, g, beta)), active=active)
        return {(delta_rule, "kda_chunk_scan"): _no_delta,
                (delta_rule, "kda_decode_step"): one}
    if fault == "mean_decay":
        inputs = model.kda_inputs

        def mean(x, p, config):
            qkv, g, beta, gate = inputs(x, p, config)
            return qkv, jnp.broadcast_to(
                jnp.mean(g, -1, keepdims=True), g.shape), beta, gate
        return {(model, "kda_inputs"): mean}
    if fault == "no_conv":
        conv = ssm.causal_conv

        def no_k(u, tail, w, b, valid=None):
            out, tail = conv(u, tail, w, b, valid)
            third = u.shape[-1] // 3
            keys = (jnp.arange(u.shape[-1]) // third) == 1
            return jnp.where(keys, jax.nn.silu(u.astype(jnp.float32)),
                             out), tail
        return {(ssm, "causal_conv"): no_k}
    if fault == "no_gate":
        output = model.kda_output
        return {(model, "kda_output"): lambda x, o, gate, p, config: output(
            x, o, jnp.ones_like(gate), p, config)}
    if fault == "state_other":
        def from_next(fn):
            def run(state, layer, *a, **k):
                other = lax.dynamic_index_in_dim(
                    state, (layer + 1) % state.shape[0], 0)
                return fn(lax.dynamic_update_slice(
                    state, other, (layer, 0, 0, 0)), layer, *a, **k)
            return run
        return {(delta_rule, "kda_chunk_scan"): from_next(scan),
                (delta_rule, "kda_decode_step"): from_next(step)}
    if fault == "bf16_state":
        def after(fn):
            def run(state, layer, *a, **k):
                y, state = fn(state, layer, *a, **k)
                return y, base._layer(state, layer, rounded)
            return run
        return {(delta_rule, "kda_chunk_scan"): after(scan),
                (delta_rule, "kda_decode_step"): after(step)}
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--fault", nargs="+", choices=FAULTS, required=True)
    ap.add_argument("--ticks", type=int, default=None,
                    help="decode ticks read (default: the check's own)")
    args = ap.parse_args(argv)
    from benchmarks.chip import harness
    from benchmarks.chip.reference import compare
    from deepspeed_tpu.utils.platform import enable_compile_cache, require_tpu
    require_tpu()
    enable_compile_cache()
    cell = harness.load_cell(ROOT, args.workload)
    # this process plants this family's faults only
    base.WEIGHTS, base.planted = WEIGHTS, planted
    for seed in args.seed:
        for fault in args.fault:
            worst, rms = base.readings(cell, seed, fault, args.ticks)
            print("CONTROL " + json.dumps({
                "workload": args.workload, "seed": seed, "fault": fault,
                "ticks": args.ticks or int(cell.traffic["check"]["ticks"]),
                "relative_error": worst, "tolerance": compare.TOLERANCE,
                "rms_error": rms, "rms_tolerance": compare.RMS_TOLERANCE,
                "logits_agree": bool(worst <= compare.TOLERANCE
                                     and rms <= compare.RMS_TOLERANCE)}),
                flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
