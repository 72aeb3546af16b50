#!/usr/bin/env python3
"""Negative controls of the logits check for the linear-attention /
grouped-attention family (``solar_open2``), at a serving cell's own sizes:
the server runs with a fault planted, the reference as it is on the weights
as drawn, and the two readings of ``compare.py`` say whether ``correct``
would turn false.

    python3 benchmarks/chip/reference/solar_open2_control.py \
        --workload <cell> --seed <n> [<n> ...] --fault <name> [<name> ...] \
        [--ticks <n>]

It is ``hybrid_ssm_moe_control.py``'s process and ``readings`` (draw the
weights, plant the fault, build the cell's server, take the check's logits
through its slot path, free the server, draw the weights again and run the
reference) with this family's faults: those of ``kimi_linear_control.py``
that the shared KDA mixer answers to, and what this family alone has.  A
fault is planted in the weights the server gets (``WEIGHTS``) or by
replacing a function for the server's lifetime (``planted``);
a run of the benchmark plants nothing.  The last line of each fault is
``CONTROL {...}``.

Faults (``FAULTS``): ``none``; ``beta_1``: ``beta`` without its factor 2
(``sigmoid(W_b h)``: Kimi-Linear's range, no negative eigenvalue);
``no_attn_gate``: the grouped layer's output gate left out; ``rotated``: the
grouped layer's queries and keys rotated (``rope_theta`` over the whole
head, halves paired); ``full_last``: the full layer put LAST in the period
(the same layers in the order K, K, K, G: a 1-indexed reading of
``gqa_layers``); ``no_shared``: the shared expert left out; ``mean_decay``:
a head's decay the mean of its channels'; ``zero``: the routed experts'
down-projections zeroed; ``int8``: every matrix of every layer on 255 levels
of its largest magnitude per output channel; ``bf16_state``: a layer's ``S``
rounded to bf16 after every chunk and every tick.

``--ticks`` (default: the traffic file's ``check.ticks``) lengthens the
decode the readings are taken over.

Read at ``solar2-serve-longctx-sat``'s own sizes: the configuration file's
``precision_judged`` and PERF.md 6 (PR 64) hold the numbers.
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
from benchmarks.chip.reference import kimi_linear_control as kda  # noqa: E402


def _zero_shared(part):
    return {k: v * 0 if k == "ws_down" else v for k, v in part.items()}


def _full_last(params):
    """One period's runs in the order the rotated labels read them: the KDA
    layers' run, then the grouped layer's."""
    assert len(params["runs"]) == 2, "full_last: one period (G, K, K, K)"
    return {**params, "runs": params["runs"][::-1]}


#: fault -> what it does to the weights (the others replace functions)
WEIGHTS = {**kda.WEIGHTS, "no_shared": kda._each_part(_zero_shared),
           "full_last": _full_last}
FAULTS = ("none", "beta_1", "no_attn_gate", "rotated", "full_last",
          "no_shared", "mean_decay", "zero", "int8", "bf16_state")
#: runnable, and read as a sound run at the published widths: what
#: ``correct`` does not judge
UNSEEN = ("bf16_state",)


def _rotated(t, positions, theta):
    """``t`` [B, S, heads, D] rotated over the whole head, halves paired."""
    import jax.numpy as jnp
    half = t.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    pos = jnp.asarray(positions, jnp.float32)
    pos = pos[None] if pos.ndim == 1 else pos               # [B or 1, S]
    angle = pos[..., None, None] * freq
    a, b = t[..., :half].astype(jnp.float32), t[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([a * jnp.cos(angle) - b * jnp.sin(angle),
                            b * jnp.cos(angle) + a * jnp.sin(angle)],
                           -1).astype(t.dtype)


def _patches(fault: str) -> dict:
    """``(module, name) -> replacement``."""
    import jax.numpy as jnp

    from deepspeed_tpu.models import linear_gqa_moe as model
    from deepspeed_tpu.models import linear_latent_moe as mixer
    if fault == "beta_1":
        inputs = mixer.kda_inputs

        def unscaled(x, p, config):
            qkv, g, beta, gate = inputs(x, p, config)
            return qkv, g, beta / config.kda_beta_scale, gate
        return {(mixer, "kda_inputs"): unscaled}
    if fault == "no_attn_gate":
        return {(model, "gated"): lambda attn, z: attn.astype(jnp.float32)}
    if fault == "rotated":
        project = model.attention_project

        def rotated(x, p, config, positions=None):
            (q, z), (k, v) = project(x, p, config, positions)
            turn = lambda t: _rotated(t, positions, 10000.0)
            return (turn(q), z), (turn(k), v)
        return {(model, "attention_project"): rotated}
    if fault == "full_last":
        units = model.layer_units
        return {(model, "layer_units"): lambda labels, **how: units(
            labels[1:] + labels[:1], **how)}
    return kda._patches(fault)


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
