#!/usr/bin/env python3
"""Negative controls of the logits check for the hybrid state-space /
attention family, at a serving cell's own sizes: the server runs with a
fault planted, the reference as it is on the weights as drawn, and the two
readings of ``compare.py`` say whether ``correct`` would turn false.

    python3 benchmarks/chip/reference/hybrid_ssm_moe_control.py \
        --workload <cell> --seed <n> [<n> ...] --fault <name> [<name> ...] \
        [--ticks <n>]

After ``latent_moe_control.py``: each seed and fault runs in turn in one
process (a second copy of the weights does not fit beside the slot pool):
draw the weights, plant the fault, build the cell's server, take the check's
logits through its slot path (``probe_logits``: the check prompts of the
traffic file, as ``kinds/_serving.check_logits`` draws them), free the
server, draw the weights again and run the reference.  What is new here is
per-slot state, so most faults are in what the program does with it, planted
by replacing a function of ``ops/pallas/ssm.py`` for the server's lifetime;
a run of the benchmark plants nothing.  The last line of each fault is
``CONTROL {...}``.

Faults (``FAULTS``): ``none``; ``chunk_edge``: every chunk of a prompt
starts its scan from a zero state (the state is not carried across a chunk
edge); ``pad_advance``: a chunk is not told where its prompt ends, so a
padded tail advances the state and the convolution tail; ``no_tail``: the
convolution starts every call from zeros (its tail is dropped, across chunk
edges and ticks); ``zero``: the routed experts' down-projections zeroed (the
routed product left out); ``bf16_state``: the layer's ``H`` rounded to bf16
after every chunk and every tick (the state kept in bf16); ``int8``: every
matrix of every layer, routed experts and router included, rounded to 255
levels of its largest magnitude per output channel (the weights' precision
lowered everywhere).

``--ticks`` (default: the traffic file's ``check.ticks``, what the cell's
own ``correct`` compares) lengthens the decode the readings are taken over.
A state kept in bf16 is rounded once a token served, and the traffic serves
hundreds a request where the check reads 6: ``--ticks 512`` (the traffic's
median output) is the reading of what such a state would do to a reply,
which the cell's check at its 6 ticks does not judge.
"""

import argparse
import contextlib
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def _zero_routed(params):
    return {**params, "runs": [{**run, "w_down": run["w_down"] * 0}
                               for run in params["runs"]]}


def _int8(params):
    """Every matrix ``[layers, ..., in, out]`` on 255 levels of its largest
    magnitude per output channel (the convolution's taps are no matrix)."""
    import jax.numpy as jnp

    def rounded(w):
        f = w.astype(jnp.float32)
        scale = jnp.maximum(jnp.abs(f).max(axis=-2, keepdims=True) / 127.0,
                            1e-30)
        return (jnp.round(f / scale) * scale).astype(w.dtype)
    return {**params, "runs": [
        {k: rounded(v) if v.ndim >= 3 and k != "conv_w" else v
         for k, v in run.items()} for run in params["runs"]]}


def _layer(stack, layer, fn):
    """``fn`` on layer ``layer`` of a state stack, written back in place."""
    from jax import lax
    one = lax.dynamic_index_in_dim(stack, layer, 0)
    return lax.dynamic_update_slice(stack, fn(one).astype(stack.dtype),
                                    (layer,) + (0,) * (stack.ndim - 1))


def _patches(fault: str, ssm) -> dict:
    """``name -> replacement`` of ``ops/pallas/ssm.py``'s functions."""
    from jax import lax
    scan, step, conv = ssm.ssd_chunk_scan, ssm.ssm_decode_step, \
        ssm.causal_conv
    # an explicit rounding: a convert to bf16 and back is one the compiler
    # may drop (``xla_allow_excess_precision``), and on the chip it does
    rounded = lambda h: lax.reduce_precision(h, exponent_bits=8,
                                             mantissa_bits=7)
    if fault == "chunk_edge":
        return {"ssd_chunk_scan": lambda state, layer, *a, **k: scan(
            _layer(state, layer, lambda h: h * 0), layer, *a, **k)}
    if fault == "pad_advance":
        # ticks keep their liveness (one token a call); chunks lose theirs
        return {
            "ssd_chunk_scan": lambda *a, valid=None, **k: scan(*a, **k),
            "causal_conv": lambda u, tail, w, b, valid=None: conv(
                u, tail, w, b, valid if u.shape[1] == 1 else None)}
    if fault == "no_tail":
        return {"causal_conv": lambda u, tail, w, b, valid=None: conv(
            u, tail * 0, w, b, valid)}
    if fault == "bf16_state":
        def after(fn):
            def run(state, layer, *a, **k):
                y, state = fn(state, layer, *a, **k)
                return y, _layer(state, layer, rounded)
            return run
        return {"ssd_chunk_scan": after(scan), "ssm_decode_step": after(step)}
    return {}


#: fault -> what it does to the weights (the others replace functions)
WEIGHTS = {"zero": _zero_routed, "int8": _int8}
FAULTS = ("none", "chunk_edge", "pad_advance", "no_tail", "zero",
          "bf16_state", "int8")


@contextlib.contextmanager
def planted(fault: str):
    from deepspeed_tpu.ops.pallas import ssm
    patches = _patches(fault, ssm)
    kept = {name: getattr(ssm, name) for name in patches}
    for name, fn in patches.items():
        setattr(ssm, name, fn)
    try:
        yield
    finally:
        for name, fn in kept.items():
            setattr(ssm, name, fn)


def readings(cell, seed: int, fault: str, ticks=None):
    """``(largest, rms)`` of the cell's check with ``fault`` planted, over
    ``ticks`` decode ticks (None: the check's own)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import deepspeed_tpu
    from benchmarks.chip.builders import resolve
    from benchmarks.chip.reference import compare

    cfg = resolve(cell.config["builder"])(cell.config)
    init = resolve(cell.config["init"])
    key = jax.random.PRNGKey(seed)
    draw = jax.jit(lambda key: init(cfg, key, jnp.bfloat16))
    weights = jax.jit(WEIGHTS.get(fault, lambda p: p),
                      donate_argnums=0)(draw(key))
    spec = cell.traffic["check"]
    ticks = int(spec["ticks"] if ticks is None else ticks)
    max_len = int(cell.traffic["serving"]["max_len"])
    lens = [min(int(n), max_len - ticks - 1) for n in spec["prompt_lens"]]
    rng = np.random.default_rng(seed + 7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    with planted(fault):
        engine = deepspeed_tpu.init_inference(model=(cfg, weights),
                                              config={"dtype": "bfloat16"})
        gateway = engine.serve(config=cell.traffic["serving"])
        gateway.shutdown(drain=False, timeout=60)
        replies, got = gateway.probe_logits(prompts, ticks)
    got = [np.asarray(g) for g in got]
    del gateway, engine, weights
    gc.collect()
    params = draw(key)
    forward = resolve(cell.config["reference"]).forward
    reference = jax.jit(lambda prm, t: forward(cell.config, prm, t,
                                               ticks + 1))
    worst = rms = 0.0
    for p, reply, logits in zip(prompts, replies, got):
        full = np.concatenate([p, np.asarray(reply, np.int32)])[None]
        ref = np.asarray(reference(params, full))[0]
        worst = max(worst, compare.relative_error(
            logits[:, :cfg.vocab_size], ref))
        rms = max(rms, compare.rms_error(logits[:, :cfg.vocab_size], ref))
    return worst, rms


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, nargs="+", required=True)
    ap.add_argument("--fault", nargs="+", choices=FAULTS, required=True)
    ap.add_argument("--ticks", type=int, default=None,
                    help="decode ticks read (default: the check's own)")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks.chip import harness
    from benchmarks.chip.reference import compare
    from deepspeed_tpu.utils.platform import enable_compile_cache, require_tpu
    require_tpu()
    enable_compile_cache()
    cell = harness.load_cell(ROOT, args.workload)
    for seed in args.seed:
        for fault in args.fault:
            worst, rms = readings(cell, seed, fault, args.ticks)
            print("CONTROL " + json.dumps({
                "workload": args.workload, "seed": seed, "fault": fault,
                "ticks": args.ticks or int(cell.traffic["check"]["ticks"]),
                "relative_error": worst, "tolerance": compare.TOLERANCE,
                "rms_error": rms, "rms_tolerance": compare.RMS_TOLERANCE,
                "logits_agree": bool(worst <= compare.TOLERANCE
                                     and rms <= compare.RMS_TOLERANCE)}),
                flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
