#!/usr/bin/env python3
"""Negative controls of the logits check for the latent-attention,
routed-expert family, at a serving cell's own sizes: the server is given
weights with a fault planted, the reference the weights as drawn, and the
two readings of ``compare.py`` say whether ``correct`` would turn false.

    python3 benchmarks/chip/reference/latent_moe_control.py \
        --workload <cell> --seed <n> [<n> ...] --fault <name> [<name> ...]

``control.py`` lays keys over the ``init_inference`` config; the faults here
are in the weights, and a second copy of them does not fit beside the
cell's slot pool.  So each seed and fault runs in turn in one process: draw the
weights from the seed, plant the fault in place (the buffers are donated),
build the cell's server, take the check's logits through its slot path
(``probe_logits``: the check prompts of the traffic file, as
``kinds/_serving.check_logits`` draws them), free the server, draw the
weights again and run the reference.  Made when a tolerance or a weight
scale is set or questioned; a run of the benchmark plants nothing.  The last
line of each fault is ``CONTROL {...}``.

Faults (``FAULTS``): ``none``; ``zero``: the routed experts' down-projections
zeroed (the routed product left out); ``permute``: each layer's held experts
hand their down-projection to the next one (a wrong group of the grouped
product); ``layer``: each expert layer reads the layer before's routed
experts (a wrong layer offset into the stack); ``int8``: every matrix of both
stacks, routed experts and router included, rounded to 255 levels of its
largest magnitude per output channel (precision lowered everywhere).
"""

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

#: the input axes of the stacked matrices that are not ``[..., in, out]``
_INPUT_AXES = {"wq_b": (1,), "wkv_b": (1,), "wo": (1, 2)}


def _rounded(name, w):
    import jax.numpy as jnp
    f = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.abs(f).max(
        axis=_INPUT_AXES.get(name, (-2,)), keepdims=True) / 127.0, 1e-30)
    return (jnp.round(f / scale) * scale).astype(w.dtype)


def _experts(params, fn, names=("w_gu", "w_down")):
    """``fn`` on the routed experts' stacks ``[layers, held, ...]``."""
    moe = params["moe_blocks"]
    return {**params, "moe_blocks": {**moe,
                                     **{k: fn(moe[k]) for k in names}}}


def _int8(params):
    return {**params, **{
        s: {k: _rounded(k, v) if v.ndim >= 3 else v
            for k, v in params[s].items()}
        for s in ("dense_blocks", "moe_blocks")}}


def _roll(w, axis):
    import jax.numpy as jnp
    return jnp.roll(w, 1, axis)


#: fault -> weights with it planted
FAULTS = {
    "none": lambda p: p,
    "zero": lambda p: _experts(p, lambda w: w * 0, ("w_down",)),
    "permute": lambda p: _experts(p, lambda w: _roll(w, 1), ("w_down",)),
    "layer": lambda p: _experts(p, lambda w: _roll(w, 0)),
    "int8": _int8}


def readings(cell, seed: int, fault: str):
    """``(largest, rms)`` of the cell's check with ``fault`` planted."""
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
    planted = jax.jit(FAULTS[fault], donate_argnums=0)(draw(key))
    engine = deepspeed_tpu.init_inference(model=(cfg, planted),
                                          config={"dtype": "bfloat16"})
    gateway = engine.serve(config=cell.traffic["serving"])
    gateway.shutdown(drain=False, timeout=60)
    spec = cell.traffic["check"]
    ticks = int(spec["ticks"])
    max_len = int(cell.traffic["serving"]["max_len"])
    lens = [min(int(n), max_len - ticks - 1) for n in spec["prompt_lens"]]
    rng = np.random.default_rng(seed + 7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]
    replies, got = gateway.probe_logits(prompts, ticks)
    got = [np.asarray(g) for g in got]
    del gateway, engine, planted
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
    ap.add_argument("--fault", nargs="+", choices=sorted(FAULTS),
                    required=True)
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
            worst, rms = readings(cell, seed, fault)
            print("CONTROL " + json.dumps({
                "workload": args.workload, "seed": seed, "fault": fault,
                "relative_error": worst, "tolerance": compare.TOLERANCE,
                "rms_error": rms, "rms_tolerance": compare.RMS_TOLERANCE,
                "logits_agree": bool(worst <= compare.TOLERANCE
                                     and rms <= compare.RMS_TOLERANCE)}),
                flush=True)
            gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
