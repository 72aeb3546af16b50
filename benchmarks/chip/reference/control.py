#!/usr/bin/env python3
"""A negative control of the logits check: one cell with its precision
lowered on purpose, to see whether ``correct`` would turn false.

    python3 benchmarks/chip/reference/control.py --workload <cell> \
        --seed <n> --control '{"inference": {"dtype": "int8"}}'

Made when the tolerances in ``compare.py`` are set or questioned; a run of
the benchmark never lowers anything.  A serving cell takes ``inference``:
keys laid over the ``init_inference`` config (``{"dtype": "int8"}`` is the
program's weight-only int8 serving, ``{"kv_cache_dtype": "int8"}`` its
8-bit cache).  A training cell takes ``{"weights": "int8"}``: the system's
forward pass gets weights rounded to 8 bits while the reference keeps the
weights as drawn.  The cell runs as in ``run.py`` with a short window; the
last line is ``CONTROL {...}`` with both readings and the verdict.
"""

import time

T_PROCESS = time.perf_counter()

import argparse   # noqa: E402
import json       # noqa: E402
import os         # noqa: E402
import sys        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def round_to_int8(params):
    """The layers' matrices (``blocks.w*``, stacked ``[L, in, ...]``) rounded
    to 255 levels of their largest magnitude along the input axis and
    returned in their own type: what weight-only 8-bit storage with a scale
    per output channel keeps.  Embeddings, norms and biases stay."""
    import jax.numpy as jnp

    def rounded(w):
        f = w.astype(jnp.float32)
        scale = jnp.maximum(jnp.abs(f).max(axis=1, keepdims=True) / 127.0,
                            1e-30)
        return (jnp.round(f / scale) * scale).astype(w.dtype)
    blocks = {k: rounded(v) if k.startswith("w") else v
              for k, v in params["blocks"].items()}
    return {**params, "blocks": blocks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--control", required=True, help="a JSON object")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks.chip import harness
    from benchmarks.chip.reference import compare
    control = json.loads(args.control)
    cell = harness.load_cell(ROOT, args.workload)
    ctx = harness.measure(cell, args.seed, args.seconds, False, T_PROCESS,
                          control=control)
    print("CONTROL " + json.dumps({
        "workload": args.workload, "seed": args.seed, "control": control,
        "relative_error": ctx.scalars["logits_relative_error"],
        "tolerance": compare.TOLERANCE,
        "rms_error": ctx.scalars["logits_rms_error"],
        "rms_tolerance": compare.RMS_TOLERANCE,
        "logits_agree": ctx.checks["logits_agree"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
