#!/usr/bin/env python3
"""``held_experts_ffn`` behind its gate, on the chip, at the routed serving
cells' shapes: device time a layer call (the ops of a profiler trace, by
name) of an admission's chunk and of a tick, in one process.  What PERF.md 6
(PR 54) quotes beside the traced runs was measured with it.

    chiprun -- python scripts/held_experts_bench.py [--cells code,rag]
        [--phases admit,tick] [--root DIR] [--skew 0.5] [--top 12]

A jitted scan over two layers routes by the cell's gate and calls the
function with the experts' stacks and the scan's index, as the families do.
``--root DIR``: import ``DIR``'s ``deepspeed_tpu`` (another checkout's
function; one process a tree, the chip is one process's).  ``--skew`` adds a
bias to the held experts' router logits, so more than a uniform share lands
here (the pages run beyond a call's first are printed).

Chip only: a time is a chip's (``utils.platform.require_tpu``)."""

import argparse
import glob
import os
import re
import sys
import tempfile

ap = argparse.ArgumentParser()
ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
ap.add_argument("--cells", default="code,rag,agent,reason,longgen")
ap.add_argument("--phases", default="admit,tick")
ap.add_argument("--skew", type=float, default=0.0)
ap.add_argument("--top", type=int, default=12)
ARGS = ap.parse_args()
sys.path.insert(0, os.path.abspath(ARGS.root))

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deepspeed_tpu.moe import held_experts as he
from deepspeed_tpu.utils.platform import require_tpu

LAYERS, REPS = 2, 5

#: cell -> (chunk rows, tick rows, k, d, f, held, experts, form, gate)
CELLS = {
    "code": (1024, 48, 8, 2304, 896, 16, 64, he.SWIGLU, "softmax"),
    "rag": (512, 128, 10, 4096, 768, 18, 72, he.SWIGLU, "softmax"),
    "agent": (1024, 128, 6, 2688, 1920, 32, 128, he.RELU2, "sigmoid"),
    "reason": (512, 128, 8, 7168, 2048, 12, 384, he.SWIGLU, "sigmoid"),
    "longgen": (1024, 80, 8, 5120, 1536, 8, 256, he.SWIGLU, "sigmoid"),
    # every expert held here: nothing to leave out
    "all_held": (1024, 48, 8, 2304, 896, 16, 16, he.SWIGLU, "softmax"),
}


def device_ops(logdir):
    """``name opcode shape`` -> ``[calls, us]`` of the trace's device ops."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        logdir, "plugins/profile/*/*.xplane.pb")))[-1]
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                m = re.match(r"^%(\S+) = (\(.*?\)|\S+) ([a-z][a-z0-9-]*)\(",
                             e.name)
                if not m or m[3] in ("while", "conditional", "call"):
                    continue
                shape = re.sub(r"{[^}]*}", "", m[2])
                c = out.setdefault(f"{m[1]} {m[3]} {shape}"[:100], [0, 0.0])
                c[0] += 1
                c[1] += e.duration_ns * 1e-3
    return out


def bench(cell, phase):
    chunk, tick, k, d, f, n_held, n_experts, form, gate = CELLS[cell]
    T = chunk if phase == "admit" else tick
    held = tuple(range(0, n_experts, n_experts // n_held))[:n_held]
    keys = jax.random.split(jax.random.PRNGKey(1), 4)
    up, wide = ("w_up", f) if form == he.RELU2 else ("w_gu", 2 * f)
    stack = {up: (jax.random.normal(keys[0], (LAYERS, n_held, d, wide),
                                    jnp.bfloat16) / np.sqrt(d)
                  ).astype(jnp.bfloat16),
             "w_down": (jax.random.normal(keys[1], (LAYERS, n_held, f, d),
                                          jnp.bfloat16) / np.sqrt(f)
                        ).astype(jnp.bfloat16)}
    w_router = jax.random.normal(keys[2], (LAYERS, d, n_experts),
                                 jnp.float32) / np.sqrt(d)
    bias = jnp.zeros((n_experts,), jnp.float32).at[
        jnp.asarray(held)].add(ARGS.skew)
    x = jax.random.normal(keys[3], (T, d), jnp.bfloat16)

    def step(x, stack, w_router):
        def body(carry, i):
            h, total = carry
            wr = lax.dynamic_index_in_dim(w_router, i, 0, keepdims=False)
            if gate == "softmax":
                top, experts = lax.top_k(jnp.dot(
                    h.astype(jnp.float32), wr,
                    precision=lax.Precision.HIGHEST) + bias, k)
                routing = he.Routing(experts.astype(jnp.int32),
                                     jax.nn.softmax(top, -1))
            else:
                routing = he.route(h, wr, bias, k, 1.0)
            out, counts = he.held_experts_ffn(h, routing, stack, held,
                                              n_experts, layer=i, form=form)
            return (x + 0.1 * out, total + counts), None
        like = jax.eval_shape(lambda: body((x, 0), 0)[0][1])
        (h, total), _ = lax.scan(body, (x, jnp.zeros(like.shape, like.dtype)),
                                 jnp.arange(LAYERS))
        return h, total

    fn = jax.jit(step)
    jax.block_until_ready(fn(x, stack, w_router))
    with tempfile.TemporaryDirectory() as logdir:
        jax.profiler.start_trace(logdir)
        for _ in range(REPS):
            h, total = fn(x, stack, w_router)
        jax.block_until_ready(h)
        jax.profiler.stop_trace()
        ops = device_ops(logdir)
    calls = REPS * LAYERS
    total = np.asarray(total)
    here = total[:n_held]
    cap = he.pairs_cap(T * k, n_held, n_experts) \
        if hasattr(he, "pairs_cap") else T * k
    products = sum(us for name, (_, us) in ops.items() if "gmm" in name)
    whole = sum(us for _, us in ops.values())
    print(f"### {cell} {phase}: T={T} pairs={T * k} cap={cap} "
          f"held a layer={here.sum() / LAYERS:.0f} "
          f"busiest over mean={here.max() * n_held / max(here.sum(), 1):.2f} "
          f"pages beyond the first={total[n_held:]} "
          f"us a layer={whole / calls:.1f} products={products / calls:.1f} "
          f"around them={(whole - products) / calls:.1f}", flush=True)
    for name, (n, us) in sorted(ops.items(),
                                key=lambda kv: -kv[1][1])[:ARGS.top]:
        print(f"   {us / calls:9.1f} us a layer  calls a layer "
              f"{n / calls:4.1f}  {name}", flush=True)


def main():
    require_tpu()
    for cell in ARGS.cells.split(","):
        for phase in ARGS.phases.split(","):
            bench(cell, phase)


if __name__ == "__main__":
    main()
