#!/usr/bin/env python3
"""The two KDA kernels alone (``ops/pallas/delta_rule.py``), on the chip, at
the shapes of ``kimilin-serve-think-sat``: wall time a call of the decode
step (256 slots, one layer of a 6-layer stack) and of the chunk scan (one
row's 1,024-token chunk) at each sub-chunk and each sub-block of the scores
asked for, each checked against its XLA twin over the whole chunk on the
way.

    chiprun -- python scripts/kda_kernel_bench.py [--chunks 64,128]
        [--sub-blocks 8,16] [--slots 256] [--tokens 1024]

Chip only: a time is a chip's (``utils.platform.require_tpu``)."""

import argparse
import itertools
import os
import sys
import time

ap = argparse.ArgumentParser()
ap.add_argument("--chunks", default="64,128")
ap.add_argument("--sub-blocks", default=None,
                help="rows of a sub-block of the scores (delta_rule."
                "SUB_BLOCK, the kernel's own where not given)")
ap.add_argument("--slots", type=int, default=256)
ap.add_argument("--tokens", type=int, default=1024)
ARGS = ap.parse_args()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.pallas import delta_rule as dr
from deepspeed_tpu.utils.platform import require_tpu

H, K, LAYERS, REPS = 32, 128, 6, 10


def draw(key, B, S):
    ks = jax.random.split(key, 5)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, S, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (B, S, H, K)))
    v = jax.random.normal(ks[2], (B, S, H, K))
    g = -jax.random.uniform(ks[3], (B, S, H, K), minval=1e-3, maxval=1.6)
    beta = jax.random.uniform(ks[4], (B, S, H))
    return q, k, v, g, beta


def timed(fn, state, *args):
    state = fn(state, *args)[1]               # compile, and warm
    jax.block_until_ready(state)
    t = time.perf_counter()
    for _ in range(REPS):
        y, state = fn(state, *args)
    jax.block_until_ready((y, state))
    return (time.perf_counter() - t) / REPS * 1e3, y, state


def main():
    require_tpu()
    key = jax.random.PRNGKey(0)
    B = ARGS.slots
    q, k, v, g, beta = (t[:, 0] for t in draw(key, B, 1))
    active = jnp.arange(B) % 16 != 5
    step = jax.jit(lambda st, *a: dr.kda_decode_step(st, 3, *a),
                   donate_argnums=0)
    st = jnp.zeros((LAYERS, B, K, H * K), jnp.float32)
    ms, y, st = timed(step, st, q, k, v, g, beta, active)
    print(f"decode_step slots={B} ms={ms:.3f} "
          f"GB/s={2 * int(active.sum()) * K * H * K * 4 / ms / 1e6:.0f}")
    small = jnp.ones((LAYERS, 8, K, H * K), jnp.float32) * 0.01
    a8 = tuple(t[:8] for t in (q, k, v, g, beta, active))
    want = dr._decode_xla(small, 3, *a8[:3], jnp.exp(a8[3]), a8[4], a8[5])
    got = jax.jit(lambda st, *a: dr.kda_decode_step(st, 3, *a))(small, *a8)
    print("decode_step vs twin:",
          float(jnp.abs(got[0] - jnp.where(a8[5][:, None, None], want[0], 0)
                        ).max()), float(jnp.abs(got[1] - want[1]).max()))
    S = ARGS.tokens
    args = draw(jax.random.fold_in(key, 1), 1, S)
    valid = jnp.asarray([S - 37])
    want = None
    sub_blocks = ([dr.SUB_BLOCK] if ARGS.sub_blocks is None
                  else map(int, ARGS.sub_blocks.split(",")))
    for sb, C in itertools.product(sub_blocks,
                                   map(int, ARGS.chunks.split(","))):
        dr.SUB_BLOCK = sb      # read where a call is traced
        scan = jax.jit(lambda st, *a, C=C: dr.kda_chunk_scan(
            st, 3, *a, valid=valid, chunk=C), donate_argnums=0)
        st1 = jnp.zeros((LAYERS, 1, K, H * K), jnp.float32)
        ms, y, st1 = timed(scan, st1, *args)
        print(f"chunk_scan tokens={S} chunk={C} sub_block={sb} ms={ms:.3f} "
              f"us/token={ms * 1e3 / S:.2f}")
        fresh = lambda: jax.jit(lambda st, *a, C=C: dr.kda_chunk_scan(
            st, 3, *a, valid=valid, chunk=C))(
                jnp.zeros((LAYERS, 1, K, H * K), jnp.float32), *args)
        y0, s0 = fresh()
        # the twin, over the whole chunk: the same sums with no reference row
        tiles, dr._tiles = dr._tiles, lambda K, V: False
        yt, stt = fresh()
        dr._tiles = tiles
        print(f"chunk={C} sub_block={sb} vs twin:",
              float(jnp.abs(yt - y0)[:, :S - 37].max()),
              float(jnp.abs(stt[3] - s0[3]).max()))
        if want is None:
            want = (np.asarray(y0), np.asarray(s0[3]))
        else:
            print(f"chunk={C} sub_block={sb} vs first:",
                  float(np.abs(np.asarray(y0) - want[0])[:, :S - 37].max()),
                  float(np.abs(np.asarray(s0[3]) - want[1]).max()))


if __name__ == "__main__":
    main()
