#!/usr/bin/env python3
"""On-chip stall anatomy: per-component timings at the GPT-2 350M
training geometry (B=24, S=1024, H=16, D=64, d_model=1024).

Measures WHERE a train step's time goes: each row times one component
inside a single jit (``lax.scan`` with a data dependence so XLA cannot
hoist or dedupe the iterations; the dispatch is amortized over ITERS),
fenced by ``jax.block_until_ready``.  It measures the chip and has no CPU
path: without a TPU, or on a ``device_kind`` with no peak on record, it
fails.

Rows:
- ``matmul_roofline``  — chained 4096^3 bf16 matmul: achievable MXU peak
  (the denominator every %-of-peak row uses is the published peak of the
  device, ``bench.PEAK_BF16_FLOPS``; this row shows how much of it a plain
  gemm can actually hit).
- ``flash_fwd`` / ``flash_fwd_bwd`` — the Pallas causal kernel at
  head_dim 64.
- ``dense_fwd_bwd`` — XLA dense-scores attention at the same shape.
- ``qkvo_fwd_bwd`` — the four attention projections.
- ``mlp_fwd_bwd`` — the d→4d→d GeLU block.
- ``head_fwd_bwd`` — the [B·S, d] x [d, 50304] logits matmul.

Each row reports actual-math TFLOP/s (causal halving applied, flash
backward counted at 5 matmul-equivalents) and % of published peak.
Appends one JSON line per row to ``out.jsonl`` (default
``chiprun_out/stall_anatomy.jsonl``) as it goes, and prints a markdown
table.

Usage:  python scripts/stall_anatomy.py [out.jsonl]
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

B, S, H, D = (int(x) for x in os.environ.get(
    "ANATOMY_DIMS", "24,1024,16,64").split(","))
DM = H * D
FFN = 4 * DM
VOCAB = 50304       # padded_vocab of the 350M preset
ITERS = int(os.environ.get("ANATOMY_ITERS", "24"))


def _bench(fn, *args):
    """Median-of-3 wall time of jit(fn) amortized over ITERS chained
    iterations; returns seconds per iteration."""
    import jax

    f = jax.jit(fn)
    jax.block_until_ready(f(*args))          # compile + warm
    best = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        best.append((time.perf_counter() - t0) / ITERS)
    return sorted(best)[1]


def _chain(body):
    """ITERS data-dependent repetitions of ``body(x) -> y`` folded into
    one jitted function: the carry perturbs the next input so XLA keeps
    every iteration."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run(x0, *rest):
        def step(x, _):
            y = body(x, *rest)
            # fold a scalar of y back into x: data dependence, no drift
            s = jnp.mean(jax.tree_util.tree_leaves(y)[0]) * 0.0
            return x * (1.0 + s), None

        x, _ = lax.scan(step, x0, None, length=ITERS)
        return x

    return run


def rows():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.ops.pallas.flash_attention import (flash_attention,
                                                          mha_reference)

    k0 = jax.random.PRNGKey(0)
    bf = jnp.bfloat16
    out = []

    # matmul roofline
    a = jax.random.normal(k0, (4096, 4096), bf)
    w = jax.random.normal(k0, (4096, 4096), bf)
    t = _bench(_chain(lambda x, w: x @ w), a, w)
    out.append(("matmul_roofline", t, 2 * 4096**3))

    # attention inputs [B, S, H, D]
    q = jax.random.normal(k0, (B, S, H, D), bf) * 0.05
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, H, D), bf) * 0.05
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, H, D), bf) * 0.05

    fwd_flops = 2 * B * H * S * S * D          # 2 matmuls, causal-halved
    bwd_flops = 5 * B * H * S * S * D          # 5 matmul-equivalents
    t = _bench(_chain(lambda x, k, v: flash_attention(x, k, v, causal=True)),
               q, k, v)
    out.append(("flash_fwd", t, fwd_flops))

    def fa_loss(x, k, v):
        return jnp.sum(flash_attention(x, k, v, causal=True).astype(jnp.float32))

    t = _bench(_chain(lambda x, k, v: jax.grad(fa_loss)(x, k, v)), q, k, v)
    out.append(("flash_fwd_bwd", t, 2 * fwd_flops + bwd_flops))

    def dense_loss(x, k, v):
        return jnp.sum(mha_reference(x, k, v, causal=True).astype(jnp.float32))

    t = _bench(_chain(lambda x, k, v: jax.grad(dense_loss)(x, k, v)), q, k, v)
    # dense computes the FULL S^2 (no causal skip): 2 un-halved matmuls
    # fwd + 4 bwd + no recompute; report at its actual math
    out.append(("dense_fwd_bwd", t, (2 + 4) * 2 * B * H * S * S * D))

    # four projections [B*S, DM] x [DM, DM] (qkv fused as 3DM)
    x = jax.random.normal(k0, (B * S, DM), bf) * 0.1
    wqkv = jax.random.normal(k0, (DM, 3 * DM), bf) * 0.02
    wo = jax.random.normal(k0, (DM, DM), bf) * 0.02

    def qkvo(x, wqkv, wo):
        h = x @ wqkv
        return h[:, :DM] @ wo

    def qkvo_loss(x, wqkv, wo):
        return jnp.sum((qkvo(x, wqkv, wo)).astype(jnp.float32))

    t = _bench(_chain(lambda x, a, b: jax.grad(qkvo_loss)(x, a, b)),
               x, wqkv, wo)
    out.append(("qkvo_fwd_bwd", t, 3 * (2 * B * S * DM * 4 * DM)))

    # mlp d -> 4d -> d with gelu
    w1 = jax.random.normal(k0, (DM, FFN), bf) * 0.02
    w2 = jax.random.normal(k0, (FFN, DM), bf) * 0.02

    def mlp_loss(x, w1, w2):
        return jnp.sum((jax.nn.gelu(x @ w1) @ w2).astype(jnp.float32))

    t = _bench(_chain(lambda x, a, b: jax.grad(mlp_loss)(x, a, b)), x, w1, w2)
    out.append(("mlp_fwd_bwd", t, 3 * 2 * (2 * B * S * DM * FFN)))

    # lm head [B*S, DM] x [DM, VOCAB]
    wh = jax.random.normal(k0, (DM, VOCAB), bf) * 0.02

    def head_loss(x, wh):
        return jnp.sum((x @ wh).astype(jnp.float32))

    t = _bench(_chain(lambda x, w: jax.grad(head_loss)(x, w)), x, wh)
    out.append(("head_fwd_bwd", t, 3 * 2 * B * S * DM * VOCAB))

    # head + softmax cross-entropy fwd+bwd: the [B*S, VOCAB] log-softmax
    # is a VPU-bound elementwise pass over 1.6G elements that the MFU
    # accounting counts only as the head matmul — if this row's TFLOP/s
    # is far below head_fwd_bwd's, the loss epilogue is a stall term
    labels = jax.random.randint(k0, (B * S,), 0, VOCAB)

    def xent_loss(x, wh, labels):
        logits = (x @ wh).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - picked)

    t = _bench(_chain(lambda x, w, l: jax.grad(xent_loss)(x, w, l)),
               x, wh, labels)
    out.append(("head_xent_fwd_bwd", t, 3 * 2 * B * S * DM * VOCAB))

    # embedding gather fwd + scatter-add bwd: not matmul flops at all —
    # reported against the HBM-traffic-equivalent "flops" of the head
    # matmul row would be meaningless, so count 1 flop/elem-touched and
    # read the row by its ms column (a slow sort-based scatter onto the
    # 50304-row table is a classic TPU stall)
    tok = jax.random.randint(k0, (B * S,), 0, VOCAB)
    wte = jax.random.normal(k0, (VOCAB, DM), bf) * 0.02

    def embed_loss(wte, tok):
        return jnp.sum(wte[tok].astype(jnp.float32))

    t = _bench(_chain(lambda w, tk: jax.grad(embed_loss)(w, tk)), wte, tok)
    out.append(("embed_gather_scatter", t, 2 * B * S * DM))
    return out


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else os.path.join(
        REPO, "chiprun_out", "stall_anatomy.jsonl")
    from bench import peak_bf16_flops
    from deepspeed_tpu.utils.platform import enable_compile_cache, require_tpu
    kind = require_tpu()[0].device_kind
    peak = peak_bf16_flops(kind)
    enable_compile_cache()
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    lines = []
    with open(path, "a") as f:
        f.write(json.dumps({"meta": {"device": kind, "B": B, "S": S,
                                     "H": H, "D": D, "iters": ITERS,
                                     "peak": peak,
                                     "ts": time.strftime("%Y-%m-%dT%H:%M:%S")}
                            }) + "\n")
        for name, sec, flops in rows():
            rec = {"component": name, "ms": round(sec * 1e3, 3),
                   "tflops": round(flops / sec / 1e12, 2),
                   "pct_peak": round(100 * flops / sec / peak, 1)}
            f.write(json.dumps(rec) + "\n")
            f.flush()
            lines.append(rec)
            sys.stderr.write(f"[anatomy] {name}: {rec['ms']} ms "
                             f"{rec['tflops']} TF/s ({rec['pct_peak']}%)\n")
    print("| component | ms/iter | TFLOP/s | % peak |")
    print("|---|---|---|---|")
    for r in lines:
        print(f"| {r['component']} | {r['ms']} | {r['tflops']} "
              f"| {r['pct_peak']} |")


if __name__ == "__main__":
    main()
