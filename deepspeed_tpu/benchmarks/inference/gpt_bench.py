"""Inference latency benchmark: prefill + per-token decode percentiles.

Counterpart of the reference's ``benchmarks/inference/gpt-bench.py``
(:35-50 — per-token latency with p50/p90/p99 reporting).  Two measurement
modes mirror the two serving shapes:

- **per-token** (the reference's loop): one jitted ``decode_step`` per
  emitted token, fenced with ``device_get`` so each sample is a real
  host-visible token latency — the percentile distribution includes
  dispatch jitter, exactly what an autoregressive server sees.
- **fused loop**: ``engine.generate`` compiles the whole decode loop into
  one XLA program (the role CUDA-graph capture plays in the reference);
  reported as amortized tokens/sec for the offline-batch shape.

Usage:
    python -m deepspeed_tpu.benchmarks.inference.gpt_bench \
        --model gpt2-125m --batch 4 --prompt 128 --new-tokens 64
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Any, Dict

import numpy as np


def run_bench(model: str = "gpt2-125m", batch: int = 1, prompt: int = 128,
              new_tokens: int = 64, dtype: str = "bfloat16",
              warmup: int = 3, kv_cache_dtype: str = "auto",
              variant: str = "learned") -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import gpt, gpt_inference

    import dataclasses
    # int8 = weight-only int8 serving: codes + scales in HBM, bf16 compute.
    # int8-compute = TRUE int8 gemms (int8xint8->int32 + scale epilogue) —
    # the compute-bound prefill/batch-serving shape (reference
    # pt_binding.cpp int8 paths).
    config = dataclasses.replace(
        gpt.PRESETS[model],
        dtype=jnp.float32 if dtype == "float32" else jnp.bfloat16)
    # variant rows measure the attention-architecture kernels: 'alibi'
    # = in-kernel bias (BLOOM shape), 'windowed:N' = banded decode whose
    # dead cache blocks are neither computed nor DMA'd (GPT-Neo shape —
    # the decode row should approach O(window) as prompt grows)
    if variant == "alibi":
        config = dataclasses.replace(config, pos_embed="alibi")
    elif variant.startswith("windowed"):
        w = int(variant.split(":", 1)[1]) if ":" in variant else 256
        config = dataclasses.replace(config, local_attention_window=w)
    elif variant != "learned":
        raise ValueError(f"unknown variant {variant!r}")
    params = gpt.init(config, jax.random.PRNGKey(0))
    eng_cfg = ({"dtype": "int8", "quant": {"int8_compute": True}}
               if dtype == "int8-compute" else {"dtype": dtype})
    eng_cfg["kv_cache_dtype"] = kv_cache_dtype
    engine = deepspeed_tpu.init_inference(model=(config, params),
                                          config=eng_cfg)
    # the manual prefill/decode path must use the SAME dtype-cast weights
    # the engine serves with, or the two modes measure different memory
    # traffic under one dtype label
    params = engine.params
    config = engine.model_config
    warmup = max(1, warmup)   # first decode call is the XLA compile
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, config.vocab_size,
                                      size=(batch, prompt)), jnp.int32)

    def fence(x):
        np.asarray(jax.device_get(jax.tree_util.tree_leaves(x)[0]))

    # ---- prefill latency
    # warmup decode steps also occupy cache slots — size for them or the
    # tail of the measured distribution decodes against a clobbered cache
    # round to a 128 multiple like engine.generate does: cached_attention's
    # Pallas path (incl. the int8 in-VMEM dequant kernel) needs a tileable
    # S_max — an odd length would silently measure the dense fallback
    cache_len = -(-(prompt + new_tokens + warmup) // 128) * 128
    cache = gpt_inference.init_cache(
        config, batch, cache_len,
        kv_dtype="int8" if kv_cache_dtype == "int8" else None)
    prefill = jax.jit(lambda p, t, c: gpt_inference.prefill(p, t, config, c))
    logits, cache0 = prefill(params, tokens, cache)
    fence(logits)                                      # compile
    t0 = time.perf_counter()
    logits, cache0 = prefill(params, tokens, cache)
    fence(logits)
    prefill_ms = (time.perf_counter() - t0) * 1000

    # ---- per-token decode latencies (the reference's measurement)
    decode = jax.jit(lambda p, tok, c: gpt_inference.decode_step(
        p, tok, config, c))
    # slice off the padded-vocab tail before argmax (engine.generate's
    # pick does the same) so OOV ids never re-enter decode
    tok = jnp.argmax(logits[:, -1, :config.vocab_size],
                     axis=-1).astype(jnp.int32)
    lat = []
    c = cache0
    for i in range(warmup + new_tokens):
        t0 = time.perf_counter()
        logits_i, c = decode(params, tok, c)
        fence(logits_i)
        if i >= warmup:
            lat.append((time.perf_counter() - t0) * 1000)
        tok = jnp.argmax(logits_i[:, :config.vocab_size],
                         axis=-1).astype(jnp.int32)
    lat = np.asarray(lat)

    # ---- fused whole-loop generate (amortized)
    out = engine.generate(tokens, max_new_tokens=new_tokens)   # compile
    fence(out)
    t0 = time.perf_counter()
    out = engine.generate(tokens, max_new_tokens=new_tokens)
    fence(out)
    fused_s = time.perf_counter() - t0

    return {
        "model": model, "batch": batch, "prompt": prompt,
        "new_tokens": new_tokens, "dtype": dtype,
        "kv_cache_dtype": kv_cache_dtype, "variant": variant,
        "prefill_ms": round(prefill_ms, 2),
        "token_latency_ms": {
            "p50": round(float(np.percentile(lat, 50)), 3),
            "p90": round(float(np.percentile(lat, 90)), 3),
            "p99": round(float(np.percentile(lat, 99)), 3),
            "mean": round(float(lat.mean()), 3),
        },
        "per_token_tokens_per_sec": round(batch * 1000.0 / lat.mean(), 1),
        "fused_loop_tokens_per_sec": round(batch * new_tokens / fused_s, 1),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--model", default="gpt2-125m",
                    help="preset name (see models.gpt.PRESETS)")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--prompt", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=["bfloat16", "float32", "int8", "int8-compute"])
    ap.add_argument("--kv-cache-dtype", default="auto",
                    choices=["auto", "int8"],
                    help="int8 stores the KV cache as codes + per-vector "
                    "scales (half the HBM footprint/stream)")
    ap.add_argument("--variant", default="learned",
                    help="attention architecture row: learned (default), "
                    "alibi (in-kernel bias), or windowed[:N] (banded "
                    "decode with dead-block DMA skip)")
    ap.add_argument("--warmup", type=int, default=3)
    args = ap.parse_args()
    from ...utils.platform import enable_compile_cache
    enable_compile_cache()
    result = run_bench(model=args.model, batch=args.batch,
                       prompt=args.prompt, new_tokens=args.new_tokens,
                       dtype=args.dtype, warmup=args.warmup,
                       kv_cache_dtype=args.kv_cache_dtype,
                       variant=args.variant)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
