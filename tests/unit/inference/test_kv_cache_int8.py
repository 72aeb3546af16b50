"""Int8 KV cache (beyond-reference; see ops/pallas/decode_attention.py):
codes + per-vector fp32 scales halve the cache's HBM footprint and the
decode kernel's memory stream.  Decode is memory-bound, so this is the
serving-side twin of weight-only int8.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt, gpt_inference
from deepspeed_tpu.ops.pallas.decode_attention import (
    cached_attention, cached_attention_reference, decode_sweep,
    dequantize_kv, quantize_kv)


def _blocks_of_256(pos, B, Smax, window=None):
    """The single-token sweep's work list for blocks of 256 tokens, two a
    512-token slot: the rule gives these narrow rows one block of 512
    (``decode_block_k``, PR 46), and the kernel takes its block from the
    list it is handed."""
    return decode_sweep(pos, B, Smax, 256, None, window)


CFG = gpt.GPTConfig(vocab_size=256, max_seq_len=256, n_layer=2, n_head=4,
                    d_model=64, dtype=jnp.float32, vocab_round_to=128)


@pytest.fixture
def pallas_interpret(monkeypatch):
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")


def test_quantize_kv_roundtrip():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4, 64), jnp.float32)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 16, 4, 1)
    back = dequantize_kv(q, s, jnp.float32)
    rel = float(jnp.linalg.norm(back - x) / jnp.linalg.norm(x))
    assert rel < 0.01, rel


@pytest.mark.parametrize("pos", [5, 100, [3, 120]])
def test_int8_decode_kernel_matches_fp(pallas_interpret, pos):
    """The in-VMEM dequant kernel must match the fp reference attention on
    the dequantized cache exactly (same math, half the HBM stream), and
    track the ORIGINAL fp cache within int8 quantization error."""
    B, Smax, H, D = 2, 256, 4, 64
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, 1, H, D), jnp.float32)
    ck = jax.random.normal(kk, (B, Smax, H, D), jnp.float32)
    cv = jax.random.normal(kv, (B, Smax, H, D), jnp.float32)
    pos = jnp.asarray(pos, jnp.int32)
    ck_q, ck_s = quantize_kv(ck)
    cv_q, cv_s = quantize_kv(cv)

    out_int8 = cached_attention(q, ck_q, cv_q, pos, k_scale=ck_s,
                                v_scale=cv_s)
    # exact vs the dense reference on the dequantized cache
    ref_deq = cached_attention_reference(
        q, dequantize_kv(ck_q, ck_s, jnp.float32),
        dequantize_kv(cv_q, cv_s, jnp.float32), pos)
    np.testing.assert_allclose(np.asarray(out_int8), np.asarray(ref_deq),
                               atol=2e-5, rtol=2e-5)
    # close to the original fp cache (per-vector int8 error only)
    ref_fp = cached_attention_reference(q, ck, cv, pos)
    np.testing.assert_allclose(np.asarray(out_int8), np.asarray(ref_fp),
                               atol=0.03, rtol=0.03)


def test_int8_cache_decode_matches_fp_cache():
    """Full decode path: int8-cache decode tracks fp-cache decode across
    steps, through the non-kernel fallback (CPU) and the rotary family."""
    import dataclasses
    for cfg in (CFG, dataclasses.replace(CFG, pos_embed="rotary")):
        params = gpt.init(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0, 256)
        cache_fp = gpt_inference.init_cache(cfg, 2, 64)
        cache_q = gpt_inference.init_cache(cfg, 2, 64, kv_dtype="int8")
        assert cache_q.k.dtype == jnp.int8 and cache_q.int8
        assert cache_q.k_scale.shape == (cfg.n_layer, 2, 64, cfg.n_head)

        lg_fp, cache_fp = gpt_inference.prefill(params, tokens[:, :8], cfg,
                                                cache_fp)
        lg_q, cache_q = gpt_inference.prefill(params, tokens[:, :8], cfg,
                                              cache_q)
        # prefill logits identical: prefill attends to the unpadded fp k/v
        np.testing.assert_allclose(np.asarray(lg_q), np.asarray(lg_fp),
                                   atol=1e-5, rtol=1e-5)
        for i in range(8, 12):
            lfp, cache_fp = gpt_inference.decode_step(params, tokens[:, i],
                                                      cfg, cache_fp)
            lq, cache_q = gpt_inference.decode_step(params, tokens[:, i],
                                                    cfg, cache_q)
            # int8 cache error stays small through the whole stack
            np.testing.assert_allclose(np.asarray(lq), np.asarray(lfp),
                                       atol=0.05, rtol=0.05,
                                       err_msg=f"step {i} ({cfg.pos_embed})")


def test_engine_kv_cache_int8_generate():
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, 256)
    base = deepspeed_tpu.init_inference(model=(CFG, params),
                                        config={"dtype": "float32"})
    q = deepspeed_tpu.init_inference(
        model=(CFG, params),
        config={"dtype": "float32", "kv_cache_dtype": "int8"})
    out_b = np.asarray(base.generate(prompt, max_new_tokens=8))
    out_q = np.asarray(q.generate(prompt, max_new_tokens=8))
    assert out_q.shape == (2, 8)
    # greedy agreement: int8 cache noise can flip near-ties on random
    # init, but most steps must agree
    agree = float(np.mean(out_q == out_b))
    assert agree >= 0.5, (agree, out_q, out_b)
    # ragged prompts ride the same int8 cache path
    out_r = q.generate(prompt, max_new_tokens=4, prompt_lens=[10, 16])
    assert np.asarray(out_r).shape == (2, 4)


def test_kv_cache_dtype_validation():
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        deepspeed_tpu.init_inference(
            model=(CFG, params),
            config={"dtype": "float32", "kv_cache_dtype": "int4"})


def test_kv_cache_int8_serves_moe():
    """Both MoE cache banks quantize on append: int8-cache generate must
    run and mostly agree with the fp-cache engine (int8 noise can flip
    near-ties on random init)."""
    from deepspeed_tpu.models import gpt_moe
    mcfg = gpt_moe.GPTMoEConfig(vocab_size=128, max_seq_len=64, n_layer=2,
                                n_head=2, d_model=32, dtype=jnp.float32,
                                vocab_round_to=128, num_experts=2)
    mparams = gpt_moe.init(mcfg, jax.random.PRNGKey(0))
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, (2, 10)), jnp.int32)
    base = deepspeed_tpu.init_inference(
        model=(mcfg, mparams), config={"dtype": "float32"})
    q = deepspeed_tpu.init_inference(
        model=(mcfg, mparams),
        config={"dtype": "float32", "kv_cache_dtype": "int8"})
    out_b = np.asarray(base.generate(prompt, max_new_tokens=8))
    out_q = np.asarray(q.generate(prompt, max_new_tokens=8))
    assert out_q.shape == (2, 8)
    agree = float(np.mean(out_q == out_b))
    assert agree >= 0.5, (agree, out_q, out_b)
    # ragged prompts compose with the int8 MoE cache too
    out_r = q.generate(prompt, max_new_tokens=4, prompt_lens=[6, 10])
    assert np.asarray(out_r).shape == (2, 4)


@pytest.mark.parametrize("variant", [dict(pos_embed="alibi"),
                                     dict(local_attention_window=32)])
def test_kv_cache_int8_serves_alibi_and_windowed(variant):
    """Alibi/windowed models now ride the streaming kernels (bias /
    band + block skip in VMEM), so int8 KV is legal for them — the
    engine must serve, and mostly agree with the auto-cache engine."""
    import dataclasses
    cfg = dataclasses.replace(CFG, **variant)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(0, 256, (2, 16)), jnp.int32)
    base = deepspeed_tpu.init_inference(
        model=(cfg, params), config={"dtype": "float32"})
    q = deepspeed_tpu.init_inference(
        model=(cfg, params),
        config={"dtype": "float32", "kv_cache_dtype": "int8"})
    out_b = np.asarray(base.generate(prompt, max_new_tokens=8))
    out_q = np.asarray(q.generate(prompt, max_new_tokens=8))
    assert out_q.shape == (2, 8)
    agree = float(np.mean(out_q == out_b))
    assert agree >= 0.5, (agree, out_q, out_b)
    # ragged prompts compose with the int8 MoE cache too
    out_r = q.generate(prompt, max_new_tokens=4, prompt_lens=[6, 10])
    assert np.asarray(out_r).shape == (2, 4)


# ------------------------------------------------ window/alibi kernel parity

@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("pos,window,Smax", [
    (5, 32, 256), (100, 32, 256), (200, 7, 256), ([3, 120], 16, 256),
    # multi-block cache (block_k=256, nk=2): block 0 is wholly below the
    # band and must be SKIPPED — exercises the live-range algebra
    (300, 32, 512), ([40, 400], 64, 512)])
def test_windowed_decode_kernel_matches_model_semantics(pallas_interpret,
                                                        int8, pos, window,
                                                        Smax):
    """The streaming decode kernel's band (visibility + block skip) must
    match gpt._windowed_attention — the single source of banded semantics
    for train/prefill — on a padded cache, for fp and int8 caches."""
    import dataclasses
    B, H, D = 2, 4, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(kq, (B, 1, H, D), jnp.float32)
    ck = jax.random.normal(kk, (B, Smax, H, D), jnp.float32)
    cv = jax.random.normal(kv, (B, Smax, H, D), jnp.float32)
    pos = jnp.asarray(pos, jnp.int32)
    sweep = _blocks_of_256(pos, B, Smax, jnp.int32(window))
    if int8:
        (ck_s, ck_sc), (cv_s, cv_sc) = quantize_kv(ck), quantize_kv(cv)
        got = cached_attention(q, ck_s, cv_s, pos, k_scale=ck_sc,
                               v_scale=cv_sc, window=jnp.int32(window),
                               sweep=sweep)
        ck = dequantize_kv(ck_s, ck_sc, jnp.float32)
        cv = dequantize_kv(cv_s, cv_sc, jnp.float32)
    else:
        got = cached_attention(q, ck, cv, pos, window=jnp.int32(window),
                               sweep=sweep)
    mcfg = dataclasses.replace(CFG, n_head=H,
                               local_attention_window=window)
    want = gpt._windowed_attention(q, ck, cv, mcfg, window, pos=pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("pos,sq,Smax", [(5, 1, 256), (100, 1, 256),
                                         (37, 8, 256), (0, 128, 256),
                                         (300, 1, 512), (290, 8, 512)])
def test_alibi_kernels_match_model_semantics(pallas_interpret, int8, pos,
                                             sq, Smax):
    """Decode (Sq=1) and chunk (Sq>1) kernels with the ALiBi bias must
    match gpt._alibi_attention (pinned elsewhere against HF BLOOM)."""
    import dataclasses
    B, H, D = 2, 4, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (B, sq, H, D), jnp.float32)
    ck = jax.random.normal(kk, (B, Smax, H, D), jnp.float32)
    cv = jax.random.normal(kv, (B, Smax, H, D), jnp.float32)
    pos_arr = jnp.asarray(pos, jnp.int32)
    slopes = gpt.alibi_slopes(H)
    # alibi models use the default 1/sqrt(D) scale (BLOOM)
    sweep = _blocks_of_256(pos_arr, B, Smax) if sq == 1 else None
    if int8:
        (ck_s, ck_sc), (cv_s, cv_sc) = quantize_kv(ck), quantize_kv(cv)
        got = cached_attention(q, ck_s, cv_s, pos_arr, k_scale=ck_sc,
                               v_scale=cv_sc, slopes=slopes, sweep=sweep)
        ck = dequantize_kv(ck_s, ck_sc, jnp.float32)
        cv = dequantize_kv(cv_s, cv_sc, jnp.float32)
    else:
        got = cached_attention(q, ck, cv, pos_arr, slopes=slopes,
                               sweep=sweep)
    mcfg = dataclasses.replace(CFG, n_head=H, pos_embed="alibi")
    want = gpt._alibi_attention(q, ck, cv, mcfg,
                                q_positions=pos_arr + jnp.arange(sq))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("pos,sq,window,Smax", [
    (37, 8, 16, 256), (100, 128, 32, 256), (0, 128, 8, 256),
    # multi-block cache, chunk straddling block 0/1: block 0 executes
    # (visible to early rows) but is FULLY masked for late rows whose
    # band lies in block 1 — a -inf running max would nan those rows
    # (the M_FLOOR guard's reason to exist)
    (200, 128, 32, 512)])
def test_windowed_chunk_kernel_matches_model_semantics(pallas_interpret,
                                                       int8, pos, sq,
                                                       window, Smax):
    """Chunked extend with a band: some streamed blocks are fully masked
    for part of their q rows (the M_FLOOR guard's reason to exist)."""
    import dataclasses
    B, H, D = 2, 4, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(kq, (B, sq, H, D), jnp.float32)
    ck = jax.random.normal(kk, (B, Smax, H, D), jnp.float32)
    cv = jax.random.normal(kv, (B, Smax, H, D), jnp.float32)
    pos_arr = jnp.asarray(pos, jnp.int32)
    if int8:
        (ck_s, ck_sc), (cv_s, cv_sc) = quantize_kv(ck), quantize_kv(cv)
        got = cached_attention(q, ck_s, cv_s, pos_arr, k_scale=ck_sc,
                               v_scale=cv_sc, window=jnp.int32(window))
        ck = dequantize_kv(ck_s, ck_sc, jnp.float32)
        cv = dequantize_kv(cv_s, cv_sc, jnp.float32)
    else:
        got = cached_attention(q, ck, cv, pos_arr, window=jnp.int32(window))
    mcfg = dataclasses.replace(CFG, n_head=H,
                               local_attention_window=window)
    want = gpt._windowed_attention(q, ck, cv, mcfg, window, pos=pos_arr)
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------- chunk kernel (extend)

@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("pos,sq", [(0, 128), (100, 128), (37, 8)])
def test_chunk_kernel_matches_dense_reference(pallas_interpret, int8, pos, sq):
    """The chunked-prefill kernel (online softmax per q row, cache blocks
    streamed) must match the dense reference exactly for fp caches and
    track it within int8 error for quantized ones."""
    B, Smax, H, D = 2, 256, 4, 64
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (B, sq, H, D), jnp.float32)
    ck = jax.random.normal(keys[1], (B, Smax, H, D), jnp.float32)
    cv = jax.random.normal(keys[2], (B, Smax, H, D), jnp.float32)
    p = jnp.asarray(pos, jnp.int32)
    if int8:
        ck_q, ck_s = quantize_kv(ck)
        cv_q, cv_s = quantize_kv(cv)
        out = cached_attention(q, ck_q, cv_q, p, k_scale=ck_s, v_scale=cv_s)
        ref = cached_attention_reference(
            q, dequantize_kv(ck_q, ck_s, jnp.float32),
            dequantize_kv(cv_q, cv_s, jnp.float32), p)
    else:
        out = cached_attention(q, ck, cv, p)
        ref = cached_attention_reference(q, ck, cv, p)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


def test_extend_rides_chunk_kernel(pallas_interpret, monkeypatch):
    """gpt_inference.extend over a tileable cache routes through the
    chunk kernel — the dense fallback is poisoned to prove the routing —
    and still composes exactly with one-shot prefill."""
    from deepspeed_tpu.ops.pallas import decode_attention as da
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(8), (1, 136), 0, 256)
    full, _ = gpt_inference.prefill(
        params, tokens, CFG, gpt_inference.init_cache(CFG, 1, 256))
    _, cache = gpt_inference.prefill(
        params, tokens[:, :8], CFG, gpt_inference.init_cache(CFG, 1, 256))

    def boom(*a, **k):
        raise AssertionError("extend fell back to the dense reference")

    monkeypatch.setattr(da, "cached_attention_reference", boom)
    # 128-token chunk: block_q=128 tiles -> kernel path
    ext, cache = gpt_inference.extend(params, tokens[:, 8:], CFG, cache)
    np.testing.assert_allclose(np.asarray(ext),
                               np.asarray(full[:, 8:]),
                               atol=3e-4, rtol=3e-4)


# compile-heavy: full-suite / slow tier only (fast tier = pytest -m "not slow")
import pytest as _pytest_tier
pytestmark = _pytest_tier.mark.slow


@pytest.mark.parametrize("variant", [dict(pos_embed="alibi"),
                                     dict(local_attention_window=32),
                                     dict(local_attention_window=32,
                                          local_attention_alternating=True)])
@pytest.mark.parametrize("masked", [False, True], ids=["all-live", "masked"])
def test_streaming_decode_traced_window_under_jit(pallas_interpret, variant,
                                                  masked):
    """Integration: decode_step through the model stack with the kernels
    ON (interpret mode) — the window arrives as a TRACED per-layer scalar
    from gpt.layer_window inside the layer scan, each layer's sweep is
    picked from the lists built once before it, and the whole step runs
    under jit, exercising the scalar-prefetch build end-to-end.  Must
    match the no-kernel (dense fallback) decode in fp32; ``masked`` steps a
    slot batch whose second row is dead (the first row's logits are then
    what they were, on either path)."""
    import dataclasses
    import os
    cfg = dataclasses.replace(CFG, **variant)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 12), 0, 256)
    active = jnp.asarray([True, False]) if masked else None
    live = slice(0, 1) if masked else slice(None)

    def run():
        cache = gpt_inference.init_cache(cfg, 2, 256)
        _, cache = gpt_inference.prefill(params, tokens[:, :8], cfg, cache)
        step = jax.jit(lambda t, c, l: gpt_inference.decode_step(
            params, t, cfg, c, lengths=l, active=active))
        outs = []
        for i in range(8, 12):
            # all live: the cache's own scalar frontier; masked: per row
            lg, cache = step(tokens[:, i], cache,
                             jnp.full((2,), i, jnp.int32) if masked else None)
            outs.append(np.asarray(lg)[live])
        return np.stack(outs)

    with_kernel = run()
    os.environ["DS_TPU_PALLAS_INTERPRET"] = "0"
    try:
        dense = run()
    finally:
        os.environ["DS_TPU_PALLAS_INTERPRET"] = "1"
    assert np.isfinite(with_kernel).all()
    np.testing.assert_allclose(with_kernel, dense, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("window", [None, 32])
def test_ragged_chunk_kernel_matches_reference(pallas_interpret, int8,
                                               window):
    """Per-row-pos CHUNKS (batched speculative verify: each row's K+1
    tokens sit at ITS frontier): the chunk kernel reads its row's pos
    from SMEM everywhere, so ragged chunks must match the dense
    reference exactly."""
    B, Sq, Smax, H, D = 3, 8, 512, 4, 64
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(kq, (B, Sq, H, D), jnp.float32)
    ck = jax.random.normal(kk, (B, Smax, H, D), jnp.float32)
    cv = jax.random.normal(kv, (B, Smax, H, D), jnp.float32)
    pos = jnp.asarray([7, 130, 301], jnp.int32)   # rows straddle blocks
    win = None if window is None else jnp.int32(window)
    if int8:
        (ck_s, ck_sc), (cv_s, cv_sc) = quantize_kv(ck), quantize_kv(cv)
        got = cached_attention(q, ck_s, cv_s, pos, k_scale=ck_sc,
                               v_scale=cv_sc, window=win)
        ck = dequantize_kv(ck_s, ck_sc, jnp.float32)
        cv = dequantize_kv(cv_s, cv_sc, jnp.float32)
    else:
        got = cached_attention(q, ck, cv, pos, window=win)
    want = cached_attention_reference(q, ck, cv, pos, window=win)
    assert not np.isnan(np.asarray(got)).any()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# the stacked pool [L, B, Smax, H*D] read where it lies, in blocks of 256, so
# the per-row frontiers below sit on both sides of a block edge
_POOL_SMAX = 512
_POOL_CASES = {
    "scalar-pos": dict(pos=100),
    "row-pos-block-edges": dict(pos=[0, 255, 256, _POOL_SMAX - 1]),
    "empty-beside-full": dict(pos=[0, _POOL_SMAX - 1, _POOL_SMAX - 1, 0]),
    "window": dict(pos=[40, 255, 256, _POOL_SMAX - 1], window=48),
    "slopes": dict(pos=[0, 255, 300, _POOL_SMAX - 1], slopes=True),
}


@pytest.mark.parametrize("case", sorted(_POOL_CASES))
@pytest.mark.parametrize("kind", ["f32", "bf16", "int8"])
def test_stacked_pool_decode_kernel_matches_reference(pallas_interpret, kind,
                                                      case):
    """The decode kernel on the pool as ``gpt_inference`` stores it, layer
    picked by a TRACED index other than 0 (every layer holds other values,
    so a wrong layer offset fails), against the dense reference on that
    layer."""
    spec = _POOL_CASES[case]
    L, B, Smax, H, D = 3, 4, _POOL_SMAX, 4, 32
    dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(kq, (B, 1, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (L, B, Smax, H, D), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (L, B, Smax, H, D), jnp.float32).astype(dtype)
    pos = jnp.asarray(spec["pos"], jnp.int32)
    window = jnp.int32(spec["window"]) if "window" in spec else None
    slopes = gpt.alibi_slopes(H) if spec.get("slopes") else None

    def fold(x):                     # [L,B,S,H,*] -> [L,B,S,H * *]
        return x.reshape(x.shape[:3] + (-1,))

    scales = {}
    if kind == "int8":
        (k, k_s), (v, v_s) = quantize_kv(k), quantize_kv(v)
        scales = dict(k_scale=fold(k_s), v_scale=fold(v_s))
        ref_k = dequantize_kv(k, k_s, jnp.float32)
        ref_v = dequantize_kv(v, v_s, jnp.float32)
    else:
        ref_k, ref_v = k.astype(jnp.float32), v.astype(jnp.float32)

    layer = 2
    got = jax.jit(lambda lay: cached_attention(
        q, fold(k), fold(v), pos, window=window, slopes=slopes, layer=lay,
        sweep=_blocks_of_256(pos, B, Smax, window), **scales))(
            jnp.int32(layer))
    want = cached_attention_reference(
        q.astype(jnp.float32), ref_k[layer], ref_v[layer], pos,
        window=window, slopes=slopes)
    tol = 2e-2 if kind == "bf16" else 2e-5
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=tol, rtol=tol)
    # the neighbouring layer is another answer: the index is honoured
    other = cached_attention_reference(
        q.astype(jnp.float32), ref_k[layer - 1], ref_v[layer - 1], pos,
        window=window, slopes=slopes)
    assert float(jnp.max(jnp.abs(other - want))) > 10 * tol
