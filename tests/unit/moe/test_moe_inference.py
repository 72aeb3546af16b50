"""MoE inference tests (reference moe_inference.py + engine.py:190 role):
KV-cache decode parity against the full forward, engine generate, and
expert-sharded serving on the virtual mesh.

Capacity factors are set generous so no token drops — prefill gates S
tokens jointly while decode gates one, so drop-free configs are the ones
with exact parity (same as the reference's deterministic-eval setting).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt_moe, gpt_moe_inference

#: the family object, as ``models.cache_family`` returns it
MOE = gpt_moe_inference.FAMILY

CFG = gpt_moe.GPTMoEConfig(
    vocab_size=128, max_seq_len=64, n_layer=2, n_head=2, d_model=32,
    dtype=jnp.float32, vocab_round_to=128, num_experts=4, moe_top_k=1,
    eval_capacity_factor=8.0, min_capacity=16)


def _params():
    return gpt_moe.init(CFG, jax.random.PRNGKey(0))


def test_moe_prefill_matches_full_forward():
    params = _params()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 128)
    full, _aux = gpt_moe.apply(params, tokens, CFG, train=False)
    cache = MOE.init_cache(CFG, 2, 32)
    logits, cache = MOE.prefill(params, tokens, CFG, cache)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full),
                               atol=2e-4, rtol=2e-4)
    assert int(cache.length) == 12


def test_moe_decode_matches_full_forward():
    params = _params()
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0, 128)
    full, _ = gpt_moe.apply(params, tokens, CFG, train=False)
    cache = MOE.init_cache(CFG, 2, 32)
    _, cache = MOE.prefill(params, tokens[:, :8], CFG, cache)
    for i in range(8, 12):
        logits, cache = MOE.decode_step(
            params, tokens[:, i], CFG, cache)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, i]),
                                   atol=3e-4, rtol=3e-4, err_msg=f"step {i}")


def test_moe_engine_generate():
    engine = deepspeed_tpu.init_inference(
        model=(CFG, _params()), config={"dtype": "float32"})
    prompt = jnp.zeros((2, 4), jnp.int32)
    out = np.asarray(engine.generate(prompt, max_new_tokens=5))
    assert out.shape == (2, 5)
    assert (out < CFG.vocab_size).all()
    # greedy is deterministic
    np.testing.assert_array_equal(
        out, np.asarray(engine.generate(prompt, max_new_tokens=5)))


def test_moe_expert_sharded_serving_matches_replicated():
    """EP-sharded params (expert mesh axis) serve the same logits."""
    from deepspeed_tpu.parallel.mesh import (ParallelDims, initialize_mesh,
                                             reset_mesh_manager)
    import dataclasses
    params = _params()
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0, 128)
    reset_mesh_manager()
    plain = deepspeed_tpu.init_inference(model=(CFG, params),
                                         config={"dtype": "float32"})
    base = np.asarray(plain(tokens))
    initialize_mesh(ParallelDims(dp=-1, tp=2, ep=2))
    cfg_ep = dataclasses.replace(CFG, ep_size=2)
    sharded = deepspeed_tpu.init_inference(
        model=(cfg_ep, params),
        config={"dtype": "float32", "tensor_parallel": {"tp_size": 2}})
    got = np.asarray(sharded(tokens))
    np.testing.assert_allclose(got, base, atol=2e-4, rtol=2e-4)
    reset_mesh_manager()


def test_moe_int8_weight_only_serving():
    """Weight-only int8 serves the MoE family through the same Int8Param
    duck-typing as dense GPT (expert wi/wo and the attention stacks store
    int8 codes; the gate/coefficient read dequantizes in the consuming
    matmul).  Perplexity must track the fp-engine closely."""
    import dataclasses

    from deepspeed_tpu.inference.quantization import Int8Param
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = gpt_moe.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 128, size=(2, 32)), jnp.int32)

    bf16 = deepspeed_tpu.init_inference(model=(cfg, params),
                                        config={"dtype": "bfloat16"})
    int8 = deepspeed_tpu.init_inference(model=(cfg, params),
                                        config={"dtype": "int8"})
    # the expert stacks really store int8 codes
    moe_blocks = int8.params["moe_blocks"]
    assert isinstance(moe_blocks["experts"]["wi"], Int8Param)
    assert moe_blocks["experts"]["wi"].q.dtype == jnp.int8
    assert isinstance(int8.params["moe_attn_blocks"]["wqkv"], Int8Param)
    # gate router stays full precision (tiny, routing-critical)
    assert not isinstance(moe_blocks["gate"]["wg"], Int8Param)

    def loss(logits):
        lg = logits[:, :-1, :cfg.vocab_size].astype(jnp.float32)
        tg = tokens[:, 1:]
        return float(jnp.mean(jax.nn.logsumexp(lg, axis=-1) -
                              jnp.take_along_axis(lg, tg[..., None],
                                                  axis=-1)[..., 0]))

    l_bf16, l_int8 = loss(bf16.forward(tokens)), loss(int8.forward(tokens))
    assert abs(np.exp(l_int8) / np.exp(l_bf16) - 1.0) < 0.02, (l_bf16, l_int8)
    out = int8.generate(tokens[:, :8], max_new_tokens=4)
    assert out.shape == (2, 4) and (np.asarray(out) < cfg.vocab_size).all()


def test_moe_inference_dropless_under_skewed_routing():
    """Inference gating is dropless (``_moe_infer_obj``): with a config
    whose EVAL capacity would drop tokens (cf=0.25, min_capacity=1 → a
    capacity-gated 8-token chunk gets 1 slot/expert), a multi-token
    ``extend`` must still match token-by-token ``decode_step`` exactly —
    the contract the speculative verify pass rides.  Capacity-gated
    inference would make the two paths route (and answer) differently."""
    cfg = gpt_moe.GPTMoEConfig(
        vocab_size=128, max_seq_len=64, n_layer=2, n_head=2, d_model=32,
        dtype=jnp.float32, vocab_round_to=128, num_experts=8, moe_top_k=2,
        capacity_factor=0.25, eval_capacity_factor=0.25, min_capacity=1)
    params = gpt_moe.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompt = jnp.asarray(rng.integers(0, 128, size=(1, 6)), jnp.int32)
    chunk = jnp.asarray(rng.integers(0, 128, size=(1, 8)), jnp.int32)

    _, c_ext = MOE.prefill(
        params, prompt, cfg, MOE.init_cache(cfg, 1, 32))
    ext_logits, c_ext = MOE.extend(params, chunk, cfg, c_ext)

    _, c_dec = MOE.prefill(
        params, prompt, cfg, MOE.init_cache(cfg, 1, 32))
    dec = []
    for i in range(8):
        lg, c_dec = MOE.decode_step(params, chunk[:, i],
                                                  cfg, c_dec)
        dec.append(np.asarray(lg))
    np.testing.assert_allclose(np.asarray(ext_logits)[0],
                               np.stack(dec)[:, 0], rtol=2e-5, atol=2e-5)


def test_moe_extend_overflow_raises():
    params = _params()
    cache = MOE.init_cache(CFG, 1, 16)
    _, cache = MOE.prefill(
        params, jnp.zeros((1, 12), jnp.int32), CFG, cache)
    with pytest.raises(ValueError, match="overflows the cache"):
        MOE.extend(params, jnp.zeros((1, 8), jnp.int32),
                                 CFG, cache)


def test_moe_long_prompt_prefill_chunks_match_single_shot(monkeypatch):
    """Prompts above _PREFILL_CHUNK gated tokens walk through extend();
    the logits must equal the single-shot gated pass (dropless gating is
    per-token independent, so chunking cannot change routing)."""
    cfg = gpt_moe.GPTMoEConfig(
        vocab_size=128, max_seq_len=256, n_layer=2, n_head=2, d_model=32,
        dtype=jnp.float32, vocab_round_to=128, num_experts=4, moe_top_k=2)
    params = gpt_moe.init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 128, (1, 150)),
                         jnp.int32)
    chunked, c1 = MOE.prefill(
        params, tokens, cfg, MOE.init_cache(cfg, 1, 160))
    monkeypatch.setattr(gpt_moe_inference, "_PREFILL_CHUNK", 10_000)
    single, c2 = MOE.prefill(
        params, tokens, cfg, MOE.init_cache(cfg, 1, 160))
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(single),
                               rtol=2e-5, atol=2e-5)
    assert int(c1.length) == int(c2.length) == 150
    np.testing.assert_allclose(np.asarray(c1.k[:, :, :150]),
                               np.asarray(c2.k[:, :, :150]),
                               rtol=2e-5, atol=2e-5)


# compile-heavy: full-suite / slow tier only (fast tier = pytest -m "not slow")
import pytest as _pytest_tier
pytestmark = _pytest_tier.mark.slow


def test_moe_int8_cache_decode_tracks_fp_cache():
    """MoE int8 KV: prefill + decode through quantized banks tracks the
    fp cache within per-vector int8 error, and the scale banks advance
    with the cache (same contract as the dense family's int8 cache)."""
    params = _params()
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, 128, size=(2, 12)), jnp.int32)
    c_fp = MOE.init_cache(CFG, 2, 32)
    c_q = MOE.init_cache(CFG, 2, 32, kv_dtype="int8")
    assert c_q.int8 and c_q.k.dtype == jnp.int8
    assert c_q.k_scale.shape == (CFG.n_layer, 2, 32, CFG.n_head)

    lg_fp, c_fp = MOE.prefill(params, tokens[:, :8], CFG, c_fp)
    lg_q, c_q = MOE.prefill(params, tokens[:, :8], CFG, c_q)
    # prefill attends to the fresh unpadded fp k/v — logits identical
    np.testing.assert_allclose(np.asarray(lg_q), np.asarray(lg_fp),
                               atol=1e-5, rtol=1e-5)
    for i in range(8, 12):
        lfp, c_fp = MOE.decode_step(params, tokens[:, i],
                                                  CFG, c_fp)
        lq, c_q = MOE.decode_step(params, tokens[:, i],
                                                CFG, c_q)
        np.testing.assert_allclose(np.asarray(lq), np.asarray(lfp),
                                   atol=0.05, rtol=0.05,
                                   err_msg=f"step {i}")
    assert int(c_q.length) == 12


def test_moe_ragged_decode_matches_per_row():
    """Ragged MoE decode: right-padded rows with per-row lengths must
    produce the same logits as decoding each row alone (dropless gating
    keeps routing per-token, so batching cannot perturb a row)."""
    params = _params()
    rng = np.random.default_rng(4)
    full = jnp.asarray(rng.integers(0, 128, size=(2, 10)), jnp.int32)
    lens = np.asarray([6, 10])
    padded = np.array(full)  # writable copy
    padded[0, 6:] = 0
    padded = jnp.asarray(padded)

    # batched ragged: prefill the padded batch, then 3 ragged steps
    cache = MOE.init_cache(CFG, 2, 32)
    lg, cache = MOE.prefill(params, padded, CFG, cache)
    pos = jnp.asarray(lens, jnp.int32)
    nxt = jnp.argmax(lg[jnp.arange(2), pos - 1, :128], -1).astype(jnp.int32)
    ragged_logits = []
    for _ in range(3):
        lgs, cache = MOE.decode_step(params, nxt, CFG, cache,
                                                   lengths=pos)
        ragged_logits.append(np.asarray(lgs))
        nxt = jnp.argmax(lgs[:, :128], -1).astype(jnp.int32)
        pos = pos + 1

    # per-row solo runs
    for row in range(2):
        L = int(lens[row])
        c1 = MOE.init_cache(CFG, 1, 32)
        lg1, c1 = MOE.prefill(params, full[row:row + 1, :L],
                                            CFG, c1)
        n1 = jnp.argmax(lg1[:, -1, :128], -1).astype(jnp.int32)
        for s in range(3):
            l1, c1 = MOE.decode_step(params, n1, CFG, c1)
            np.testing.assert_allclose(ragged_logits[s][row],
                                       np.asarray(l1)[0],
                                       rtol=2e-5, atol=2e-5,
                                       err_msg=f"row {row} step {s}")
            n1 = jnp.argmax(l1[:, :128], -1).astype(jnp.int32)


def test_moe_engine_ragged_generate():
    """Engine-level ragged MoE serving (refusal removed): right-padded
    prompts with prompt_lens decode per-row."""
    import deepspeed_tpu
    params = _params()
    eng = deepspeed_tpu.init_inference(model=(CFG, params),
                                       config={"dtype": "float32"})
    prompt = jnp.asarray(
        np.random.default_rng(5).integers(0, 128, (2, 10)), jnp.int32)
    out = eng.generate(prompt, max_new_tokens=4, prompt_lens=[6, 10])
    assert np.asarray(out).shape == (2, 4)
    # row 1 (full-length) must match the uniform path
    solo = eng.generate(prompt[1:], max_new_tokens=4)
    np.testing.assert_array_equal(np.asarray(out)[1], np.asarray(solo)[0])
