"""Speculative decoding (inference/speculative.py): greedy draft-and-verify
must emit BIT-IDENTICAL tokens to the target model decoding alone — the
draft only changes how many target forwards it takes."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.inference.speculative import speculative_generate
from deepspeed_tpu.models import gpt

TARGET = gpt.GPTConfig(vocab_size=256, max_seq_len=256, n_layer=2, n_head=4,
                       d_model=64, dtype=jnp.float32, vocab_round_to=128)
DRAFT = gpt.GPTConfig(vocab_size=256, max_seq_len=256, n_layer=1, n_head=2,
                      d_model=32, dtype=jnp.float32, vocab_round_to=128)


def _models():
    return (gpt.init(TARGET, jax.random.PRNGKey(0)),
            gpt.init(DRAFT, jax.random.PRNGKey(1)))


_TRAINED = {}


def _train(cfg, steps=80, lr=3e-3):
    """Train on the affine rule t[i+1] = (3 t[i] + 7) % V: the greedy
    continuation then CHANGES token every step — a random-init model
    emits a constant token, which cannot catch off-by-one emission bugs
    (one hid behind exactly that degeneracy).  Cached per (cfg, steps)
    across the module's tests."""
    key = (repr(cfg), steps)
    if key in _TRAINED:
        return _TRAINED[key]
    from deepspeed_tpu.parallel.mesh import (ParallelDims, initialize_mesh,
                                             reset_mesh_manager)
    from deepspeed_tpu.runtime.model import from_gpt
    reset_mesh_manager()
    rows = []
    for s in range(8):
        t = [(s * 17 + 3) % 256]
        for _ in range(48):
            t.append((t[-1] * 3 + 7) % 256)
        rows.append(t)
    data = np.asarray(rows, np.int32)
    mm = initialize_mesh(ParallelDims(dp=-1))
    eng, _, _, _ = deepspeed_tpu.initialize(
        model=from_gpt(cfg),
        config={"train_micro_batch_size_per_gpu": 8 // mm.dp_world_size,
                "gradient_accumulation_steps": 1,
                "optimizer": {"type": "Adam", "params": {"lr": lr}},
                "zero_optimization": {"stage": 1},
                "steps_per_print": 1 << 30},
        mesh_manager=mm, rng=jax.random.PRNGKey(0))
    for _ in range(steps):
        eng.train_batch_fused({"tokens": data})
    _TRAINED[key] = jax.tree_util.tree_map(
        lambda l: jnp.asarray(np.asarray(jax.device_get(l), np.float32)),
        eng.state["params"])
    return _TRAINED[key]


@pytest.mark.parametrize("draft_k", [1, 3, 5])
def test_speculative_matches_plain_greedy(draft_k):
    """Trained target (token changes every step — shift-sensitive) +
    random draft: output must still be bit-identical to plain greedy."""
    tparams = _train(TARGET)
    _, dparams = _models()
    prompt = jnp.asarray([[3] + [(3 * 3 + 7) % 256]], jnp.int32)
    eng = deepspeed_tpu.init_inference(model=(TARGET, tparams),
                                       config={"dtype": "float32"})
    want = np.asarray(eng.generate(prompt, max_new_tokens=16))
    # the trained continuation really is shift-sensitive
    assert (want[0][:-1] != want[0][1:]).all(), want
    got, fwds = speculative_generate(tparams, TARGET, dparams, DRAFT,
                                     prompt, 16, draft_k=draft_k)
    np.testing.assert_array_equal(np.asarray(got), want)
    # even an unrelated random draft costs at most one verify per token
    assert 1 <= int(fwds) <= 16 + 1


def test_speculative_trained_draft_speeds_up():
    """A draft that learned the same rule gets its proposals accepted:
    identical output, strictly fewer target forwards than plain decode."""
    tparams = _train(TARGET)
    dparams = _train(DRAFT, steps=120)
    prompt = jnp.asarray([[3] + [(3 * 3 + 7) % 256]], jnp.int32)
    eng = deepspeed_tpu.init_inference(model=(TARGET, tparams),
                                       config={"dtype": "float32"})
    want = np.asarray(eng.generate(prompt, max_new_tokens=24))
    got, fwds = speculative_generate(tparams, TARGET, dparams, DRAFT,
                                     prompt, 24, draft_k=4)
    np.testing.assert_array_equal(np.asarray(got), want)
    # plain decode = 24 target passes + prefill; speculation must beat it
    assert int(fwds) < 24, int(fwds)


def test_speculative_self_draft_accepts_everything():
    """Draft == target: every proposal verifies, so each round emits
    draft_k+1 tokens and the verify count collapses toward N/(k+1)."""
    tparams, _ = _models()
    prompt = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0, 256)
    got, fwds = speculative_generate(tparams, TARGET, tparams, TARGET,
                                     prompt, 16, draft_k=3)
    eng = deepspeed_tpu.init_inference(model=(TARGET, tparams),
                                       config={"dtype": "float32"})
    want = np.asarray(eng.generate(prompt, max_new_tokens=16))
    np.testing.assert_array_equal(np.asarray(got), want)
    # ceil(16 / (3+1)) verify rounds + the prefill
    assert int(fwds) == 16 // 4 + 1, int(fwds)


def test_engine_generate_speculative():
    tparams, dparams = _models()
    prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 10), 0, 256)
    eng = deepspeed_tpu.init_inference(model=(TARGET, tparams),
                                       config={"dtype": "float32"})
    out, fwds = eng.generate_speculative(prompt, (DRAFT, dparams),
                                         max_new_tokens=12, draft_k=4)
    want = np.asarray(eng.generate(prompt, max_new_tokens=12))
    np.testing.assert_array_equal(np.asarray(out), want)
    # draft engines work as the draft argument too
    deng = deepspeed_tpu.init_inference(model=(DRAFT, dparams),
                                        config={"dtype": "float32"})
    out2, _ = eng.generate_speculative(prompt, deng, max_new_tokens=12,
                                       draft_k=4)
    np.testing.assert_array_equal(np.asarray(out2), want)


def test_speculative_validation():
    tparams, dparams = _models()
    # batched GREEDY is supported; batched SAMPLING refuses clearly
    with pytest.raises(NotImplementedError, match="batch 1"):
        speculative_generate(tparams, TARGET, dparams, DRAFT,
                             jnp.zeros((2, 4), jnp.int32), 4,
                             temperature=0.8)
    other = dataclasses.replace(DRAFT, vocab_size=128)
    with pytest.raises(ValueError, match="vocabulary"):
        speculative_generate(tparams, TARGET, dparams, other,
                             jnp.zeros((1, 4), jnp.int32), 4)


def test_speculative_context_overflow_raises():
    """Near max_seq_len the speculative overshoot must be rejected up
    front — a clamped cache write would silently break the bit-identical
    guarantee."""
    tparams, dparams = _models()
    prompt = jnp.zeros((1, 240), jnp.int32)
    with pytest.raises(ValueError, match="overshoot"):
        speculative_generate(tparams, TARGET, dparams, DRAFT, prompt,
                             16, draft_k=4)   # 240+16+5 > 256


# ---------------------------------------------------- speculative SAMPLING

def test_spec_accept_preserves_target_distribution():
    """The Leviathan/Chen acceptance rule's exactness theorem, checked
    empirically: over draft randomness + accept randomness, the first
    emitted token is distributed exactly as the target distribution —
    for a draft close to, far from, and disjoint-ish from the target."""
    from deepspeed_tpu.inference.speculative import spec_accept
    V = 4
    cases = [
        (jnp.asarray([0.4, 0.3, 0.2, 0.1]), jnp.asarray([0.35, 0.35, 0.2, 0.1])),
        (jnp.asarray([0.7, 0.1, 0.1, 0.1]), jnp.asarray([0.1, 0.1, 0.1, 0.7])),
        (jnp.asarray([0.97, 0.01, 0.01, 0.01]), jnp.asarray([0.01, 0.97, 0.01, 0.01])),
    ]
    n = 40_000
    for t_row, d_row in cases:
        t_probs = jnp.stack([t_row, jnp.full((V,), 0.25)])  # [K+1=2, V]
        d_probs = d_row[None, :]                            # [K=1, V]

        def one(k):
            kd, ka = jax.random.split(k)
            d_tok = jax.random.categorical(kd, jnp.log(d_row))[None]
            a, nxt = spec_accept(ka, d_tok.astype(jnp.int32), d_probs,
                                 t_probs)
            return jnp.where(a >= 1, d_tok[0], nxt)

        toks = jax.vmap(one)(jax.random.split(jax.random.PRNGKey(0), n))
        freq = np.bincount(np.asarray(toks), minlength=V) / n
        np.testing.assert_allclose(freq, np.asarray(t_row), atol=0.012,
                                   err_msg=str((t_row, d_row)))


def test_spec_accept_bonus_is_target_row():
    """All-accepted rounds sample the bonus token from t_probs[K]."""
    from deepspeed_tpu.inference.speculative import spec_accept
    V = 4
    d_row = jnp.asarray([1.0, 0.0, 0.0, 0.0])   # deterministic draft
    t_probs = jnp.stack([jnp.asarray([1.0, 0.0, 0.0, 0.0]),   # always accept
                         jnp.asarray([0.1, 0.2, 0.3, 0.4])])

    def one(k):
        a, nxt = spec_accept(k, jnp.asarray([0], jnp.int32), d_row[None],
                             t_probs)
        return a, nxt

    a, nxt = jax.vmap(one)(jax.random.split(jax.random.PRNGKey(1), 20_000))
    assert int(jnp.min(a)) == 1   # always accepted
    freq = np.bincount(np.asarray(nxt), minlength=V) / 20_000
    np.testing.assert_allclose(freq, [0.1, 0.2, 0.3, 0.4], atol=0.012)


def test_speculative_sampling_generate():
    """temperature > 0: deterministic per key, varies across keys, valid
    tokens; temperature=0 arg reproduces the greedy path exactly."""
    tparams = _train(TARGET)
    dparams = _train(DRAFT, steps=120)
    prompt = jnp.asarray([[3] + [(3 * 3 + 7) % 256]], jnp.int32)
    g0, _ = speculative_generate(tparams, TARGET, dparams, DRAFT, prompt,
                                 12, draft_k=3, temperature=0.0)
    g1, _ = speculative_generate(tparams, TARGET, dparams, DRAFT, prompt,
                                 12, draft_k=3)
    np.testing.assert_array_equal(np.asarray(g0), np.asarray(g1))

    s1, f1 = speculative_generate(tparams, TARGET, dparams, DRAFT, prompt,
                                  12, draft_k=3, temperature=0.8,
                                  key=jax.random.PRNGKey(7))
    s1b, _ = speculative_generate(tparams, TARGET, dparams, DRAFT, prompt,
                                  12, draft_k=3, temperature=0.8,
                                  key=jax.random.PRNGKey(7))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s1b))
    outs = [np.asarray(speculative_generate(
        tparams, TARGET, dparams, DRAFT, prompt, 12, draft_k=3,
        temperature=0.8, key=jax.random.PRNGKey(s))[0]) for s in range(4)]
    assert any(not np.array_equal(outs[0], o) for o in outs[1:]), outs
    assert all((o >= 0).all() and (o < 256).all() for o in outs)
    assert 1 <= int(f1) <= 13
    # the engine surface passes temperature/key through
    eng = deepspeed_tpu.init_inference(model=(TARGET, tparams),
                                       config={"dtype": "float32"})
    out, _ = eng.generate_speculative(prompt, (DRAFT, dparams),
                                      max_new_tokens=8, draft_k=3,
                                      temperature=0.8,
                                      key=jax.random.PRNGKey(2))
    assert np.asarray(out).shape == (1, 8)


def test_filter_logits_shared_semantics():
    """One filter implementation serves generate and the speculative
    sampler: temperature scaling, top-k cut, nucleus cut (first crossing
    token kept), batched shapes."""
    from deepspeed_tpu.inference.sampling import filter_logits
    lg = jnp.log(jnp.asarray([[0.5, 0.25, 0.15, 0.1]]))
    f = filter_logits(lg, 1.0, top_k=2)
    assert np.isfinite(np.asarray(f)[0, :2]).all()
    assert np.isinf(np.asarray(f)[0, 2:]).all()
    # nucleus 0.6: keep 0.5 (inside) + 0.25 (first crossing)
    f = filter_logits(lg, 1.0, top_p=0.6)
    assert np.isfinite(np.asarray(f)[0, :2]).all()
    assert np.isinf(np.asarray(f)[0, 2:]).all()
    # temperature divides before filtering (engine's order)
    np.testing.assert_allclose(np.asarray(filter_logits(lg, 2.0))[0],
                               np.asarray(lg)[0] / 2.0, rtol=1e-6)


def test_speculative_sampling_top_filters():
    """top_k/top_p apply to draft AND target: outputs stay inside the
    target's top-k set at every step, deterministic per key."""
    tparams = _train(TARGET)
    dparams = _train(DRAFT, steps=120)
    prompt = jnp.asarray([[3] + [(3 * 3 + 7) % 256]], jnp.int32)
    eng = deepspeed_tpu.init_inference(model=(TARGET, tparams),
                                       config={"dtype": "float32"})
    out, _ = eng.generate_speculative(prompt, (DRAFT, dparams),
                                      max_new_tokens=10, draft_k=3,
                                      temperature=0.8, top_k=1,
                                      key=jax.random.PRNGKey(5))
    # top_k=1 sampling IS greedy — must equal the greedy path exactly
    want = np.asarray(eng.generate(prompt, max_new_tokens=10))
    np.testing.assert_array_equal(np.asarray(out), want)
    # nucleus run: valid + deterministic per key
    o1, _ = eng.generate_speculative(prompt, (DRAFT, dparams),
                                     max_new_tokens=10, draft_k=3,
                                     temperature=0.8, top_p=0.9,
                                     key=jax.random.PRNGKey(6))
    o2, _ = eng.generate_speculative(prompt, (DRAFT, dparams),
                                     max_new_tokens=10, draft_k=3,
                                     temperature=0.8, top_p=0.9,
                                     key=jax.random.PRNGKey(6))
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert (np.asarray(o1) < 256).all() and (np.asarray(o1) >= 0).all()


def test_filter_logits_top_p_zero_keeps_top_token():
    """top_p<=0 must keep exactly the top token, not silently disable
    the filter (the cutoff-0 index would wrap to the smallest logit)."""
    from deepspeed_tpu.inference.sampling import filter_logits
    lg = jnp.log(jnp.asarray([[0.5, 0.25, 0.15, 0.1]]))
    f = np.asarray(filter_logits(lg, 1.0, top_p=0.0))
    assert np.isfinite(f[0, 0]) and np.isinf(f[0, 1:]).all()


def test_speculative_filters_require_temperature():
    tparams, dparams = _models()
    eng = deepspeed_tpu.init_inference(model=(TARGET, tparams),
                                       config={"dtype": "float32"})
    with pytest.raises(ValueError, match="temperature"):
        eng.generate_speculative(jnp.zeros((1, 4), jnp.int32),
                                 (DRAFT, dparams), top_p=0.9)


def test_moe_extend_composes_with_prefill():
    """MoE chunked prefill: prefill(t[:, :c]) ; extend(t[:, c:]) equals
    one full prefill — the contract the MoE verify pass rides."""
    from deepspeed_tpu.models import gpt_moe, gpt_moe_inference
    mfam = gpt_moe_inference.FAMILY
    cfg = gpt_moe.GPTMoEConfig(
        vocab_size=256, max_seq_len=128, n_layer=2, n_head=4, d_model=64,
        dtype=jnp.float32, vocab_round_to=128,
        num_experts=4, moe_top_k=2, ep_size=1)
    params = gpt_moe.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 256, size=(2, 24)), jnp.int32)

    full_logits, full_cache = mfam.prefill(
        params, tokens, cfg, mfam.init_cache(cfg, 2, 64))
    _, part_cache = mfam.prefill(
        params, tokens[:, :16], cfg, mfam.init_cache(cfg, 2, 64))
    ext_logits, ext_cache = mfam.extend(params, tokens[:, 16:], cfg,
                                        part_cache)
    np.testing.assert_allclose(np.asarray(ext_logits),
                               np.asarray(full_logits[:, 16:]),
                               rtol=2e-5, atol=2e-5)
    assert int(ext_cache.length) == int(full_cache.length) == 24
    np.testing.assert_allclose(np.asarray(ext_cache.k[:, :, :24]),
                               np.asarray(full_cache.k[:, :, :24]),
                               rtol=2e-5, atol=2e-5)


def test_speculative_moe_target_matches_plain_greedy():
    """MoE TARGET + dense draft: greedy speculative output must be
    bit-identical to the MoE model decoding alone (reference MoE
    inference has no speculation at all — this closes the refused
    combo)."""
    from deepspeed_tpu.models import gpt_moe, gpt_moe_inference
    mfam = gpt_moe_inference.FAMILY
    cfg = gpt_moe.GPTMoEConfig(
        vocab_size=256, max_seq_len=256, n_layer=2, n_head=4, d_model=64,
        dtype=jnp.float32, vocab_round_to=128,
        num_experts=4, moe_top_k=2, ep_size=1)
    tparams = gpt_moe.init(cfg, jax.random.PRNGKey(0))
    dparams = gpt.init(DRAFT, jax.random.PRNGKey(1))
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, 256, size=(1, 9)), jnp.int32)
    N = 17

    # plain greedy: prefill + decode_step argmax loop
    logits, cache = mfam.prefill(params=tparams, tokens=prompt, config=cfg,
                                 cache=mfam.init_cache(cfg, 1, 64))
    cur = jnp.argmax(logits[:, -1, :256], -1).astype(jnp.int32)
    plain = []
    for _ in range(N):
        plain.append(int(cur[0]))
        lg, cache = mfam.decode_step(tparams, cur, cfg, cache)
        cur = jnp.argmax(lg[:, :256], -1).astype(jnp.int32)

    spec, fwds = speculative_generate(tparams, cfg, dparams, DRAFT,
                                      prompt, max_new_tokens=N, draft_k=4)
    assert np.asarray(spec)[0, :N].tolist() == plain
    assert int(fwds) <= N + 1  # never worse than plain + prefill


# compile-heavy: full-suite / slow tier only (fast tier = pytest -m "not slow")
import pytest as _pytest_tier
pytestmark = _pytest_tier.mark.slow


@pytest.mark.parametrize("variant", [dict(pos_embed="alibi"),
                                     dict(local_attention_window=16)])
def test_speculative_alibi_windowed_target_matches_plain(variant):
    """Alibi/windowed TARGETS (verify rides the variant-aware extend,
    whose kernels carry the bias/band): greedy speculative output is
    bit-identical to the target decoding alone."""
    cfg = dataclasses.replace(TARGET, **variant)
    tparams = gpt.init(cfg, jax.random.PRNGKey(0))
    dparams = gpt.init(DRAFT, jax.random.PRNGKey(1))
    prompt = jnp.asarray(
        np.random.default_rng(6).integers(0, 256, (1, 9)), jnp.int32)
    eng = deepspeed_tpu.init_inference(model=(cfg, tparams),
                                       config={"dtype": "float32"})
    want = np.asarray(eng.generate(prompt, max_new_tokens=12))
    got, fwds = speculative_generate(tparams, cfg, dparams, DRAFT,
                                     prompt, 12, draft_k=3)
    np.testing.assert_array_equal(np.asarray(got)[:, :12], want)
    assert 1 <= int(fwds) <= 12 + 1


def test_batched_speculative_matches_per_row_greedy():
    """BATCHED greedy speculation (beyond-reference: rows accept
    different draft counts per round, so frontiers diverge and every
    draft/verify step runs ragged): each row's output must be
    bit-identical to that row decoded alone — trained target, so the
    continuations are shift-sensitive and rows genuinely disagree."""
    tparams = _train(TARGET)
    _, dparams = _models()
    # three different prompts on the affine rule → three different
    # continuations (and different accept counts vs the random draft)
    starts = [3, 11, 40]
    prompts = []
    for s in starts:
        seq = [s]
        for _ in range(3):
            seq.append((3 * seq[-1] + 7) % 256)
        prompts.append(seq)
    prompt = jnp.asarray(prompts, jnp.int32)            # [3, 4]
    eng = deepspeed_tpu.init_inference(model=(TARGET, tparams),
                                       config={"dtype": "float32"})
    N = 14
    got, fwds = speculative_generate(tparams, TARGET, dparams, DRAFT,
                                     prompt, N, draft_k=3)
    assert got.shape == (3, N)
    for b in range(3):
        want = np.asarray(eng.generate(prompt[b:b + 1], max_new_tokens=N))
        np.testing.assert_array_equal(np.asarray(got)[b], want[0],
                                      err_msg=f"row {b}")
    # a round advances every active row ≥ 1 token
    assert 1 <= int(fwds) <= N + 1


def test_engine_batched_speculative():
    """Engine surface for batched greedy speculation."""
    tparams = _train(TARGET)
    _, dparams = _models()
    eng = deepspeed_tpu.init_inference(model=(TARGET, tparams),
                                       config={"dtype": "float32"})
    prompt = jnp.asarray([[3, 16, 55], [8, 31, 100]], jnp.int32)
    toks, fwds = eng.generate_speculative(prompt, (DRAFT, dparams),
                                          max_new_tokens=10, draft_k=3)
    assert np.asarray(toks).shape == (2, 10)
    for b in range(2):
        want = np.asarray(eng.generate(prompt[b:b + 1], max_new_tokens=10))
        np.testing.assert_array_equal(np.asarray(toks)[b], want[0])


def test_batched_speculative_moe_target_matches_per_row():
    """Batched greedy speculation with a MoE TARGET: the ragged verify
    rides the MoE dual-bank extend; each row bit-matches its solo run
    (dropless gating keeps ragged rows' routing independent)."""
    from deepspeed_tpu.models import gpt_moe
    cfg = gpt_moe.GPTMoEConfig(
        vocab_size=256, max_seq_len=256, n_layer=2, n_head=4, d_model=64,
        dtype=jnp.float32, vocab_round_to=128,
        num_experts=4, moe_top_k=2, ep_size=1)
    tparams = gpt_moe.init(cfg, jax.random.PRNGKey(0))
    dparams = gpt.init(DRAFT, jax.random.PRNGKey(1))
    prompt = jnp.asarray(np.random.default_rng(8).integers(0, 256, (2, 7)),
                         jnp.int32)
    N = 10
    got, fwds = speculative_generate(tparams, cfg, dparams, DRAFT,
                                     prompt, N, draft_k=3)
    assert got.shape == (2, N)
    eng = deepspeed_tpu.init_inference(model=(cfg, tparams),
                                       config={"dtype": "float32"})
    for b in range(2):
        want = np.asarray(eng.generate(prompt[b:b + 1], max_new_tokens=N))
        np.testing.assert_array_equal(np.asarray(got)[b], want[0],
                                      err_msg=f"row {b}")


def test_spec_accept_batch_per_slot_streams_preserve_target():
    """The serving tick's batched accept: per-slot round keys fan out
    into a DRAFT stream (proposal draws, ``SPEC_DRAFT_DOMAIN + j``) and
    an ACCEPT stream (``SPEC_ACCEPT_DOMAIN``) — disjoint fold-in domains,
    so the accept uniforms are independent of the proposals they judge.
    Checked the only way that matters: with rows holding DIFFERENT
    draft/target pairs and both streams derived from the same round
    keys, each row's first emitted token is still distributed exactly as
    its own target row.  Correlated streams or cross-row key bleed would
    both show up as a skewed marginal."""
    from deepspeed_tpu.inference.speculative import (spec_accept_batch,
                                                     spec_accept_keys,
                                                     spec_draft_keys)
    V = 4
    t_rows = jnp.asarray([[0.4, 0.3, 0.2, 0.1],
                          [0.1, 0.1, 0.1, 0.7],
                          [0.01, 0.97, 0.01, 0.01]])
    d_rows = jnp.asarray([[0.35, 0.35, 0.2, 0.1],
                          [0.7, 0.1, 0.1, 0.1],
                          [0.97, 0.01, 0.01, 0.01]])
    B = t_rows.shape[0]
    t_probs = jnp.concatenate(
        [t_rows[:, None], jnp.full((B, 1, V), 0.25)], axis=1)  # [B, 2, V]
    d_probs = d_rows[:, None]                                  # [B, 1, V]

    def one_round(k):
        round_keys = jax.random.split(k, B)            # per-slot [B, 2]
        d_tok = jax.vmap(jax.random.categorical)(
            spec_draft_keys(round_keys, 0), jnp.log(d_rows))
        a, nxt = spec_accept_batch(spec_accept_keys(round_keys),
                                   d_tok[:, None].astype(jnp.int32),
                                   d_probs, t_probs)
        return jnp.where(a >= 1, d_tok, nxt)           # first emitted [B]

    n = 20_000
    toks = jax.vmap(one_round)(jax.random.split(jax.random.PRNGKey(3), n))
    for b in range(B):
        freq = np.bincount(np.asarray(toks[:, b]), minlength=V) / n
        np.testing.assert_allclose(freq, np.asarray(t_rows[b]), atol=0.015,
                                   err_msg=f"slot {b}")
