"""Chunked prefill (`gpt_inference.extend`): long prompts process in
bounded-activation chunks, and a multi-turn server appends new turns to
the session cache instead of re-prefilling the conversation."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import gpt, gpt_inference

CFG = gpt.GPTConfig(vocab_size=256, max_seq_len=128, n_layer=2, n_head=4,
                    d_model=64, dtype=jnp.float32, vocab_round_to=128)


@pytest.mark.parametrize("variant", [{}, {"pos_embed": "rotary"},
                                     {"pos_embed": "alibi"},
                                     {"local_attention_window": 8}])
def test_extend_composes_with_prefill(variant):
    """prefill(t[:, :c]) ; extend(t[:, c:]) == prefill(t) — logits of the
    appended chunk and subsequent decode steps match the one-shot run."""
    cfg = dataclasses.replace(CFG, **variant)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 256)

    full_logits, full_cache = gpt_inference.prefill(
        params, tokens, cfg, gpt_inference.init_cache(cfg, 2, 48))

    _, cache = gpt_inference.prefill(
        params, tokens[:, :10], cfg, gpt_inference.init_cache(cfg, 2, 48))
    ext_logits, cache = gpt_inference.extend(params, tokens[:, 10:], cfg,
                                             cache)
    assert int(cache.length) == 24
    np.testing.assert_allclose(np.asarray(ext_logits),
                               np.asarray(full_logits[:, 10:]),
                               atol=2e-4, rtol=2e-4, err_msg=str(variant))
    # the caches decode identically afterwards
    nxt = jnp.argmax(ext_logits[:, -1, :cfg.vocab_size], -1).astype(jnp.int32)
    d_full, _ = gpt_inference.decode_step(params, nxt, cfg, full_cache)
    d_ext, _ = gpt_inference.decode_step(params, nxt, cfg, cache)
    np.testing.assert_allclose(np.asarray(d_ext), np.asarray(d_full),
                               atol=2e-4, rtol=2e-4)


def test_extend_multi_chunk_jit():
    """Three chunks under jit (the long-prompt serving shape) reproduce the
    one-shot prefill."""
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 30), 0, 256)
    full_logits, _ = gpt_inference.prefill(
        params, tokens, CFG, gpt_inference.init_cache(CFG, 1, 32))
    ext = jax.jit(lambda p, t, c: gpt_inference.extend(p, t, CFG, c))
    _, cache = gpt_inference.prefill(
        params, tokens[:, :10], CFG, gpt_inference.init_cache(CFG, 1, 32))
    outs = []
    for lo in (10, 20):
        lg, cache = ext(params, tokens[:, lo:lo + 10], cache)
        outs.append(lg)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(full_logits[:, 10:]),
                               atol=2e-4, rtol=2e-4)


def test_extend_int8_cache():
    """extend writes quantized K/V into an int8 cache; the composed run
    tracks the one-shot int8 prefill + decode within int8 error."""
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 16), 0, 256)
    _, cache = gpt_inference.prefill(
        params, tokens[:, :8], CFG,
        gpt_inference.init_cache(CFG, 2, 32, kv_dtype="int8"))
    lg, cache = gpt_inference.extend(params, tokens[:, 8:], CFG, cache)
    assert cache.int8 and int(cache.length) == 16
    # vs the fp-cache composed run: int8 error only
    _, fcache = gpt_inference.prefill(
        params, tokens[:, :8], CFG, gpt_inference.init_cache(CFG, 2, 32))
    flg, _ = gpt_inference.extend(params, tokens[:, 8:], CFG, fcache)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(flg),
                               atol=0.05, rtol=0.05)


def test_extend_overflow_raises_eagerly():
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(4), (1, 20), 0, 256)
    _, cache = gpt_inference.prefill(
        params, tokens[:, :12], CFG, gpt_inference.init_cache(CFG, 1, 16))
    with pytest.raises(ValueError, match="overflows the cache"):
        gpt_inference.extend(params, tokens[:, 12:], CFG, cache)


def test_inference_session_multi_turn():
    """Engine-level session: two turns + replies over ONE persistent
    cache must reproduce the stateless engine run on the concatenated
    history."""
    import deepspeed_tpu
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    eng = deepspeed_tpu.init_inference(model=(CFG, params),
                                       config={"dtype": "float32"})
    rng = np.random.default_rng(0)
    t1 = jnp.asarray(rng.integers(0, 256, (1, 10)), jnp.int32)
    t2 = jnp.asarray(rng.integers(0, 256, (1, 7)), jnp.int32)

    s = eng.start_session(batch=1, max_len=128)
    s.append(t1)
    r1 = s.generate(max_new_tokens=5)
    assert s.length == 15
    s.append(t2)
    r2 = s.generate(max_new_tokens=5)
    assert s.length == 27

    # stateless reference: greedy over the concatenated history
    hist = jnp.concatenate([t1, r1], axis=1)
    ref1 = eng.generate(t1, max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(ref1))
    hist2 = jnp.concatenate([hist, t2], axis=1)
    ref2 = eng.generate(hist2, max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(r2), np.asarray(ref2))

    # cache-full and usage errors are loud
    with pytest.raises(ValueError, match="session cache full"):
        s.append(jnp.zeros((1, 128), jnp.int32))
    fresh = eng.start_session(batch=1, max_len=64)
    with pytest.raises(ValueError, match="append"):
        fresh.generate(4)


def test_inference_session_int8_cache():
    import deepspeed_tpu
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    eng = deepspeed_tpu.init_inference(
        model=(CFG, params),
        config={"dtype": "float32", "kv_cache_dtype": "int8"})
    s = eng.start_session(batch=2, max_len=64)
    assert s.cache.int8
    t = jnp.asarray(np.random.default_rng(1).integers(0, 256, (2, 9)),
                    jnp.int32)
    s.append(t)
    out = s.generate(max_new_tokens=4)
    assert out.shape == (2, 4) and s.length == 13


def test_session_moe_multi_turn():
    """MoE sessions (refusal removed): turns + replies over one
    persistent dual-bank cache match the stateless MoE engine run on the
    concatenated history."""
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt_moe
    mcfg = gpt_moe.GPTMoEConfig(vocab_size=128, max_seq_len=64, n_layer=2,
                                n_head=2, d_model=32, dtype=jnp.float32,
                                vocab_round_to=128, num_experts=2)
    mparams = gpt_moe.init(mcfg, jax.random.PRNGKey(0))
    eng = deepspeed_tpu.init_inference(model=(mcfg, mparams),
                                       config={"dtype": "float32"})
    rng = np.random.default_rng(3)
    t1 = jnp.asarray(rng.integers(0, 128, (1, 8)), jnp.int32)
    t2 = jnp.asarray(rng.integers(0, 128, (1, 5)), jnp.int32)

    s = eng.start_session(batch=1, max_len=64)
    s.append(t1)
    r1 = s.generate(max_new_tokens=4)
    assert s.length == 12
    s.append(t2)
    r2 = s.generate(max_new_tokens=4)
    assert s.length == 21

    ref1 = eng.generate(t1, max_new_tokens=4)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(ref1))
    hist2 = jnp.concatenate([t1, r1, t2], axis=1)
    ref2 = eng.generate(hist2, max_new_tokens=4)
    np.testing.assert_array_equal(np.asarray(r2), np.asarray(ref2))

    # fork shares the prefix state zero-copy
    f = s.fork()
    assert f.cache is s.cache and f.length == s.length

    # int8 MoE session composes
    q = deepspeed_tpu.init_inference(
        model=(mcfg, mparams),
        config={"dtype": "float32", "kv_cache_dtype": "int8"})
    sq = q.start_session(batch=1, max_len=64)
    assert sq.cache.int8
    sq.append(t1)
    assert sq.generate(max_new_tokens=4).shape == (1, 4)


def test_sessions_share_compiled_programs():
    import deepspeed_tpu
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    eng = deepspeed_tpu.init_inference(model=(CFG, params),
                                       config={"dtype": "float32"})
    s1, s2 = eng.start_session(), eng.start_session()
    # jit caches key on the function object: sessions must share programs
    assert s1._progs is s2._progs
    s1.append(jnp.zeros((1, 4), jnp.int32))
    # zero-token reply is a defined no-op, not a stack error
    assert s1.generate(max_new_tokens=0).shape == (1, 0)


def test_session_sampled_replies():
    """Session replies support the shared sampling filter: deterministic
    per key, varies across keys, valid tokens, cache still advances."""
    import deepspeed_tpu
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    eng = deepspeed_tpu.init_inference(model=(CFG, params),
                                       config={"dtype": "float32"})
    s = eng.start_session(batch=2, max_len=64)
    s.append(jnp.zeros((2, 6), jnp.int32))
    r1 = np.asarray(s.generate(8, do_sample=True, temperature=0.9,
                               top_p=0.95, key=jax.random.PRNGKey(1)))
    assert r1.shape == (2, 8) and (r1 < CFG.vocab_size).all()
    assert s.length == 14
    # a fresh session with the same key reproduces the reply
    s2 = eng.start_session(batch=2, max_len=64)
    s2.append(jnp.zeros((2, 6), jnp.int32))
    r2 = np.asarray(s2.generate(8, do_sample=True, temperature=0.9,
                                top_p=0.95, key=jax.random.PRNGKey(1)))
    np.testing.assert_array_equal(r1, r2)
    # different keys explore — fresh session per key, so a key-ignored
    # regression cannot hide behind the advancing cache
    outs = []
    for k in range(3):
        sk = eng.start_session(batch=2, max_len=64)
        sk.append(jnp.zeros((2, 6), jnp.int32))
        outs.append(np.asarray(sk.generate(4, do_sample=True,
                                           temperature=0.9,
                                           key=jax.random.PRNGKey(k))))
    assert any(not np.array_equal(outs[0], o) for o in outs[1:])
    # greedy + filters is a loud error, not a silent no-op
    with pytest.raises(ValueError, match="do_sample"):
        s.generate(4, top_p=0.9)


def test_session_fork_prefix_caching():
    """Process a shared system prompt once, fork per conversation: each
    fork diverges independently and matches the stateless run on ITS
    concatenated history; the parent is unaffected."""
    import deepspeed_tpu
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    eng = deepspeed_tpu.init_inference(model=(CFG, params),
                                       config={"dtype": "float32"})
    rng = np.random.default_rng(2)
    system = jnp.asarray(rng.integers(0, 256, (1, 12)), jnp.int32)
    base = eng.start_session(batch=1, max_len=128)
    base.append(system)

    turn_a = jnp.asarray(rng.integers(0, 256, (1, 5)), jnp.int32)
    turn_b = jnp.asarray(rng.integers(0, 256, (1, 7)), jnp.int32)
    fa, fb = base.fork(), base.fork()
    fa.append(turn_a)
    ra = np.asarray(fa.generate(6))
    fb.append(turn_b)
    rb = np.asarray(fb.generate(6))
    assert not np.array_equal(ra, rb)  # genuinely diverged

    # each fork == the stateless engine on its own concatenated history
    np.testing.assert_array_equal(
        ra, np.asarray(eng.generate(
            jnp.concatenate([system, turn_a], 1), max_new_tokens=6)))
    np.testing.assert_array_equal(
        rb, np.asarray(eng.generate(
            jnp.concatenate([system, turn_b], 1), max_new_tokens=6)))
    # the parent still holds only the system prompt and continues cleanly
    assert base.length == 12
    base.append(turn_a)
    np.testing.assert_array_equal(np.asarray(base.generate(6)), ra)


# compile-heavy: full-suite / slow tier only (fast tier = pytest -m "not slow")
import pytest as _pytest_tier
pytestmark = _pytest_tier.mark.slow


@pytest.mark.parametrize("variant", [{}, {"pos_embed": "rotary"}])
def test_ragged_extend_matches_per_row(variant):
    """Ragged extend (each row's chunk at ITS frontier — the batched
    speculative verify shape): logits and cache state must equal each
    row extended alone."""
    import dataclasses
    cfg = dataclasses.replace(CFG, **variant)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    B, Sc = 2, 4
    lens = np.asarray([5, 9])
    prompts = jnp.asarray(rng.integers(0, 256, (B, 9)), jnp.int32)
    chunk = jnp.asarray(rng.integers(0, 256, (B, Sc)), jnp.int32)

    # batched: prefill the right-padded batch, ragged-extend the chunk
    cache = gpt_inference.init_cache(cfg, B, 32)
    _, cache = gpt_inference.prefill(params, prompts, cfg, cache)
    lg, cache = gpt_inference.extend(params, chunk, cfg, cache,
                                     lengths=jnp.asarray(lens, jnp.int32))
    assert int(cache.length) == 9 + Sc

    for b in range(B):
        L = int(lens[b])
        c1 = gpt_inference.init_cache(cfg, 1, 32)
        _, c1 = gpt_inference.prefill(params, prompts[b:b + 1, :L], cfg, c1)
        lg1, c1 = gpt_inference.extend(params, chunk[b:b + 1], cfg, c1)
        np.testing.assert_allclose(np.asarray(lg)[b], np.asarray(lg1)[0],
                                   rtol=2e-5, atol=2e-5,
                                   err_msg=f"row {b} ({variant})")
        np.testing.assert_allclose(
            np.asarray(cache.k[:, b, L:L + Sc]),
            np.asarray(c1.k[:, 0, L:L + Sc]), rtol=2e-5, atol=2e-5)


def _family(name):
    """A served family at the rehearsal's tiny sizes, in bf16."""
    from tests.unit.models.test_family_declaration import _served
    cfg, init, fam = _served(name)
    cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    params = jax.tree_util.tree_map(
        lambda w: w.astype(jnp.bfloat16) if w.dtype == jnp.float32 else w,
        init(jax.random.PRNGKey(0)))
    return cfg, params, fam


def _same(a, b):
    """Every leaf of two pytrees, bit for bit."""
    la, lb = map(jax.tree_util.tree_leaves, (a, b))
    assert len(la) == len(lb)
    return all(x.dtype == y.dtype and np.array_equal(
        np.asarray(x.astype(jnp.float32)), np.asarray(y.astype(jnp.float32)))
        for x, y in zip(la, lb))


@pytest.mark.parametrize("start", [0, 5], ids=["fresh", "prefix-off-grid"])
@pytest.mark.parametrize("name,kv", [
    ("dense", None), ("dense", "int8"), ("moe", "int8"), ("latent", None),
    ("hybrid", None)],
    ids=["bf16-dense", "int8-dense", "int8-moe", "bf16-latent",
         "bf16-hybrid"])
def test_one_rows_ragged_chunk_is_the_scatter_bit_for_bit(monkeypatch, name,
                                                           kv, start):
    """A batch-1 ragged ``extend`` (an admission's further chunks, a
    prefix's continuation) writes each bank by one update slice; banks,
    scale banks, state and logits are the scatter's (the parent's form,
    ``_chunk_scatter``) bit for bit, chunk after chunk: inside the row,
    over its end (a slot of 40 cells in chunks of 16: the last chunk's rows
    past the end fall off and the cached prefix stays as it was, where a
    clamped slice would have landed on it), and wholly past it."""
    cfg, params, fam = _family(name)
    S, C = 40, 16
    tokens = jax.random.randint(jax.random.PRNGKey(5), (4, 1, C), 0,
                                cfg.vocab_size)

    def chunks(place):
        with monkeypatch.context() as m:
            m.setattr(gpt_inference, "_chunk_slice", place)
            ext = jax.jit(lambda p, t, c, l, n: fam.extend(
                p, t, cfg, c, lengths=l, valid=n))
            _, cache = fam.prefill(params, tokens[0][:, :max(start, 1)], cfg,
                                   fam.init_cache(cfg, 1, S, kv_dtype=kv))
            out = []
            # ... + 16, + 32 (its end passes 40), and one from the row's end
            for i, pos in enumerate([start, start + C, start + 2 * C, S]):
                real = int(np.clip(S - pos, 1, C))
                lg, cache = ext(params, tokens[i], cache,
                                jnp.asarray([pos], jnp.int32),
                                jnp.asarray([real], jnp.int32))
                out.append((lg[:, :real], cache))
        return out

    sliced = chunks(gpt_inference._chunk_slice)
    scattered = chunks(gpt_inference._chunk_scatter)
    assert all(_same(a, b) for a, b in zip(sliced, scattered))
    # the rows before the last chunk's frontier are the chunk before's
    pos = start + 2 * C
    assert pos + C > S
    before, after = sliced[1][1], sliced[2][1]
    for name in ("k", "v", "k_scale", "v_scale"):
        was, now = getattr(before, name), getattr(after, name)
        assert (now is not None) == (
            name == "k" or kv is not None
            or name == "v" and after.v is not None)
        if now is not None:
            assert _same(was[:, :, :pos], now[:, :, :pos])
            assert not _same(was[:, :, pos:], now[:, :, pos:])


def test_rows_at_unequal_frontiers_still_scatter(monkeypatch):
    """The batched verify's form is the parent's: ``B > 1`` never takes the
    slice (nor does a chunk longer than the row), whatever the lengths; one
    row does, a one-slot server's verify window of ``K + 1`` tokens
    included."""
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    taken = []
    slice_ = gpt_inference._chunk_slice
    monkeypatch.setattr(gpt_inference, "_chunk_slice",
                        lambda *a: taken.append(a[2].shape) or slice_(*a))

    def text(B, Sc, S=32):
        cache = gpt_inference.init_cache(CFG, B, S)
        return str(jax.make_jaxpr(lambda c, t, l: gpt_inference.extend(
            params, t, CFG, c, lengths=l))(
                cache, jnp.zeros((B, Sc), jnp.int32),
                jnp.arange(B, dtype=jnp.int32) + 3))

    assert "scatter" in text(2, 4) and not taken
    assert "scatter" in text(1, 48) and not taken
    # two banks, in the layer scan's one body
    assert "scatter" not in text(1, 5) and taken == [(1, 5, 64)] * 2
