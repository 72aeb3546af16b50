"""What the hybrid state-space / attention family alone has (the probes
every family answers are ``test_family_conformance.py``'s): the state leaf
beside the banks, the counter groups the gateway records, the chunked scan
against the recurrence from a non-zero state, what a padded tail and an
inactive slot leave behind, the slot ops on the state leaf, the softmax
gate, grouped heads in the decode sweep, the admission as one launch, the
published sizes and a ragged ``generate``."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.chip import hybrid_ssm_moe_family
from deepspeed_tpu.models import (cache_family, gpt_inference,
                                  hybrid_ssm_moe_inference)
from deepspeed_tpu.moe import held_experts
from deepspeed_tpu.ops.pallas import decode_attention, ssm
from tests.unit.models import family_harness as harness
from tests.unit.models.family_harness import CHUNK

SPEC = harness.SPECS["granite-4.0-h-small-ep4"]


def test_the_family_shares_the_one_cache_family_and_adds_a_state_leaf():
    cfg, _ = harness.model(SPEC)
    fam = cache_family(cfg)
    assert fam is hybrid_ssm_moe_inference.FAMILY
    assert isinstance(fam, gpt_inference.Family)
    assert hybrid_ssm_moe_inference.KVCache is gpt_inference.KVCache
    for op in ("write_slot", "read_slot", "reset_slot"):
        assert op not in vars(hybrid_ssm_moe_inference), op
    cache = fam.init_cache(cfg, 3, 64)
    # the banks belong to the one attention layer, the grouped row; the
    # state to the three state-space layers, with no token axis
    assert cfg.runs == (("mamba", 0, 2), ("attention", 0, 1),
                        ("mamba", 2, 1))
    assert cache.k.shape == cache.v.shape == (1, 3, 64, 2 * 16)
    state, tails = cache.state
    assert state.shape == (3, 3, 16, 128) and state.dtype == jnp.float32
    assert tails.shape == (3, 3, 3, 128 + 2 * 16)
    # a family that keeps none has none
    from deepspeed_tpu.models import gpt
    dense = gpt_inference.init_cache(
        gpt.GPTConfig(n_layer=2, n_head=2, d_model=32, vocab_size=64), 2, 16)
    assert dense.state is None


@pytest.mark.parametrize("groups", [("moe_pairs", "state_steps"),
                                    ("state_steps",), ("moe_pairs",), ()],
                         ids=lambda g: "+".join(g) or "none")
def test_the_gateway_records_the_counter_groups_a_family_has(groups):
    """The family owns the layout of its counters (``stats_groups``); the
    scheduler's harvest records each group it finds by name, and a family
    with per-slot state and no expert layer (``state_steps`` alone), or
    with neither, is served like any other."""
    cfg, params = harness.model(SPEC)
    gateway = harness.engine(cfg, params).serve(config={
        "slots": 2, "max_len": 64, "prefill_chunk": CHUNK,
        "queue_capacity": 4})
    try:
        b = gateway._batcher
        assert b._stats_groups == hybrid_ssm_moe_inference.stats_groups(cfg)
        b._stats_groups = {g: b._stats_groups[g] for g in groups}
        prompt = np.arange(CHUNK + 3, dtype=np.int32) % cfg.vocab_size
        reply = gateway.submit(prompt, max_new_tokens=4).result(timeout=120)
        assert len(reply) == 4
        snap = gateway.metrics.snapshot()
        n_ssm = cfg.count("mamba")
        if "state_steps" in groups:
            assert snap["state_steps"]["scan_tokens_real"] == \
                prompt.size * n_ssm
            assert snap["state_steps"]["ssm_rows_stepped"] >= 3 * n_ssm
        else:
            assert snap["state_steps"] == {}
        assert (snap["moe_pairs_held"] > 0) == ("moe_pairs" in groups)
    finally:
        gateway.shutdown(drain=False, timeout=60)


def _scan_inputs(rng, B, S, H, P, N):
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (f(B, S, H, P), jnp.abs(f(B, S, H)) * 0.3,
            -jnp.exp(f(H)), f(B, S, N), f(B, S, N))


def _recurrence(state, v, dt, a, Bm, Cm):
    """Token by token from ``state`` [B, N, H*P]; float64."""
    v, dt, a, Bm, Cm = (np.asarray(t, np.float64)
                        for t in (v, dt, a, Bm, Cm))
    B, S, H, P = v.shape
    h, ys = np.asarray(state, np.float64), []
    for t in range(S):
        decay = np.repeat(np.exp(dt[:, t] * a), P, -1)
        x = (dt[:, t, :, None] * v[:, t]).reshape(B, H * P)
        h = decay[:, None] * h + Bm[:, t][:, :, None] * x[:, None]
        ys.append(np.einsum("bnc,bn->bc", h, Cm[:, t]))
    return np.stack(ys, 1), h


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
def test_chunked_scan_equals_the_recurrence_from_a_nonzero_state(
        monkeypatch, interpret):
    """Three sub-chunks from a state that is not zero, one row's tail
    padded: ``y`` at the real positions and the state after the last real
    token; the other layers of the stack untouched."""
    if interpret:
        monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(0)
    B, S, H, P, N, L = 2, 384, 4, 64, 16, 3
    v, dt, a, Bm, Cm = _scan_inputs(rng, B, S, H, P, N)
    stack = jnp.asarray(rng.normal(size=(L, B, N, H * P)), jnp.float32)
    valid = np.array([S, 300])
    y, out = ssm.ssd_chunk_scan(stack, 1, v, dt, a, Bm, Cm,
                                valid=jnp.asarray(valid), chunk=128)
    masked = np.where(np.arange(S)[None, :, None] < valid[:, None, None],
                      np.asarray(dt), 0.0)
    want_y, want_h = _recurrence(stack[1], v, masked, a, Bm, Cm)
    for b in range(B):
        np.testing.assert_allclose(np.asarray(y)[b, :valid[b]],
                                   want_y[b, :valid[b]], atol=2e-4,
                                   rtol=2e-4)
    np.testing.assert_allclose(np.asarray(out)[1], want_h, atol=2e-4,
                               rtol=2e-4)
    assert (np.asarray(out)[[0, 2]] == np.asarray(stack)[[0, 2]]).all()


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
def test_decode_step_equals_the_recurrence_and_skips_dead_slots(
        monkeypatch, interpret):
    if interpret:
        monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(1)
    B, H, P, N, L = 3, 4, 64, 16, 2
    v, dt, a, Bm, Cm = _scan_inputs(rng, B, 1, H, P, N)
    stack = jnp.asarray(rng.normal(size=(L, B, N, H * P)), jnp.float32)
    active = jnp.asarray([True, False, True])
    wide = lambda t: jnp.repeat(t[:, 0], P, axis=-1)
    y, out = ssm.ssm_decode_step(
        stack, 1, wide(dt) * v.reshape(B, -1), wide(jnp.exp(dt * a)),
        Bm[:, 0], Cm[:, 0], active=active)
    want_y, want_h = _recurrence(stack[1], v, dt, a, Bm, Cm)
    live = [0, 2]
    np.testing.assert_allclose(np.asarray(y)[live], want_y[live, 0],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out)[1, live], want_h[live],
                               atol=1e-5, rtol=1e-5)
    # the dead slot: no result, its state bit for bit; the other layer too
    assert not np.asarray(y)[1].any()
    assert (np.asarray(out)[1, 1] == np.asarray(stack)[1, 1]).all()
    assert (np.asarray(out)[0] == np.asarray(stack)[0]).all()


def test_a_padded_tail_and_an_inactive_slot_leave_the_state_bit_for_bit():
    """Through the family's own passes: a chunk with 5 real tokens and 11
    of padding leaves what a chunk of those 5 alone leaves, whatever the
    padding holds; a tick leaves a freed slot's ``H`` and convolution tail
    exactly as they were."""
    cfg, params = harness.model(SPEC)
    fam = hybrid_ssm_moe_inference.FAMILY
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, cfg.vocab_size, (1, CHUNK)).astype(np.int32)
    other = tokens.copy()
    other[:, 5:] = rng.integers(0, cfg.vocab_size, (1, CHUNK - 5))
    states = []
    for t in (tokens, other):
        _, cache = fam.prefill(params, jnp.asarray(t), cfg,
                               fam.init_cache(cfg, 1, 64),
                               valid=jnp.asarray([5]))
        states.append(cache.state)
    for a, b in zip(*states):
        assert (np.asarray(a) == np.asarray(b)).all()
    _, exact = fam.prefill(params, jnp.asarray(tokens[:, :5]), cfg,
                           fam.init_cache(cfg, 1, 64))
    for a, b in zip(states[0], exact.state):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # a tick over two slots of which one is live
    pool = fam.init_cache(cfg, 2, 64)
    pool = fam.write_slot(pool, 0, cache)
    pool = fam.write_slot(pool, 1, cache)
    before = [np.asarray(a) for a in pool.state]
    _, after = fam.decode_step(
        params, jnp.asarray([3, 4]), cfg, pool,
        lengths=jnp.asarray([5, 5]), active=jnp.asarray([True, False]))
    for a, b in zip(before, after.state):
        assert (a[:, 1] == np.asarray(b)[:, 1]).all()
        assert (a[:, 0] != np.asarray(b)[:, 0]).any()


def test_slot_ops_on_the_state_leaf():
    cfg, params = harness.model(SPEC)
    fam = hybrid_ssm_moe_inference.FAMILY
    tokens = jnp.arange(7, dtype=jnp.int32)[None] % cfg.vocab_size
    _, row = fam.prefill(params, tokens, cfg, fam.init_cache(cfg, 1, 32))
    pool = fam.write_slot(fam.init_cache(cfg, 3, 32), 2, row)
    back = fam.read_slot(pool, 2, length=7)
    for a, b in zip(row.state + (row.k, row.v),
                    back.state + (back.k, back.v)):
        assert a.shape == b.shape and (np.asarray(a) == np.asarray(b)).all()
    assert all(np.asarray(s)[:, 2].any() for s in pool.state)
    assert not any(np.asarray(s)[:, :2].any() for s in pool.state)
    cleared = fam.reset_slot(pool, 2)
    assert not any(np.asarray(s).any() for s in cleared.state)
    assert not np.asarray(cleared.k).any()


def test_the_softmax_gate_against_its_definition():
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.normal(size=(11, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(32, 24)), jnp.float32)
    routing = held_experts.route_softmax(h, w, 5)
    logits = np.asarray(h, np.float64) @ np.asarray(w, np.float64)
    for t in range(11):
        order = np.argsort(-logits[t])[:5]
        assert set(order) == set(np.asarray(routing.experts[t]))
        e = np.exp(logits[t, np.asarray(routing.experts[t])]
                   - logits[t, order[0]])
        np.testing.assert_allclose(np.asarray(routing.weights[t]),
                                   e / e.sum(), rtol=1e-5)
    assert routing.experts.dtype == jnp.int32


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
@pytest.mark.parametrize("sq", [1, 16], ids=["decode", "chunk"])
def test_grouped_heads_against_the_dense_formula(monkeypatch, interpret, sq):
    """8 query heads on 2 key-value heads over the stacked pool, a dead row
    among the live ones: the sweep and the chunk pass against the dense
    formula with every key-value head repeated for its 4 query heads."""
    if interpret:
        monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(7)
    L, B, S, Hq, Hkv, D = 2, 3, 256, 8, 2, 128
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    k, v, q = f(L, B, S, Hkv * D), f(L, B, S, Hkv * D), f(B, sq, Hq, D)
    pos = jnp.asarray([5, 200, 130])
    active = jnp.asarray([True, True, False]) if sq == 1 else None
    got = decode_attention.cached_attention(
        q, k, v, pos, sm_scale=1 / 128, layer=1, active=active,
        kv_heads=Hkv)
    rep = lambda t: jnp.repeat(t[1].reshape(B, S, Hkv, D), Hq // Hkv, 2)
    want = decode_attention.cached_attention_reference(
        q, rep(k), rep(v), pos, 1 / 128)
    live = slice(0, 2) if sq == 1 else slice(None)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=2e-6, rtol=1e-5)
    if sq == 1:
        assert not np.asarray(got)[2].any()


def test_gmm_tile_follows_the_matrices():
    assert held_experts.gmm_tiling(7168, 4096) == held_experts.GMM_TILING
    assert held_experts.gmm_tiling(2048, 7168) == held_experts.GMM_TILING
    # a narrower column tile leaves room for the whole contraction side
    assert held_experts.gmm_tiling(4096, 1536) == (128, 4096, 768)
    assert held_experts.gmm_tiling(768, 4096) == (128, 768, 1024)
    assert held_experts.gmm_tiling(16384, 1536) == (128, 1024, 768)


def test_an_admission_is_one_launch_and_one_compile():
    """Five prompt lengths (under a chunk, a chunk, over one, several):
    one launch an admission, one compile of the admission program."""
    cfg, params = harness.model(SPEC)
    b = harness.gateway(cfg, params)._batcher
    rng = np.random.default_rng(8)
    lengths = (1, CHUNK - 1, CHUNK, CHUNK + 1, 3 * CHUNK + 5)
    for row, n in enumerate(lengths):
        b.admit(row % b.slots, rng.integers(0, cfg.vocab_size, n).astype(
            np.int32), jax.random.PRNGKey(0), True, 1.0)
    assert b.admit_launches / len(lengths) == 1.0
    counts = b.compile_counts()
    assert counts["admit"] == 1 and all(n <= 1 for n in counts.values())


def test_the_published_sizes():
    cfg = hybrid_ssm_moe_family.build(harness.published(SPEC.name))
    assert cfg.runs == (("mamba", 0, 5), ("attention", 0, 1),
                        ("mamba", 5, 4))
    assert (cfg.d_model, cfg.d_inner, cfg.d_conv, cfg.ssm_state,
            cfg.ssm_chunk) == (4096, 8192, 8448, 128, 256)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim, cfg.attn_scale) == (
        32, 8, 128, 1 / 128)
    assert (cfg.n_experts, len(cfg.held), cfg.experts_per_token,
            cfg.d_expert, cfg.d_shared) == (72, 18, 10, 768, 1536)
    assert cfg.cache_row == (1024, 1024) and cfg.cache_layers == 1
    (n, shape, dtype), (_, tail, _) = cfg.cache_state
    assert (n, shape, dtype) == (9, (128, 8192), jnp.float32)
    assert tail == (3, 8448)
    # a slot's state: 9 layers x (4.19 MB + 0.05 MB) whatever its length
    per_slot = 9 * (128 * 8192 * 4 + 3 * 8448 * 2)
    assert round(per_slot / 1e6, 1) == 38.2
    ops, nbytes = hybrid_ssm_moe_family.state_step_count(cfg, 1)
    assert nbytes == 2 * 128 * 8192 * 4 and ops / nbytes == 0.625
    assert hybrid_ssm_moe_family.decode_count(cfg, 1) == (16384.0, 4096.0)


def test_ragged_generate_equals_each_row_alone():
    """``engine.generate`` with right-padded prompts tells the family where
    each row's prompt ends: a padded row generates what it generates
    alone."""
    cfg, params = harness.model(SPEC)
    engine = harness.engine(cfg, params)
    rng = np.random.default_rng(9)
    tokens = rng.integers(0, cfg.vocab_size, (2, 12)).astype(np.int32)
    both = np.asarray(engine.generate(tokens, max_new_tokens=5,
                                      prompt_lens=[12, 7]))
    alone = np.asarray(engine.generate(tokens[1:, :7], max_new_tokens=5))
    assert (both[1] == alone[0]).all()

