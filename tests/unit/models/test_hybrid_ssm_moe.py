"""The hybrid state-space / attention family against its plain reference at
a tiny size on the CPU, in float32: the uncached ``apply`` and the slot path
(chunked prefill, then decode through the per-slot state) against the
reference's token-by-token recurrence; the chunked scan against the
recurrence from a non-zero state; what a padded tail and an inactive slot
leave behind; the slot ops on the state leaf; the expert layer's shares
against the uncut layer; the softmax gate; grouped heads in the decode
sweep; the admission as one launch; and what the family refuses."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.chip import hybrid_ssm_moe_family
from benchmarks.chip.reference import hybrid_ssm_moe_reference as reference
from deepspeed_tpu.models import (cache_family, gpt_inference,
                                  hybrid_ssm_moe, hybrid_ssm_moe_inference)
from deepspeed_tpu.moe import held_experts
from deepspeed_tpu.ops.pallas import decode_attention, ssm

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
NAME = "granite-4.0-h-small-ep4"
CHUNK = 16


def _file(**keys):
    """The benchmark's configuration file at the rehearsal's tiny sizes."""
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           NAME + ".json")) as f:
        file = json.load(f)
    with open(os.path.join(ROOT, "tests", "unit", "chipbench", "tiny",
                           "configs", NAME + ".json")) as f:
        file.update(json.load(f))
    return {**file, **keys}


def _model(file, seed=0):
    cfg = dataclasses.replace(hybrid_ssm_moe_family.build(file),
                              dtype=jnp.float32)
    return cfg, hybrid_ssm_moe_family.init(cfg, jax.random.PRNGKey(seed),
                                           jnp.float32)


def _gateway(cfg, params, **serving):
    engine = deepspeed_tpu.init_inference(model=(cfg, params),
                                          config={"dtype": "float32"})
    gateway = engine.serve(config={"slots": 4, "max_len": 128,
                                   "prefill_chunk": CHUNK,
                                   "queue_capacity": 8, **serving})
    gateway.shutdown(drain=False, timeout=60)
    return gateway


def test_the_family_shares_the_one_cache_family_and_adds_a_state_leaf():
    cfg, _ = _model(_file())
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


def test_apply_equals_the_reference():
    file = _file()
    cfg, params = _model(file, seed=1)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0,
                                cfg.vocab_size)
    got = hybrid_ssm_moe.apply(params, tokens, cfg)[..., :cfg.vocab_size]
    # compiled: op by op the reference compiles every primitive of every
    # new shape on its own
    ref = jax.jit(lambda p, t: reference.forward(file, p, t, 40))(params,
                                                                  tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=1e-4)


@pytest.mark.parametrize("n", [1, CHUNK, CHUNK + 1, 3 * CHUNK + 5],
                         ids=["1", "C", "C+1", "3C+5"])
def test_slot_path_equals_the_reference_full_forward(n):
    """Chunked prefill, then 8 decode ticks through the gateway's own
    programs and slot cache, against the reference's full forward, on
    logits: a prompt shorter than a chunk, exactly one, one more, several
    and a ragged tail."""
    file = _file()
    cfg, params = _model(file)
    gateway = _gateway(cfg, params)
    rng = np.random.default_rng(3 + n)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)]
    ticks = 8
    replies, got = gateway.probe_logits(prompts, ticks)
    full = np.concatenate([prompts[0], np.asarray(replies[0], np.int32)])
    # compiled: op by op the reference compiles every primitive of every
    # new shape on its own
    ref = np.asarray(jax.jit(lambda p, t: reference.forward(
        file, p, t, ticks + 1))(params, full[None]))[0]
    np.testing.assert_allclose(got[0][:, :cfg.vocab_size], ref, atol=2e-5,
                               rtol=1e-4)
    # the counters, each group where the family's layout puts it
    named = dict(zip(hybrid_ssm_moe_inference.STATE_COUNTERS,
                     gateway._batcher.counts("state_steps")))
    n_ssm = cfg.count("mamba")
    padded = -(-n // CHUNK) * CHUNK
    assert named == {"ssm_rows_stepped": ticks * n_ssm,
                     "scan_tokens_real": n * n_ssm,
                     "scan_tokens_padded": (padded - n) * n_ssm}
    pairs = gateway._batcher.counts("moe_pairs")
    assert pairs[0] == pairs[3:-2].sum() > 0 == pairs[-2] == pairs[-1] \
        and len(pairs) == 5 + len(cfg.held)
    assert gateway.metrics.snapshot()["state_steps"] == {}  # no harvest ran


@pytest.mark.parametrize("groups", [("moe_pairs", "state_steps"),
                                    ("state_steps",), ("moe_pairs",), ()],
                         ids=lambda g: "+".join(g) or "none")
def test_the_gateway_records_the_counter_groups_a_family_has(groups):
    """The family owns the layout of its counters (``stats_groups``); the
    scheduler's harvest records each group it finds by name, and a family
    with per-slot state and no expert layer (``state_steps`` alone), or
    with neither, is served like any other."""
    cfg, params = _model(_file())
    engine = deepspeed_tpu.init_inference(model=(cfg, params),
                                          config={"dtype": "float32"})
    gateway = engine.serve(config={"slots": 2, "max_len": 64,
                                   "prefill_chunk": CHUNK,
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
    cfg, params = _model(_file())
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
    cfg, params = _model(_file())
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


def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Experts 0-3, 4-7, 8-11, 12-15 of 16 on four chips: the routed parts
    the four shares give, with the shared MLP counted once, are the uncut
    layer's result."""
    file = _file(num_local_experts=16)
    cfg, params = _model(file)
    p = jax.tree_util.tree_map(lambda a: a[0], params["runs"][0])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, cfg.d_model))
    whole, counts = hybrid_ssm_moe.expert_ffn(x, p, cfg)
    assert counts[0] == counts[1] == 2 * 9 * cfg.experts_per_token
    routed, pairs = 0.0, 0
    for share in range(4):
        held = tuple(range(4 * share, 4 * share + 4))
        part = dataclasses.replace(cfg, held_experts=held)
        mine = {**p, "w_gu": p["w_gu"][held[0]:held[-1] + 1],
                "w_down": p["w_down"][held[0]:held[-1] + 1]}
        out, c = hybrid_ssm_moe.expert_ffn(x, mine, part)
        alone, _ = hybrid_ssm_moe.expert_ffn(
            x, {**mine, "w_down": mine["w_down"] * 0}, part)
        routed = routed + (out - alone)         # this share's routed part
        shared = alone - x                      # what every chip computes
        pairs += int(c[0])
    assert pairs == int(counts[0])
    np.testing.assert_allclose(np.asarray(x + shared + routed),
                               np.asarray(whole), atol=1e-6, rtol=1e-5)


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
    cfg, params = _model(_file())
    b = _gateway(cfg, params)._batcher
    rng = np.random.default_rng(8)
    lengths = (1, CHUNK - 1, CHUNK, CHUNK + 1, 3 * CHUNK + 5)
    for row, n in enumerate(lengths):
        b.admit(row % b.slots, rng.integers(0, cfg.vocab_size, n).astype(
            np.int32), jax.random.PRNGKey(0), True, 1.0)
    assert b.admit_launches / len(lengths) == 1.0
    counts = b.compile_counts()
    assert counts["admit"] == 1 and all(n <= 1 for n in counts.values())


@pytest.mark.parametrize("feature,serving", [
    ("speculative", {"speculative": {"enabled": True, "draft_k": 2,
                                     "draft": {"n_layer": 1}}}),
    ("paging", {"paging": {"enabled": True, "block_size": 16,
                           "hbm_blocks": 32}}),
], ids=["speculative", "paging"])
def test_what_the_family_does_not_serve_is_refused_at_construction(
        feature, serving):
    cfg, params = _model(_file())
    engine = deepspeed_tpu.init_inference(model=(cfg, params),
                                          config={"dtype": "float32"})
    with pytest.raises(NotImplementedError, match=feature):
        engine.serve(config={"slots": 2, "max_len": 64, "prefill_chunk": 16,
                             **serving})


def test_a_pooled_prefix_and_the_int8_cache_are_refused():
    cfg, params = _model(_file())
    gateway = _gateway(cfg, params)
    tokens = np.arange(20, dtype=np.int32) % cfg.vocab_size
    with pytest.raises(NotImplementedError, match="prefix"):
        gateway._batcher.build_prefix(tokens[:8])
    with pytest.raises(NotImplementedError, match="prefix"):
        gateway.submit(tokens, max_new_tokens=2, prefix_len=8)
    with pytest.raises(NotImplementedError, match="compute dtype"):
        hybrid_ssm_moe_inference.FAMILY.init_cache(cfg, 1, 32,
                                                   kv_dtype="int8")


def test_the_published_sizes():
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           NAME + ".json")) as f:
        file = json.load(f)
    cfg = hybrid_ssm_moe_family.build(file)
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
    cfg, params = _model(_file())
    engine = deepspeed_tpu.init_inference(model=(cfg, params),
                                          config={"dtype": "float32"})
    rng = np.random.default_rng(9)
    tokens = rng.integers(0, cfg.vocab_size, (2, 12)).astype(np.int32)
    both = np.asarray(engine.generate(tokens, max_new_tokens=5,
                                      prompt_lens=[12, 7]))
    alone = np.asarray(engine.generate(tokens[1:, :7], max_new_tokens=5))
    assert (both[1] == alone[0]).all()


def _probe(cfg, params, fault):
    from benchmarks.chip.reference import hybrid_ssm_moe_control as control
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 2 * CHUNK + 5)]
    with control.planted(fault):
        gateway = _gateway(
            cfg, control.WEIGHTS.get(fault, lambda p: p)(params))
        _, got = gateway.probe_logits(prompts, 4)
    return np.concatenate([np.asarray(g) for g in got])


@pytest.mark.parametrize("fault", ["chunk_edge", "pad_advance", "no_tail",
                                   "zero", "bf16_state", "int8"])
def test_every_planted_fault_reaches_the_slot_paths_logits(fault):
    """The chip's negative controls (``reference/hybrid_ssm_moe_control.py``)
    plant their faults by replacing functions of ``ops/pallas/ssm.py`` or in
    the server's weights: each
    must change what the server's own programs compute (whether it reads
    over ``compare.py``'s limits is the chip's to say, at the published
    widths), and leave the module as it was."""
    cfg, params = _model(_file())
    kept = {n: getattr(ssm, n) for n in ("ssd_chunk_scan", "ssm_decode_step",
                                         "causal_conv")}
    clean, faulty = _probe(cfg, params, "none"), _probe(cfg, params, fault)
    assert np.abs(faulty - clean).max() > 0
    assert all(getattr(ssm, n) is fn for n, fn in kept.items())
