"""The latent-attention, routed-expert family against its plain reference
at a tiny size on the CPU, in float32: the slot path (chunked prefill, then
decode) and the uncached ``apply`` (both the absorbed form) against the
reference's explicit, up-projected full forward; the YaRN table against its
closed form; the router; the expert layer's shares against the uncut layer;
the latent kernels under the interpreter; and what the family refuses."""

import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.chip import latent_moe_family
from benchmarks.chip.reference import latent_moe_reference as reference
from deepspeed_tpu.models import (cache_family, gpt_inference, latent_moe,
                                  latent_moe_inference)
from deepspeed_tpu.moe import held_experts
from deepspeed_tpu.ops.pallas import decode_attention

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
NAME = "kimi-k2.7-code-ep32"


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
    import dataclasses
    cfg = dataclasses.replace(latent_moe_family.build(file),
                              dtype=jnp.float32)
    return cfg, latent_moe_family.init(cfg, jax.random.PRNGKey(seed),
                                       jnp.float32)


def test_the_family_is_picked_by_its_config_and_shares_the_one_cache_family():
    cfg, _ = _model(_file())
    fam = cache_family(cfg)
    assert fam is latent_moe_inference.FAMILY
    assert isinstance(fam, gpt_inference.Family)
    assert latent_moe_inference.KVCache is gpt_inference.KVCache
    for op in ("write_slot", "read_slot", "reset_slot"):
        assert op not in vars(latent_moe_inference), op
    cache = fam.init_cache(cfg, 3, 64)
    # one bank, the latent row in whole lane rows; no second bank
    assert cache.v is None and cache.k.shape == (cfg.n_layer, 3, 64, 128)
    assert cfg.row_elements == 40 and cfg.cache_row == (128,)
    assert gpt_inference.cache_row(cfg) == (128,)


def test_slot_path_equals_the_reference_full_forward():
    """Chunked prefill (chunk boundaries crossed) and decode ticks through
    the gateway's own programs against the reference, on logits."""
    file = _file()
    cfg, params = _model(file)
    engine = deepspeed_tpu.init_inference(model=(cfg, params),
                                          config={"dtype": "float32"})
    gateway = engine.serve(config={"slots": 4, "max_len": 128,
                                   "prefill_chunk": 16,
                                   "queue_capacity": 8})
    gateway.shutdown(drain=False, timeout=60)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 17, 33, 70)]
    ticks = 5
    replies, got = gateway.probe_logits(prompts, ticks)
    for p, reply, logits in zip(prompts, replies, got):
        full = np.concatenate([p, np.asarray(reply, np.int32)])[None]
        # compiled: op by op the reference compiles every primitive of
        # every new shape on its own
        ref = np.asarray(jax.jit(lambda p, t: reference.forward(
            file, p, t, ticks + 1))(params, full))[0]
        np.testing.assert_allclose(logits[:, :cfg.vocab_size], ref,
                                   atol=2e-5, rtol=1e-4)
    counts = gateway._batcher.device_counts
    assert counts is not None and counts[0] == counts[3:-2].sum() > 0
    assert counts[1] > counts[0] >= counts[2] > 0


def test_absorbed_apply_equals_the_up_projected_reference():
    file = _file()
    cfg, params = _model(file, seed=1)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0,
                                cfg.vocab_size)
    got = latent_moe.apply(params, tokens, cfg)[..., :cfg.vocab_size]
    ref = reference.forward(file, params, tokens, 40)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=2e-5,
                               rtol=1e-4)


def test_yarn_table_against_its_closed_form():
    """The published sizes: 64 rotary dims, theta 50000, factor 64 over 4096
    positions, beta 32 / 1: dims that turn more than 32 times keep their
    frequency, dims that turn less than once are divided by 64, a linear
    ramp between."""
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           NAME + ".json")) as f:
        file = json.load(f)
    cfg = latent_moe_family.build(file)
    got = np.asarray(latent_moe.rotary_inv_freq(cfg), np.float64)
    dim, theta, factor, original = 64, 50000.0, 64.0, 4096
    j = np.arange(dim // 2)
    plain = theta ** (-2.0 * j / dim)
    turns = original * plain / (2 * math.pi)
    low = math.floor(dim * math.log(original / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(dim * math.log(original / (1 * 2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (8, 20)
    want = np.where(j <= low, plain, np.where(
        j >= high, plain / factor,
        plain / factor * ((j - low) / (high - low))
        + plain * (1 - (j - low) / (high - low))))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert (turns[:low + 1] > 32).all() and (turns[high:] < 1).all()
    np.testing.assert_allclose(np.asarray(reference.yarn_inv_freq(file)),
                               want, rtol=1e-5)
    m = 0.1 * math.log(64) + 1
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m)
    assert reference.softmax_scale(file) == pytest.approx(cfg.softmax_scale)


def test_the_bias_moves_selection_and_not_weight():
    h = jnp.eye(4, 8, dtype=jnp.float32)
    w = jnp.asarray(np.random.default_rng(0).normal(size=(8, 6)), jnp.float32)
    zero = jnp.zeros((6,), jnp.float32)
    plain = held_experts.route(h, w, zero, 2, 2.5)
    scores = jax.nn.sigmoid(h @ w)
    bias = zero.at[5].set(10.0)                  # expert 5 wins everywhere
    moved = held_experts.route(h, w, bias, 2, 2.5)
    assert (np.asarray(moved.experts) == 5).any(axis=1).all()
    assert not (np.asarray(plain.experts) == 5).any(axis=1).all()
    # weights are the scores alone, normalised over the selected and scaled
    sel = np.take_along_axis(np.asarray(scores), np.asarray(moved.experts), 1)
    np.testing.assert_allclose(np.asarray(moved.weights),
                               2.5 * sel / sel.sum(1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(moved.weights).sum(1), 2.5,
                               rtol=1e-6)
    assert moved.weights.dtype == jnp.float32


def test_ties_go_to_the_lower_expert_and_bf16_inputs_route_in_float32():
    h = jnp.ones((3, 4), jnp.bfloat16)
    w = jnp.zeros((4, 5), jnp.bfloat16)            # every score 0.5
    tied = held_experts.route(h, w, jnp.zeros((5,)), 2, 1.0)
    assert np.asarray(tied.experts).tolist() == [[0, 1]] * 3
    assert tied.weights.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(tied.weights), 0.5)
    # a difference far under bf16's step still orders the scores
    w32 = jnp.zeros((4, 5), jnp.float32).at[0, 3].set(1e-6)
    got = held_experts.route(jnp.ones((1, 4), jnp.float32), w32,
                             jnp.zeros((5,)), 1, 1.0)
    assert int(got.experts[0, 0]) == 3


def test_the_shares_add_up_to_the_uncut_layer():
    """32 shares of 2 experts each: the routed parts every share computes
    for its own experts, plus what every chip computes alike (the shared
    expert, the residual) counted once, are the uncut reference's layer."""
    file = _file(n_routed_experts=64,
                 num_experts_per_tok=6, num_hidden_layers=2,
                 published={"num_hidden_layers": 61, "n_routed_experts": 64,
                            "vocab_size": 4096})
    whole, params = _model(file, seed=4)
    p = jax.tree_util.tree_map(lambda a: a[0], params["moe_blocks"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, whole.d_model))
    uncut = reference._expert_layer(file, x.reshape(18, -1), p,
                                    tuple(range(64)))
    h = latent_moe.rms_norm(x, p["ln2"], whole.eps)
    shared = latent_moe.swiglu(h, p["ws_gu"], p["ws_down"], jnp.float32)
    import dataclasses
    total = x + shared
    pairs = 0
    for share in range(32):
        held = (2 * share, 2 * share + 1)
        cfg = dataclasses.replace(whole, held_experts=held)
        mine = {**p, "w_gu": p["w_gu"][jnp.asarray(held)],
                "w_down": p["w_down"][jnp.asarray(held)]}
        out, counts = latent_moe.expert_ffn(x, mine, cfg)
        total = total + (out - x - shared)
        pairs += int(counts[0])
        assert int(counts[1]) == 18 * 6
    assert pairs == 18 * 6                       # every pair is some share's
    np.testing.assert_allclose(np.asarray(total).reshape(18, -1),
                               np.asarray(uncut), atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("sq", [1, 16])
def test_latent_kernels_under_the_interpreter(monkeypatch, sq):
    """The decode sweep and the chunk pass against the dense absorbed
    reference, lane-aligned sizes, ragged frontiers and a dead row."""
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    B, H, W, R, L, S = 3, 8, 256, 128, 2, 256
    keys = jax.random.split(jax.random.PRNGKey(sq), 2)
    q = jax.random.normal(keys[0], (B, sq, H, W), jnp.float32)
    bank = jax.random.normal(keys[1], (L, B, S, W), jnp.float32)
    pos = jnp.asarray([200, 7, 130], jnp.int32)
    active = jnp.asarray([True, False, True]) if sq == 1 else None
    got = decode_attention.cached_attention(
        q, bank, None, pos, sm_scale=0.1, layer=1, active=active,
        latent_rank=R)
    want = decode_attention.latent_attention_reference(q, bank[1], pos, 0.1, R)
    rows = [0, 2] if sq == 1 else [0, 1, 2]
    np.testing.assert_allclose(np.asarray(got)[rows], np.asarray(want)[rows],
                               atol=2e-5, rtol=1e-4)
    if sq == 1:
        assert not np.asarray(got)[1].any()      # a dead row returns zeros


def _published_heads(heads, dtype, **keys):
    """One attention layer at the published head widths (128 + 64 | 128)
    over a small rank and stream, a stack of two."""
    cfg = latent_moe.LatentMoEConfig(**{**dict(
        n_head=heads, d_model=64, q_rank=32, kv_rank=128, d_nope=128,
        d_rope=64, d_v=128, dtype=dtype, param_dtype=dtype), **keys})
    stack = latent_moe.attention_init(jax.random.PRNGKey(heads), cfg, 2,
                                      0.1, 0.02)
    return cfg, stack


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("heads,keys,kv_scale", [
    (32, {}, 1.0), (64, {}, 1.0), (32, {"rope": False, "q_rank": None}, 1.0),
    (32, {}, 3.46)], ids=["32", "64", "32-nope", "32-kv_scale"])
def test_the_up_projected_chunk_kernel_under_the_interpreter(
        monkeypatch, heads, keys, kv_scale, dtype):
    """A chunk through the layer's own calls in the up-projected form
    (``with_up`` -> ``latent_project`` -> ``cached_attention(latent_up=)``
    -> ``latent_output``), the kernel under the interpreter, against the
    absorbed form through the dense reference and ``W_kvb[v]``: a chunk
    that starts at no multiple of the key block and runs into the next, in
    layer 1 of a pool and of a stack of two."""
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    # the form for a chunk this short (the rule's own test is the next)
    monkeypatch.setattr(decode_attention, "latent_up_projects",
                        lambda *a: True)
    cfg, stack = _published_heads(heads, dtype, **keys)
    p = jax.tree_util.tree_map(lambda a: a[1], stack)
    S, Sq, at = 1024, 32, 500
    rng = jax.random.split(jax.random.PRNGKey(7), 2)
    x = jax.random.normal(rng[0], (1, at + Sq, cfg.d_model), jnp.float32)
    positions = jnp.arange(at + Sq)
    _, rows = latent_moe.latent_project(x, p, cfg, positions, 1.0, kv_scale)
    bank = jnp.zeros((2, 1, S, cfg.cache_row[0]), dtype).at[1, :, :at + Sq] \
        .set(rows.astype(dtype))
    chunk, where = x[:, at:], positions[at:]

    def layer(p, attention):
        q, _ = latent_moe.latent_project(chunk, p, cfg, where, 1.0, kv_scale)
        return latent_moe.latent_output(chunk, attention(q), p, cfg)

    def kernel(q):
        q, up = q
        assert q.shape[-1] == cfg.d_nope + cfg.d_rope
        return decode_attention.cached_attention(
            q, bank, None, jnp.asarray([at]), sm_scale=cfg.softmax_scale,
            layer=1, latent_rank=cfg.kv_rank, latent_up=up)
    got = layer(latent_moe.with_up(p, latent_moe.head_major(
        stack["wkv_b"], cfg), 1), kernel)
    want = layer(p, lambda q: decode_attention.latent_attention_reference(
        q, bank[1], at, cfg.softmax_scale, cfg.kv_rank))
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        return
    # bf16: no further from the absorbed form's dense reference than the
    # absorbed KERNEL is (both round their probabilities; this one its keys
    # and values too, where the absorbed one rounds its queries)
    absorbed = layer(p, lambda q: decode_attention.cached_attention(
        q, bank, None, jnp.asarray([at]), sm_scale=cfg.softmax_scale,
        layer=1, latent_rank=cfg.kv_rank))
    far = lambda a: float(jnp.max(jnp.abs(a - want)))
    assert far(got) <= max(2 * far(absorbed), 0.02), (far(got),
                                                       far(absorbed))


def _kernels(jaxpr):
    from tests.unit.ops.traced_sweeps import _deep
    return [e.params["name"] for e in _deep(jaxpr)
            if e.primitive.name == "pallas_call"]


@pytest.mark.parametrize("sq,biased,kernel", [
    (1, False, decode_attention.LATENT_SWEEP),
    (8, False, "latent_chunk_attention"),
    (8, True, "latent_chunk_attention"),
    (256, True, "latent_chunk_attention"),
    (512, True, "latent_chunk_attention"),
    (256, False, decode_attention.LATENT_UP_CHUNK),
    (512, False, decode_attention.LATENT_UP_CHUNK)])
def test_the_calls_shape_and_bias_pick_the_form(monkeypatch, sq, biased,
                                                kernel):
    """One call site, un-absorbed queries and the layer's up-projection, at
    the published widths (64 heads of 128 + 64 | 128 over a rank of 512 in
    640 lanes): a tick, a verify's few tokens and any call under a bias run
    the absorbed kernels, a prompt's chunk the up-projected one.  Traced,
    never run."""
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    H, R, W, S = 64, 512, 640, 2048
    assert decode_attention.latent_up_projects(158, H, W, R, 128, 64, 128) \
        and not decode_attention.latent_up_projects(157, H, W, R, 128, 64,
                                                    128)
    # ... which is what a family tells the batcher of a chunk's passes
    published = latent_moe.LatentMoEConfig(
        n_head=H, kv_rank=R, d_nope=128, d_rope=64, d_v=128)
    assert latent_moe_inference.FAMILY.chunk_form(published, sq) == (
        "up_projected" if sq >= 158 else "absorbed")
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16)

    def call(q, bank, w, bias):
        return decode_attention.latent_cached_attention(
            q, bank, jnp.asarray([300]), 0.07, R, layer=1,
            bias=bias if biased else None,
            up=decode_attention.LatentUp(w, 1, 128))
    jaxpr = jax.make_jaxpr(call)(
        shape(1, sq, H, 192), shape(2, 1, S, W), shape(2, H, R, 256),
        jax.ShapeDtypeStruct((1, sq, S), jnp.float32))
    assert _kernels(jaxpr.jaxpr) == [kernel]
    assert jaxpr.out_avals[0].shape == (1, sq, H, 128)


def test_what_the_family_does_not_serve_is_refused_at_construction():
    cfg, params = _model(_file())
    with pytest.raises(NotImplementedError, match="scale banks"):
        latent_moe_inference.FAMILY.init_cache(cfg, 2, 64, kv_dtype="int8")
    engine = deepspeed_tpu.init_inference(model=(cfg, params),
                                          config={"dtype": "float32"})
    base = {"slots": 2, "max_len": 64, "prefill_chunk": 16}
    with pytest.raises(NotImplementedError, match="serving.paging"):
        engine.serve(config={**base, "paging": {"enabled": True}})
    with pytest.raises(NotImplementedError, match="serving.speculative"):
        engine.serve(config={**base, "speculative": {
            "enabled": True, "draft": {"n_layer": 1, "d_model": 32,
                                       "n_head": 2}}})


def test_bf16_passes_and_a_left_out_term_fails():
    """``test_reference.py``'s third test for this family (that file's are
    ``gpt_reference``'s): bf16 inside the program passes the comparison that
    decides ``correct``; the shared expert, the dense layer's FFN or the
    expert layers' attention left out does not."""
    import dataclasses

    from benchmarks.chip.reference import compare
    file = _file()
    cfg, params = _model(file, seed=3)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 48)).astype(np.int32)
    ref = reference.forward(file, params, tokens, 16)
    bf16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)

    def system(params, cfg):
        return latent_moe.apply(params, tokens, cfg)[:, -16:, :cfg.vocab_size]
    assert compare.agrees(system(params, bf16), ref)
    moe = params["moe_blocks"]
    no_shared = {**params, "moe_blocks": {
        **moe, "ws_down": jnp.zeros_like(moe["ws_down"])}}
    assert not compare.agrees(system(no_shared, bf16), ref)
    dense = params["dense_blocks"]
    no_dense_ffn = {**params, "dense_blocks": {
        **dense, "w_down": jnp.zeros_like(dense["w_down"])}}
    assert not compare.agrees(system(no_dense_ffn, bf16), ref)
    no_attention = {**params, "moe_blocks": {
        **moe, "wo": jnp.zeros_like(moe["wo"])}}
    assert not compare.agrees(system(no_attention, bf16), ref)


@pytest.fixture(scope="module")
def loud():
    """``(config, weights as the other matrices are drawn, the program's
    forward, the reference's logits)`` for the faults below: built once, the
    sound run held to the comparison here."""
    from benchmarks.chip.reference import compare
    file = _file()
    cfg, _ = _model(file)
    params = latent_moe.init(cfg, jax.random.PRNGKey(3))
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 48)).astype(np.int32)
    ref = reference.forward(file, params, tokens, 16)
    system = jax.jit(lambda params: latent_moe.apply(
        params, tokens, cfg)[:, -16:, :cfg.vocab_size])
    assert compare.agrees(system(params), ref)
    return params, system, ref


@pytest.mark.parametrize("fault", ["zero", "permute", "layer"])
def test_a_fault_in_the_routed_experts_fails(loud, fault):
    """The routed product left out, a held expert's rows through another
    expert's down-projection, a layer reading another layer's experts
    (``latent_moe_control.FAULTS``): with the routed experts drawn as the
    other matrices are and the program in float32 (so that no choice of the
    router differs between the two), none passes the comparison that decides
    ``correct``.  (At the family's benchmark scale, an eighth, the tiny
    size reads under the limits, as everything does there: ``compare.py``;
    the chip's readings are in PERF.md 6.)"""
    from benchmarks.chip.reference import compare
    from benchmarks.chip.reference.latent_moe_control import FAULTS
    params, system, ref = loud
    assert not compare.agrees(system(FAULTS[fault](params)), ref)


def test_the_control_runs_the_check_through_the_slot_path():
    """``latent_moe_control.readings`` at the tiny size: the check's
    prompts through the server's slot path with and without a planted
    fault, the reference on the weights as drawn."""
    from benchmarks.chip import harness
    from benchmarks.chip.reference import compare
    from benchmarks.chip.reference.latent_moe_control import readings
    cell = harness.Cell(
        name="tiny", chips=1, config=_file(), end_to_end=[], per_layer=[],
        root=ROOT, traffic={
            "serving": {"slots": 4, "max_len": 64, "prefill_chunk": 16},
            "check": {"prompt_lens": [5, 17, 33], "ticks": 3}})
    worst, rms = readings(cell, 11, "none")
    assert worst <= compare.TOLERANCE and rms <= compare.RMS_TOLERANCE
    for fault in ("zero", "layer", "int8"):
        assert readings(cell, 11, fault)[1] > rms, fault
