"""What the latent-attention, routed-expert family alone has (the probes
every family answers are ``test_family_conformance.py``'s): its one bank of
latent rows, the YaRN table against its closed form, the sigmoid router, the
latent kernels under the interpreter, which form a call's shape picks, and
the terms whose absence bf16 must not hide."""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.chip import latent_moe_family
from benchmarks.chip.reference import compare
from benchmarks.chip.reference import latent_moe_reference as reference
from deepspeed_tpu.models import (cache_family, gpt_inference, latent_moe,
                                  latent_moe_inference)
from deepspeed_tpu.moe import held_experts
from deepspeed_tpu.ops.pallas import decode_attention
from tests.unit.models import family_harness as harness

SPEC = harness.SPECS["kimi-k2.7-code-ep32"]


def test_the_family_is_picked_by_its_config_and_shares_the_one_cache_family():
    cfg, _ = harness.model(SPEC)
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


def test_yarn_table_against_its_closed_form():
    """The published sizes: 64 rotary dims, theta 50000, factor 64 over 4096
    positions, beta 32 / 1: dims that turn more than 32 times keep their
    frequency, dims that turn less than once are divided by 64, a linear
    ramp between."""
    file = harness.published(SPEC.name)
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


def _heads_at_the_published_widths(heads, dtype, **keys):
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
    cfg, stack = _heads_at_the_published_widths(heads, dtype, **keys)
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


#: heads, rank, lanes of the row, d_nope as the head-major copy has it,
#: keys of the call: the published latent attention, and the selecting
#: family's two kinds of layer (a window layer's 192-wide key part in 256
#: lanes, its call over the unrolled ring and the chunk)
_WIDTHS = {"published": (64, 512, 640, 128, 2048),
           "selecting-full": (128, 512, 640, 128, 16384),
           "selecting-window": (64, 1024, 1152, 256, 640 + 1024)}


@pytest.mark.parametrize("widths,sq,biased,kernel", [
    ("published", 1, False, decode_attention.LATENT_SWEEP),
    ("published", 8, False, "latent_chunk_attention"),
    ("published", 8, True, "latent_chunk_attention"),
    ("published", 256, True, decode_attention.LATENT_UP_CHUNK),
    ("published", 512, True, decode_attention.LATENT_UP_CHUNK),
    ("published", 256, False, decode_attention.LATENT_UP_CHUNK),
    ("published", 512, False, decode_attention.LATENT_UP_CHUNK),
    ("selecting-full", 1024, True, decode_attention.LATENT_UP_CHUNK),
    ("selecting-window", 1024, True, decode_attention.LATENT_UP_CHUNK),
    ("selecting-window", 8, True, "latent_chunk_attention")])
def test_the_calls_shape_picks_the_form(monkeypatch, widths, sq, biased,
                                        kernel):
    """One call site, un-absorbed queries and the layer's up-projection, at
    the published widths (64 heads of 128 + 64 | 128 over a rank of 512 in
    640 lanes) and at the selecting family's two: a tick and a verify's few
    tokens run the absorbed kernels, a prompt's chunk the up-projected one,
    under a bias (which reaches the kernel as one more operand, an int8
    mask) as under none (the parent's six operands).  Traced, never run."""
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    H, R, W, d_nope, S = _WIDTHS[widths]
    assert decode_attention.latent_up_projects(158, 64, 640, 512, 128, 64,
                                               128) \
        and not decode_attention.latent_up_projects(157, 64, 640, 512, 128,
                                                    64, 128)
    # ... which is what a family tells the batcher of a chunk's passes
    published = latent_moe.LatentMoEConfig(
        n_head=64, kv_rank=512, d_nope=128, d_rope=64, d_v=128)
    assert latent_moe_inference.FAMILY.chunk_form(published, sq) == (
        "up_projected" if sq >= 158 else "absorbed")
    shape = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16)

    def call(q, bank, w, bias):
        return decode_attention.latent_cached_attention(
            q, bank, jnp.asarray([300]), 0.07, R, layer=1,
            bias=bias if biased else None,
            up=decode_attention.LatentUp(w, 1, d_nope))
    jaxpr = jax.make_jaxpr(call)(
        shape(1, sq, H, d_nope + 64), shape(2, 1, S, W),
        shape(2, H, R, d_nope + 128),
        jax.ShapeDtypeStruct((1, sq, S), jnp.float32))
    assert _kernels(jaxpr.jaxpr) == [kernel]
    assert jaxpr.out_avals[0].shape == (1, sq, H, 128)
    if kernel == decode_attention.LATENT_UP_CHUNK:
        from tests.unit.ops.traced_sweeps import _deep
        launch, = [e for e in _deep(jaxpr.jaxpr)
                   if e.primitive.name == "pallas_call"]
        assert [str(v.aval.dtype) for v in launch.invars[6:]] == \
            ["int8"] * biased


def test_a_left_out_term_fails_where_bf16_passes():
    """Beside ``test_bf16_passes_the_cells_limits``: the shared expert, the
    dense layer's FFN or the expert layers' attention left out of the bf16
    program does not pass the comparison that decides ``correct``."""
    cfg, params = harness.model(SPEC, seed=3)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 48)).astype(np.int32)
    ref = harness.reference_logits(SPEC, params, tokens, 16)
    bf16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)
    system = jax.jit(lambda params: latent_moe.apply(
        params, tokens, bf16)[:, -16:, :cfg.vocab_size])
    moe = params["moe_blocks"]
    no_shared = {**params, "moe_blocks": {
        **moe, "ws_down": jnp.zeros_like(moe["ws_down"])}}
    assert not compare.agrees(system(no_shared), ref)
    dense = params["dense_blocks"]
    no_dense_ffn = {**params, "dense_blocks": {
        **dense, "w_down": jnp.zeros_like(dense["w_down"])}}
    assert not compare.agrees(system(no_dense_ffn), ref)
    no_attention = {**params, "moe_blocks": {
        **moe, "wo": jnp.zeros_like(moe["wo"])}}
    assert not compare.agrees(system(no_attention), ref)

