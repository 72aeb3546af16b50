"""What the shortcut-connected double-layer family alone has (the probes
every family answers are ``test_family_conformance.py``'s): two cache rows a
layer, the zero-compute experts through the slot path and in the gateway's
record, the shortcut's place, the published cut's bytes, the terms whose
absence bf16 must not hide, and the gates' choices against the
reference's."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.chip import longcat_flash_family as family
from benchmarks.chip.reference import compare
from benchmarks.chip.reference import longcat_flash_reference as reference
from deepspeed_tpu.models import (cache_family, gpt_inference,
                                  shortcut_latent_moe as model,
                                  shortcut_latent_moe_inference as served)
from deepspeed_tpu.moe import held_experts
from tests.unit.models import family_harness as harness
from tests.unit.models.family_harness import ATOL, RTOL

SPEC = harness.SPECS["longcat-flash-chat-ep32"]


def test_the_family_is_picked_by_its_config_and_owns_two_rows_a_layer():
    cfg, _ = harness.model(SPEC)
    fam = cache_family(cfg)
    assert fam is served.FAMILY and isinstance(fam, gpt_inference.Family)
    assert served.KVCache is gpt_inference.KVCache
    for op in ("write_slot", "read_slot", "reset_slot"):
        assert op not in vars(served), op
    cache = fam.init_cache(cfg, 3, 64)
    # one bank of latent rows in whole lane rows, two rows a double layer
    assert cache.v is None and cache.k.shape == (2 * cfg.n_layer, 3, 64, 128)
    assert gpt_inference.cache_layers(cfg) == 2 * cfg.n_layer
    assert cfg.row_elements == 40 and cfg.cache_row == (128,)
    assert cache.stats.shape == (held_experts.n_pair_counts(len(cfg.held)),)
    assert cfg.router_width == 16 + 8 and len(cfg.held) == 4
    assert cfg.q_scale == pytest.approx((64 / 48) ** 0.5)
    assert cfg.kv_scale == pytest.approx(2 ** 0.5)


def _first_layer_choices(file, params, tokens):
    """The reference's own choices at layer 0 for ``tokens`` [S]: ``[S,
    moe_topk]`` ids."""
    eps = file["rms_norm_eps"]
    dim = file["qk_rope_head_dim"]
    inv_freq = float(file["rope_theta"]) ** (
        -jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    blocks = params["blocks"]
    p = blocks["attn0"]
    with jax.default_matmul_precision("highest"):
        x = params["wte"][tokens].astype(jnp.float32)
        x = x + reference._attention(
            file, reference._norm(x, p["ln1"][0], eps), p, 0, inv_freq)
        h = reference._norm(x, p["ln2"][0], eps)
        prob = jax.nn.softmax(h @ blocks["moe"]["router"][0], -1)
    return np.asarray(jax.lax.top_k(
        prob + blocks["moe"]["router_bias"][0], file["moe_topk"])[1])


def test_zero_compute_choices_through_the_slot_path_and_in_the_record():
    """The conformance suite's slot path for this family (``SPEC.keys``: two
    choices a token, a selection bias that is not zero) holds tokens
    whose every choice is a zero-compute expert and tokens that chose none;
    about half the routed pairs are counted ``zero``, and the gateway's
    record of a harvested tick names them."""
    file = harness.tiny_file(SPEC.name, **SPEC.keys)
    cfg, params = harness.loud(SPEC)
    assert np.abs(np.asarray(params["blocks"]["moe"]["router_bias"])).min() > 0
    lengths = SPEC.slot_paths[0][1]
    chosen = _first_layer_choices(file, params,
                                  harness.prompts(cfg, lengths)[-1])
    zero = chosen >= cfg.n_experts
    assert zero.all(axis=1).any() and (~zero).all(axis=1).any()
    gateway = harness.loud_served(SPEC)
    before = harness.counters(gateway)
    gateway.probe_logits(harness.prompts(cfg, lengths), 2)
    counts = held_experts.read_pair_counts(
        harness.grown(gateway, before, "moe_pairs"))
    assert counts["routed"] > counts["zero"] > counts["routed"] // 4
    # the record the gateway makes of them at a harvested tick
    gateway.metrics.record_moe_pairs(gateway._batcher.counts("moe_pairs"))
    snap = gateway.metrics.snapshot()
    total = held_experts.read_pair_counts(
        gateway._batcher.counts("moe_pairs"))
    assert snap["moe_pairs_zero"] == total["zero"]
    assert snap["moe_pages_over_cap"] == 0
    assert snap["moe_expert_pairs"] == total["per_expert"]


def test_the_shortcut_joins_after_the_second_dense_ffn(monkeypatch):
    """Moving ``s`` one sublayer earlier (before the second attention reads
    the stream) changes the logits by far more than the tolerance the sound
    run is held to."""
    cfg, _ = harness.model(SPEC)
    # every matrix drawn as the others are (the router too), so that the
    # expert branch is a large part of the stream at this tiny size
    params = model.init(cfg, jax.random.PRNGKey(2))
    tokens = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 24)).astype(np.int32)
    ref = harness.reference_logits(SPEC, params, tokens, 24)
    sound = np.asarray(model.apply(params, tokens, cfg))[..., :cfg.vocab_size]
    np.testing.assert_allclose(sound, ref, atol=ATOL, rtol=RTOL)
    monkeypatch.setattr(model, "SHORTCUT_JOINS_AFTER", 0)
    early = np.asarray(model.apply(params, tokens, cfg))[..., :cfg.vocab_size]
    # 250 times the tolerance at this size (two layers: only the second
    # attention and the second dense FFN read the moved branch); the chip's
    # reading at the published widths is the control's (``shortcut_early``)
    assert np.abs(early - ref).max() > 50 * ATOL


def test_the_published_cut_weighs_what_the_configuration_says():
    """The builder's own layout at the published cut, two bytes a
    parameter: 4 double layers of 1,242.8 M held parameters, 16,384 rows of
    embedding and of head: 10.35 GB."""
    cfg = family.build(harness.published(SPEC.name))
    assert (cfg.n_layer, len(cfg.held), cfg.router_width) == (4, 16, 768)
    shapes = jax.eval_shape(
        lambda: family.init(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert all(x.dtype == jnp.bfloat16 for x in leaves)
    blocks = sum(x.size for x in jax.tree_util.tree_leaves(shapes["blocks"]))
    mla = (6144 * 1536 + 1536 * 64 * 192 + 6144 * 576 + 512 * 64 * 256
           + 64 * 128 * 6144 + 2 * 6144 + 1536 + 512)
    layer = (2 * mla + 2 * 3 * 6144 * 12288 + 6144 * 768 + 768
             + 16 * 3 * 6144 * 2048)
    assert blocks == 4 * layer
    total = 2 * sum(x.size for x in leaves)
    assert total == 2 * (4 * layer + 2 * 16384 * 6144 + 6144)
    assert total == pytest.approx(10.35e9, rel=2e-3)
    # a cached token: 8 attention sublayers x 640 stored lanes x 2 bytes
    assert 2 * cfg.cache_layers * cfg.cache_row[0] == 10240


def test_a_left_out_term_fails_where_bf16_passes():
    """Beside ``test_bf16_passes_the_cells_limits``: with every matrix drawn
    as the others are, bf16 inside the program passes the comparison that
    decides ``correct``, and a dense FFN, the second attention or the
    identity part left out does not.  (At the benchmark's scales, the
    embedding at 1.0, the tiny size reads under the limits whatever is left
    out, as everything does there: ``compare.py``; the chip's readings at
    the published widths are the control's.)"""
    cfg, _ = harness.model(SPEC)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 48)).astype(np.int32)
    bf16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)

    def system(params):
        return model.apply(params, tokens, bf16)[:, -16:, :cfg.vocab_size]
    loud = model.init(cfg, jax.random.PRNGKey(3))
    ref = harness.reference_logits(SPEC, loud, tokens, 16)
    assert compare.agrees(system(loud), ref)
    blocks = loud["blocks"]

    def without(part, name):
        return {**loud, "blocks": {**blocks, part: {
            **blocks[part], name: jnp.zeros_like(blocks[part][name])}}}
    assert not compare.agrees(system(without("dense1", "w_down")), ref)
    assert not compare.agrees(system(without("attn1", "wo")), ref)
    zero_weight = held_experts.zero_weight
    try:
        held_experts.zero_weight = lambda routing, n: (
            0.0 * zero_weight(routing, n)[0], zero_weight(routing, n)[1])
        assert not compare.agrees(system(loud), ref)
    finally:
        held_experts.zero_weight = zero_weight


def test_the_gates_choices_in_common_with_the_references():
    """``--in-common`` at the tiny size: a float32 program's gates choose
    what the reference's do, token for token, through a chunked prefill; a
    gate computed in bf16 does not."""
    from benchmarks.chip.reference import longcat_flash_control as control
    file = harness.tiny_file(SPEC.name)
    cfg, params = harness.model(SPEC, seed=5)
    tokens = np.random.default_rng(12).integers(
        0, cfg.vocab_size, 41).astype(np.int32)
    want = np.asarray(jax.jit(lambda p, t: reference.choices(file, p, t))(
        params, tokens[None]))[0]
    assert want.shape == (cfg.n_layer, 41, cfg.experts_per_token)
    got = control.program_choices(cfg, params, tokens, 16)
    sound = control.shares(got, want)
    assert sound["mean"] == sound["least"] == 1.0 and sound["tokens"] == 41
    with control.planted("bf16_router"):
        wrong = control.shares(
            control.program_choices(cfg, params, tokens, 16), want)
    assert sound["agree"] and len(wrong["by_layer"]) == cfg.n_layer
    assert wrong["mean"] < 1.0 and not wrong["agree"], wrong
    assert model.route_softmax_all is held_experts.route_softmax_all
