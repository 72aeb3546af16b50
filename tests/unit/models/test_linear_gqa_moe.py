"""What the linear-attention / grouped-attention family alone has
(``solar_open2``: KDA layers with ``beta`` in (0, 2) behind a gated NoPE
grouped layer that LEADS its period; the probes every family answers, the
shares of a layer that add up to the uncut one and the siblings ``build``
refuses among them, are ``test_family_conformance.py``'s, its kernels
``tests/unit/ops/test_delta_rule.py``'s): the layer kinds from the one
0-indexed list, the published sizes, ``beta``'s range beside Kimi-Linear's,
the gate's place before ``W_o``, the cache's two banks and state leaf
together, and the scopes the telemetry names."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.chip import solar_open2_family
from benchmarks.chip.reference import solar_open2_reference as reference
from deepspeed_tpu.models import (cache_family, gpt_inference, linear_gqa_moe,
                                  linear_gqa_moe_inference,
                                  linear_latent_moe,
                                  linear_latent_moe_inference)
from deepspeed_tpu.moe.held_experts import n_pair_counts
from tests.unit.models import family_harness as harness

SPEC = harness.SPECS["solar-open2-250b-ep8"]
KIMI = harness.SPECS["kimi-linear-48b-a3b-ep8"]
KDA, GQA = linear_gqa_moe.KDA, linear_gqa_moe.GQA


def test_the_zero_indexed_list_puts_the_full_layer_first():
    file = harness.published(SPEC.name)
    cfg = solar_open2_family.build(file)
    # the published 12-entry list cut to 4 layers: layer 0, 0-INDEXED
    assert file["gqa_layers"][:3] == [0, 4, 8] and cfg.gqa_layers == (0,)
    assert cfg.labels == (GQA, KDA, KDA, KDA)
    assert cfg.units == (((GQA,), (0,), 1), ((KDA,), (0,), 3))
    assert [("".join(u), n) for u, n in reference._units(
        reference._kinds(file))] == [("G", 1), ("K", 3)]
    # read 1-indexed (Kimi-Linear's lists) layer 4 would be the full one
    assert linear_latent_moe.LinearLatentMoEConfig(
        n_layer=4, kda_layers=(1, 2, 3), full_attn_layers=(4,),
        first_k_dense=0).labels[-1] == linear_latent_moe.LATENT
    # two periods: a unit of four kinds, twice, the full layer first in both
    deep = solar_open2_family.build({**file, "num_hidden_layers": 8})
    assert deep.gqa_layers == (0, 4)
    assert deep.units == (((GQA, KDA, KDA, KDA), (0, 0, 1, 2), 2),)
    params = jax.eval_shape(lambda k: solar_open2_family.init(cfg, k),
                            jax.random.PRNGKey(0))
    first, second = params["runs"]
    # per head [q | z]: the query projection twice as wide
    assert first["wq"].shape == (1, 64 * 2 * 128, 4096)
    assert first["wk"].shape == first["wv"].shape == (1, 4096, 8 * 128)
    assert first["wo"].shape == (1, 64 * 128, 4096)
    assert second["w_qkv"].shape == (3, 4096, 3 * 8192)
    assert second["conv_w"].shape == (3, 4, 3 * 8192)
    assert second["w_fa"].shape == second["w_ga"].shape == (3, 4096, 128)
    assert second["w_b"].shape == (3, 4096, 64)
    for run in params["runs"]:      # every layer is an expert layer
        assert run["w_gu"].shape[1:] == (40, 4096, 2 * 1280)
        assert run["router"].shape[1:] == (4096, 320)
        assert run["ws_gu"].shape[1:] == (4096, 2 * 1280)
    axes = linear_gqa_moe.logical_axes(cfg)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, params)) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    # 3.31 B parameters, 6.62 GB in bf16
    n = sum(int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(params))
    assert round(n / 1e9, 2) == 3.31


def test_the_published_sizes():
    cfg = solar_open2_family.build(harness.published(SPEC.name))
    assert (cfg.d_model, cfg.d_expert) == (4096, 1280)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.d_kda, cfg.conv_kernel,
            cfg.kda_chunk, cfg.kda_beta_scale) == (64, 128, 8192, 4, 64, 2.0)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim) == (64, 8, 128)
    assert cfg.attn_scale == pytest.approx(128 ** -0.5)
    assert (cfg.n_experts, len(cfg.held), cfg.experts_per_token,
            cfg.n_shared_experts, cfg.routed_scale, cfg.norm_topk) == \
        (320, 40, 8, 1, 1.0, True)
    assert cfg.cache_row == (1024, 1024) and cfg.cache_layers == 1
    (n, shape, dtype), (_, tail, _) = cfg.cache_state
    assert (n, shape, dtype) == (3, (128, 8192), jnp.float32)
    assert tail == (3, 3 * 8192)
    # a KDA layer's state of a slot: twice any other state in the tree
    assert 128 * 8192 * 4 == 4_194_304
    # a slot of 16,384: the row, three states, three tails = 80.1 MB
    slot = 16384 * 2 * 1024 * 2 + 3 * 4_194_304 + 3 * 3 * 24576 * 2
    assert round(slot / 1e6, 1) == 80.1


@pytest.mark.parametrize("deep", [False, True], ids=["one-period", "two"])
def test_apply_is_the_reference_at_both_layouts(deep):
    """One period is two runs of one kind (the cell's layout), two periods
    ONE run of a unit of four kinds: both against the plain reference,
    under the conformance's tolerance for its reason, a layer's share of it
    for each layer (the conformance's model has four)."""
    keys = dict(num_hidden_layers=8) if deep else {}
    cfg, params = harness.model(SPEC, seed=2, std=harness.LOUD, **keys)
    assert len(cfg.units) == (1 if deep else 2)
    toks = harness.tokens(cfg, 24)
    got = jax.jit(lambda p, t: linear_gqa_moe.apply(p, t, cfg))(params, toks)
    ref = harness.reference_logits(SPEC, params, toks, 24, **keys)
    assert harness.off(np.asarray(got)[..., :cfg.vocab_size], ref) \
        <= harness.ATOL * cfg.n_layer / 4, SPEC.why


def test_beta_reaches_past_one_and_the_kimi_familys_stays_under_it():
    def betas(spec):
        cfg, params = harness.model(spec, seed=5, std=harness.LOUD)
        run = next(part for run in params["runs"]
                   for part in linear_latent_moe.run_parts(run)
                   if "w_b" in part)
        p = jax.tree_util.tree_map(lambda a: a[0], run)
        x = 20 * jax.random.normal(jax.random.PRNGKey(1),
                                   (2, 64, cfg.d_model))
        return cfg, np.asarray(linear_latent_moe.kda_inputs(x, p, cfg)[2])

    cfg, beta = betas(SPEC)
    assert cfg.kda_beta_scale == 2.0
    assert 0 < beta.min() and beta.max() < 2 and (beta > 1).mean() > 0.3
    cfg, beta = betas(KIMI)
    assert cfg.kda_beta_scale == 1.0
    assert 0 < beta.min() and beta.max() < 1


def test_the_gate_is_applied_before_w_o():
    cfg, params = harness.model(SPEC, seed=3, std=harness.LOUD)
    p = jax.tree_util.tree_map(lambda a: a[0], params["runs"][0])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, cfg.d_model))
    H, D = cfg.n_head, cfg.head_dim
    (q, z), (k, v) = linear_gqa_moe.attention_project(x, p, cfg)
    assert q.shape == z.shape == (2, 9, H, D) and k.shape == (
        2, 9, cfg.n_kv_head, D)
    # per head [q | z]: head j's query is rows 2 j D .. of ``wq``, its gate
    # the next D
    h = linear_gqa_moe.rms_norm(x, p["ln1"], cfg.eps, cfg.dtype)
    np.testing.assert_allclose(
        np.asarray(z[:, :, 1]), np.asarray(h @ p["wq"][3 * D:4 * D].T),
        atol=1e-5)
    a = linear_gqa_moe.hybrid_ssm_moe._causal_attention(q, k, v, cfg)
    got = linear_gqa_moe.attention_output(
        x, linear_gqa_moe.gated(a, z), p, cfg)
    want = x + (np.asarray(a) * np.asarray(jax.nn.sigmoid(z))).reshape(
        2, 9, H * D) @ np.asarray(p["wo"])
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    # gate logits of zero halve every channel of what reaches W_o
    shut = dict(p, wq=p["wq"].reshape(H, 2, D, -1).at[:, 1].set(0.0)
                .reshape(p["wq"].shape))
    (q0, z0), _ = linear_gqa_moe.attention_project(x, shut, cfg)
    assert float(jnp.abs(z0).max()) == 0
    half = linear_gqa_moe.attention_output(
        x, linear_gqa_moe.gated(a, z0), p, cfg)
    np.testing.assert_allclose(
        np.asarray(half - x),
        0.5 * np.asarray(a).reshape(2, 9, H * D) @ np.asarray(p["wo"]),
        atol=1e-5)


def test_the_cache_holds_two_grouped_banks_and_a_state_leaf_together():
    cfg, _ = harness.model(SPEC)
    fam = cache_family(cfg)
    assert fam is linear_gqa_moe_inference.FAMILY
    cache = fam.init_cache(cfg, 3, 64)
    assert (cfg.count(KDA), cfg.count(GQA)) == (3, 1)
    assert cache.k.shape == cache.v.shape == (1, 3, 64, 2 * 16)
    state, tails = cache.state
    assert state.shape == (3, 3, 16, 64) and state.dtype == jnp.float32
    assert tails.shape == (3, 3, 3, 3 * 64)
    assert cache.ring is None
    assert cache.stats.shape == (n_pair_counts(len(cfg.held)) + 3,)
    # two banks, and a state: an admission keeps its row cache
    assert not gpt_inference.in_place(fam, cache)
    assert not fam.pool_rows
    # the step is the KDA families' one layer loop
    assert linear_gqa_moe_inference.kda_step \
        is linear_latent_moe_inference.kda_step


def test_the_scopes_the_telemetry_names_are_in_both_programs():
    """A tick's and a prompt pass's HLO name every scope ``docs/
    telemetry.md`` lists for the family."""
    cfg, params = harness.model(SPEC)
    fam = cache_family(cfg)
    cache = fam.init_cache(cfg, 2, 64)
    lengths = jnp.array([5, 9], jnp.int32)
    tick = jax.jit(lambda p, t, c, n: fam.decode_step(
        p, t, cfg, c, lengths=n, active=n > 0)).lower(
        params, jnp.zeros((2,), jnp.int32), cache, lengths).as_text(
        debug_info=True)
    chunk = jax.jit(lambda p, t, c, n: fam.extend(
        p, t, cfg, c, lengths=n, valid=n)).lower(
        params, jnp.zeros((2, 16), jnp.int32), cache, lengths).as_text(
        debug_info=True)
    shared = ("kda_in_proj", "kda_gate", "kda_conv", "kda_output",
              "gqa_attention/project", "gqa_attention/cache_update",
              "gqa_attention/cache_read", "attn_gate", "attn_out",
              "moe_router", "moe_routed", "moe_shared")
    for scope in shared + ("kda_decode_step",):
        assert scope in tick, scope
    for scope in shared + ("kda_chunk_scan",):
        assert scope in chunk, scope
    assert "cache_read/attn_gate" in tick and "kda_chunk_scan" not in tick
