"""What the linear-attention / latent-attention family alone has
(``kimi_linear``: KDA layers beside NoPE latent layers; the probes every
family answers are ``test_family_conformance.py``'s, its kernels
``tests/unit/ops/test_delta_rule.py``'s): the layer kinds from the two
published lists, the published sizes, and the cache's latent bank and state
leaf together."""


import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.chip import kimi_linear_family
from benchmarks.chip.reference import kimi_linear_reference as reference
from deepspeed_tpu.models import (cache_family, linear_latent_moe,
                                  linear_latent_moe_inference)
from deepspeed_tpu.moe.held_experts import n_pair_counts
from tests.unit.models import family_harness as harness

SPEC = harness.SPECS["kimi-linear-48b-a3b-ep8"]
KDA, LATENT = linear_latent_moe.KDA, linear_latent_moe.LATENT


def test_layer_kinds_come_from_the_two_lists_and_a_run_is_a_repeated_unit():
    cfg = kimi_linear_family.build(harness.published(SPEC.name))
    K, L = KDA, LATENT
    # the published 27-entry lists cut to 8: KDA 1 2 3 5 6 7, latent 4 8
    assert cfg.kda_layers == (1, 2, 3, 5, 6, 7)
    assert cfg.full_attn_layers == (4, 8)
    assert cfg.labels == (K + "+dense", K, K, L, K, K, K, L)
    assert cfg.units == (((K + "+dense",), (0,), 1), ((K,), (1,), 2),
                         ((L,), (0,), 1), ((K,), (3,), 3), ((L,), (1,), 1))
    assert [("".join(u), n) for u, n in reference._units(
        reference._labels(harness.published(SPEC.name)))] == [
        ("Kd", 1), ("Ke", 2), ("Le", 1), ("Ke", 3), ("Le", 1)]
    # the tiny file's own lists: a unit of three labels, twice
    tiny = kimi_linear_family.build(harness.tiny_file(SPEC.name))
    assert tiny.units == (((K + "+dense",), (0,), 1),
                          ((K, L, K), (1, 0, 2), 2))
    params = jax.eval_shape(lambda k: kimi_linear_family.init(cfg, k),
                            jax.random.PRNGKey(0))
    first, second = params["runs"][:2]
    assert first["w_gu"].shape == (1, 2304, 2 * 9216) \
        and "router" not in first
    assert second["w_gu"].shape == (2, 32, 2304, 2 * 1024)
    assert second["w_qkv"].shape == (2, 2304, 3 * 4096)
    assert second["conv_w"].shape == (2, 4, 3 * 4096)
    assert second["w_fa"].shape == second["w_ga"].shape == (2, 2304, 128)
    assert params["runs"][2]["wq"].shape == (1, 2304, 32, 192)
    assert "wq_a" not in params["runs"][2]
    axes = linear_latent_moe.logical_axes(cfg)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, params)) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    # 2.09 B parameters, 4.19 GB in bf16
    n = sum(int(np.prod(a.shape))
            for a in jax.tree_util.tree_leaves(params))
    assert round(n / 1e9, 2) == 2.09


def test_the_published_sizes():
    cfg = kimi_linear_family.build(harness.published(SPEC.name))
    assert (cfg.d_model, cfg.d_ff, cfg.d_expert) == (2304, 9216, 1024)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.d_kda, cfg.conv_kernel) \
        == (32, 128, 4096, 4)
    assert (cfg.n_head, cfg.kv_rank, cfg.d_nope, cfg.d_rope, cfg.d_v,
            cfg.q_rank, cfg.rope) == (32, 512, 128, 64, 128, None, False)
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5)
    assert (cfg.n_experts, len(cfg.held), cfg.experts_per_token,
            cfg.routed_scale, cfg.first_k_dense) == (256, 32, 8, 2.446, 1)
    assert cfg.cache_row == (640,) and cfg.cache_layers == 2
    (n, shape, dtype), (_, tail, _) = cfg.cache_state
    assert (n, shape, dtype) == (6, (128, 4096), jnp.float32)
    assert tail == (3, 3 * 4096)
    # a KDA layer's state of a slot is the hybrid families', to the byte
    assert 128 * 4096 * 4 == 2_097_152
    ops, nbytes = kimi_linear_family.state_step_count(cfg, 1)
    assert nbytes == 2 * 2_097_152 and ops / nbytes == 0.875
    assert kimi_linear_family.decode_count(cfg, 1) == (
        2.0 * 32 * (576 + 512), 2.0 * 576)
    ops, nbytes = kimi_linear_family.chunk_scan_count(cfg, 1)
    assert ops == 32 * (10.0 * 64 * 128 + 6.0 * 128 * 128)
    assert nbytes == 4.0 * 7 * 4096


def test_the_cache_holds_a_latent_bank_and_a_state_leaf_together():
    cfg, _ = harness.model(SPEC)
    fam = cache_family(cfg)
    assert fam is linear_latent_moe_inference.FAMILY
    cache = fam.init_cache(cfg, 3, 64)
    assert (cfg.count(KDA), cfg.count(LATENT)) == (5, 2)
    assert cache.k.shape == (2, 3, 64, 128) and cache.v is None
    state, tails = cache.state
    assert state.shape == (5, 3, 16, 64) and state.dtype == jnp.float32
    assert tails.shape == (5, 3, 3, 3 * 64)
    assert cache.ring is None
    assert cache.stats.shape == (n_pair_counts(len(cfg.held)) + 3,)
