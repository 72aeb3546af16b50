"""What the window-and-full attention family alone has (``mellum``; the
probes every family answers are ``test_family_conformance.py``'s, the ring's
wrap among its slot paths): the file against the source's config, the
published sizes, the count of a prompt pass, both pools of the cache and the
slot ops over them, a chunk that straddles the window's edge, the sweep's
counts by pool, the gate, grouped heads with a band through
``cached_attention``, and the ring against the band over the whole
history."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.chip import mellum_family
from deepspeed_tpu.models import (cache_family, window_moe,
                                  window_moe_inference)
from deepspeed_tpu.moe import held_experts
from deepspeed_tpu.ops.pallas import decode_attention as da
from tests.unit.chipbench.common import check_configuration
from tests.unit.models import family_harness as harness
from tests.unit.models.family_harness import ATOL, RTOL

SPEC = harness.SPECS["mellum2-12b-a2.5b-ep4"]
WINDOW = 16                 # the tiny file's: a chunk is a window long
#: the numbers of the source's config.json (the catalog's row, whose
#: ``source_url`` the file's ``source`` is)
SOURCE = {
    "head_dim": 128, "hidden_size": 2304, "intermediate_size": 7168,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "moe_intermediate_size": 896, "num_attention_heads": 32,
    "num_experts": 64, "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "sliding_window": 1024,
    "vocab_size": 98304,
    "layer_types": (["sliding_attention"] * 3 + ["full_attention"]) * 7,
    "mlp_layer_types": ["sparse"] * 28,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}}}


# ------------------------------------------------------- the configuration

def test_the_file_is_the_sources_but_for_what_reduced_lists():
    entry, file = harness.entry(SPEC.name), harness.published(SPEC.name)
    cfg = check_configuration(file, entry, SOURCE)
    assert entry["reduced"] == ["num_experts", "vocab_size"]
    assert file["published"] == {"num_experts": 64, "vocab_size": 98304}
    assert set(file["assumed"]) >= {"qk_norm", "weights", "max_len", "ring"}
    assert file["deployment_chips_per_layer"] == 4
    assert (file["num_experts"], file["vocab_size"]) == (16, 24576)
    assert cfg.n_layer == 28 and cfg.units == (
        (("window",) * 3 + ("full",), (0, 1, 2, 0), 7),)


def test_the_published_sizes():
    cfg = mellum_family.build(harness.published(SPEC.name))
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim,
            cfg.window, cfg.qk_norm) == (2304, 32, 4, 128, 1024, True)
    assert (cfg.n_experts, len(cfg.held), cfg.experts_per_token,
            cfg.d_expert) == (64, 16, 8, 896)
    assert cfg.yarn == (16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    assert cfg.cache_row == (512, 512) and cfg.cache_layers == 7
    assert cfg.cache_ring == (21, 1024)
    # a slot of 8,192 positions: 117.4 MB of whole rows beside 44.0 MB of
    # rings, 161.5 MB; in one geometry 469.8 MB
    row = 2 * sum(cfg.cache_row)
    assert row == 2048 and round(7 * 8192 * row / 1e6, 1) == 117.4
    assert round(21 * 1024 * row / 1e6, 1) == 44.0
    assert round(28 * 8192 * row / 1e6, 1) == 469.8
    plan = cache_family(cfg).sweep_plan(cfg, 8192)
    assert (plan.kernel, plan.block_k, plan.windows) == (
        da.GROUPED_SWEEP, 512, ((None, 7),))
    assert (plan.ring.kernel, plan.ring.block_k, plan.ring.Smax,
            plan.ring.windows) == (da.GROUPED_SWEEP, 512, 1024,
                                   ((None, 21),))
    assert round(plan.share_of_one_geometry, 4) == round(161.48 / 469.76, 4)
    assert mellum_family.decode_count(cfg, 1) == (16384.0, 2048.0)
    assert mellum_family.expert_count(cfg, 1, 1) == (
        2.0 * 3 * 2304 * 896, 2.0 * 3 * 2304 * 896)
    # 7 lane rows: the grouped matmul's tiles divide both matrices
    assert held_experts.gmm_tiling(2304, 1792) == (128, 2304, 896)
    assert held_experts.gmm_tiling(896, 2304) == (128, 896, 768)
    params = jax.eval_shape(lambda k: mellum_family.init(cfg, k),
                            jax.random.PRNGKey(0))
    parts = params["runs"][0]
    assert len(params["runs"]) == 1 and len(parts) == 4
    assert parts[3]["w_gu"].shape == (7, 16, 2304, 1792)
    assert parts[0]["q_norm"].shape == parts[0]["k_norm"].shape == (7, 128)
    # the attention matrices head-major
    assert parts[0]["wq"].shape == parts[0]["wo"].shape == (7, 32, 128, 2304)
    assert parts[0]["wk"].shape == parts[0]["wv"].shape == (7, 4, 128, 2304)
    assert params["head"].shape == params["wte"].shape == (24576, 2304)
    axes = window_moe.logical_axes(cfg)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, params)) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))


def test_the_count_of_a_prompt_pass_follows_the_band():
    cfg = mellum_family.build(harness.published(SPEC.name))
    # one admission of 2 chunks of 512 from 0: a window layer's query sees
    # min(p + 1, 1,024) keys, so both kinds see the same pairs here ...
    ops, nbytes, calls = mellum_family.chunk_count(cfg, [(0, 2, 512)])
    pairs = 1024 * 1025 / 2
    assert calls == 2 * 28 and ops == 28 * pairs * 16384
    # ... and past the window only the 7 full layers' grow with the prefix
    far, _, _ = mellum_family.chunk_count(cfg, [(4096, 1, 512)])
    full = sum(range(4097, 4097 + 512))
    assert far == (7 * full + 21 * 512 * 1024) * 16384


# ------------------------------------------------------------- the passes

def test_the_cache_holds_whole_rows_for_full_layers_and_rings_for_window():
    cfg, _ = harness.model(SPEC)
    fam = cache_family(cfg)
    assert fam is window_moe_inference.FAMILY
    cache = fam.init_cache(cfg, 3, 64)
    assert (cfg.count("window"), cfg.count("full")) == (6, 2)
    assert cache.k.shape == cache.v.shape == (2, 3, 64, 2 * 16)
    assert [r.shape for r in cache.ring] == [(6, 3, WINDOW, 2 * 16)] * 2
    # a slot shorter than the window never laps: its ring is the slot
    assert fam.init_cache(cfg, 1, 8).ring[0].shape == (6, 1, 8, 32)
    for feature in ("paging", "prefix", "speculative", "int8", "draft"):
        assert fam.unsupported[feature]
    with pytest.raises(NotImplementedError, match="compute dtype"):
        fam.init_cache(cfg, 1, 64, kv_dtype="int8")


@pytest.mark.parametrize("chunk", [8, 24, 40], ids=["W/2", "1.5W", "2.5W"])
def test_a_chunk_that_straddles_the_windows_edge(chunk):
    """Chunks shorter and LONGER than the window (a chunk of 24 or 40 keeps
    its last 16 rows in the ring and its first queries still see the ring
    as it was): 61 tokens, so every chunk but the first straddles the edge
    of some query's window and the last is padded."""
    cfg, params = harness.model(SPEC, seed=4)
    (got, ref), = harness.slot_path_logits(
        SPEC, harness.gateway(cfg, params, prefill_chunk=chunk), params,
        (61,))
    np.testing.assert_allclose(got, ref, atol=ATOL, rtol=RTOL)


def test_slot_write_read_and_reset_walk_both_pools():
    cfg, params = harness.model(SPEC)
    fam = cache_family(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 40), 0,
                                cfg.vocab_size)
    _, row = fam.prefill(params, tokens, cfg, fam.init_cache(cfg, 1, 64))
    pool = fam.write_slot(fam.init_cache(cfg, 3, 64), 2, row)
    for name in ("k", "v"):
        bank, src = np.asarray(getattr(pool, name)), \
            np.asarray(getattr(row, name))
        assert (bank[:, 2] == src[:, 0]).all() and src.any()
        assert not bank[:, :2].any()
    for bank, src in zip(pool.ring, row.ring):
        assert (np.asarray(bank)[:, 2] == np.asarray(src)[:, 0]).all()
        assert np.asarray(src).any(axis=-1).all(), "40 tokens lap a ring"
        assert not np.asarray(bank)[:, :2].any()
    back = fam.read_slot(pool, 2, length=40)
    assert all((np.asarray(a) == np.asarray(b)).all()
               for a, b in zip(back.ring + (back.k, back.v),
                               row.ring + (row.k, row.v)))
    assert int(back.length) == 40 and not np.asarray(back.stats).any()
    blank = fam.reset_slot(pool, 2)
    assert not any(np.asarray(a).any()
                   for a in blank.ring + (blank.k, blank.v))


def test_the_sweeps_counts_follow_both_pools():
    cfg, gateway = harness.model(SPEC)[0], harness.served(SPEC)
    bat = gateway._batcher
    assert bat.sweep_plan.ring.Smax == WINDOW
    assert set(bat.sweep_by_kind([5, 40])) == {"full", "window"}
    assert gateway.snapshot()["kv_pool_share_of_one_geometry"] == \
        (2 * 128 + 6 * 16) / (8 * 128)
    # a window of one block of 128 in slots of 256: each pool counted by
    # its own length, a ring that has lapped all live and no more
    wide = dataclasses.replace(cfg, window=128)
    plan = cache_family(wide).sweep_plan(wide, 256)
    assert (plan.block_k, plan.ring.block_k, plan.ring.Smax) == (
        256, 128, 128)
    by_kind = plan.by_kind([5, 200])
    assert by_kind == {"full": (2 * (6 + 201), 2 * (256 + 256), 2),
                       "window": (6 * (6 + 128), 6 * (128 + 128), 6)}
    assert plan.token_counts([5, 200]) == (
        2 * 207 + 6 * 134, 2 * 512 + 6 * 256)
    assert plan.block_counts([5, 200], 4) == (2 * 2 + 6 * 2,
                                              2 * 4 * 1 + 6 * 4 * 1)


# ----------------------------------------------------------- expert layer

def test_route_softmax_is_the_softmax_over_all_renormalised_over_the_chosen():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.normal(size=(9, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    routing = held_experts.route_softmax(h, w, 4)
    every = np.asarray(jax.nn.softmax(
        jnp.dot(h, w, precision=jax.lax.Precision.HIGHEST), axis=-1))
    chosen = np.asarray(routing.experts)
    want = np.take_along_axis(every, chosen, axis=-1)
    np.testing.assert_allclose(np.asarray(routing.weights),
                               want / want.sum(-1, keepdims=True), rtol=1e-5)
    assert (np.sort(chosen, -1) == np.sort(
        np.argsort(-every, -1)[:, :4], -1)).all()


# --------------------------------------------- grouped heads with a band

@pytest.mark.parametrize("sq,per_row", [(1, True), (128, False), (128, True)],
                         ids=["token", "chunk", "ragged-chunk"])
def test_a_window_with_grouped_heads_through_cached_attention(
        monkeypatch, sq, per_row):
    """``G = 4`` query heads a key-value head and a band of 200 keys through
    the chunk kernel (interpret mode): the chunk pass walks the band and
    ``valid_from`` hides a chunk's first keys.  A single token keeps the
    refusal: no family sweeps grouped heads under a band (a ring is swept
    whole), and ``valid_from`` bounds a chunk's call alone."""
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(sq)
    B, H, Hkv, D, S = 2, 8, 2, 128, 512
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    q, k, v = f(B, sq, H, D), f(B, S, Hkv, D), f(B, S, Hkv, D)
    pos = jnp.asarray([300, 170]) if per_row else jnp.asarray(300)
    assert da.sweep_plan((Hkv * D,) * 2, S, H, Hkv).kernel == da.GROUPED_SWEEP
    if sq == 1:
        with pytest.raises(NotImplementedError, match="no window under a"):
            da.cached_attention(q, k, v, pos, window=200, kv_heads=Hkv)
        with pytest.raises(NotImplementedError, match="valid_from"):
            da.cached_attention(q, k, v, pos, kv_heads=Hkv, valid_from=3)
        return
    got = da.cached_attention(q, k, v, pos, window=200, kv_heads=Hkv)
    rep = lambda t: jnp.repeat(t, H // Hkv, axis=2)
    want = da.cached_attention_reference(q, rep(k), rep(v), pos, window=200)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-4)
    no_band = da.cached_attention_reference(q, rep(k), rep(v), pos)
    assert np.abs(np.asarray(no_band) - np.asarray(want)).max() > 1e-2
    first = jnp.asarray([260, 100])
    got = da.cached_attention(q, k, v, pos, window=200, kv_heads=Hkv,
                              valid_from=first)
    want = da.cached_attention_reference(q, rep(k), rep(v), pos,
                                         window=200, valid_from=first)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("pos", [0, 100, 256, 700], ids=str)
def test_ring_attention_is_the_band_over_the_whole_history(monkeypatch, pos):
    """A chunk of 128 queries at ``pos`` over a ring of 256 cells (the last
    256 tokens in cells ``p mod 256``) and its own rows, through the chunk
    kernel, equals banded attention over the whole history laid out flat."""
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(pos)
    H, Hkv, D, R, C, S = 8, 2, 128, 256, 128, 1024
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    k, v, q = f(1, S, Hkv, D), f(1, S, Hkv, D), f(1, C, H, D)
    ring_k, ring_v = (jnp.zeros((2, 1, R, Hkv * D)).at[
        1, 0, jnp.arange(max(pos - R, 0), pos) % R].set(
        t[0, max(pos - R, 0):pos].reshape(-1, Hkv * D)) for t in (k, v))
    got = da.ring_attention(q, ring_k, ring_v, k[:, pos:pos + C],
                            v[:, pos:pos + C], jnp.asarray([pos]), R, 1,
                            kv_heads=Hkv)
    rep = lambda t: jnp.repeat(t, H // Hkv, axis=2)
    want = da.cached_attention_reference(q, rep(k), rep(v), pos, window=R)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-4)


# ----------------------------------------------------------------- faults
