"""What the hybrid family's single-part block alone has (``nemotron_h``: a
layer is a mixer OR the expert layer; the probes every family answers are
``test_family_conformance.py``'s): the runs as repeated units, the published
sizes, the grouped state-space kernels against a per-head loop (held to 2e-4:
state and ``y`` of order 1 to 10 over 256 tokens, float64 loop), the padding
of an expert's matrices, and the family's own departures from the published
mathematics, each of which must read over a tolerance (on weights drawn at
``harness.LOUD``)."""

import contextlib
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.chip import nemotron_h_family
from benchmarks.chip.reference import nemotron_h_reference as reference
from deepspeed_tpu.models import (cache_family, hybrid_ssm_moe,
                                  hybrid_ssm_moe_inference)
from deepspeed_tpu.moe import held_experts
from deepspeed_tpu.ops.pallas import ssm
from tests.unit.models import family_harness as harness
from tests.unit.models.family_harness import ATOL, LOUD

SPEC = harness.SPECS["nemotron-3-nano-30b-a3b-ep4"]


def test_a_layer_is_one_part_and_a_run_is_a_repeated_unit():
    cfg = nemotron_h_family.build(harness.published(SPEC.name))
    M, E, A = "mamba", "experts", "attention"
    # MEMEM*EMEMEM*EMEME: seven runs, three of them scans
    assert cfg.units == (((M, E), (0, 0), 2), ((M,), (2,), 1),
                         ((A,), (0,), 1), ((E, M), (2, 3), 3),
                         ((A,), (1,), 1), ((E, M), (5, 6), 2),
                         ((E,), (7,), 1))
    assert [u for u, _ in reference._units(
        harness.published(SPEC.name)["hybrid_override_pattern"])] == [
        "ME", "M", "*", "EM", "*", "EM", "E"]
    assert (cfg.count(M), cfg.count(E), cfg.count(A)) == (8, 8, 2)
    # the one-kind reading the Granite block has always had
    one = dataclasses.replace(cfg, layer_types=(M,) * 5 + (A,) + (M,) * 4)
    assert one.runs == ((M, 0, 5), (A, 0, 1), (M, 5, 4))
    params = jax.eval_shape(lambda k: nemotron_h_family.init(cfg, k),
                            jax.random.PRNGKey(0))
    first = params["runs"][0]
    assert isinstance(first, list) and len(first) == 2
    # a mixer holds one norm and no expert layer; an expert layer the other
    assert "ln1" in first[0] and "ln2" not in first[0] \
        and "router" not in first[0]
    assert set(first[1]) == {"ln2", "router", "router_bias", "w_up",
                             "w_down", "ws_up", "ws_down"}
    assert first[1]["w_up"].shape == (2, 32, 2688, 1920)
    assert first[1]["ws_up"].shape == (2, 2688, 3712)
    assert params["head"].shape == params["wte"].shape == (32768, 2688)
    axes = hybrid_ssm_moe.logical_axes(cfg)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, params)) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))


def test_the_published_sizes():
    cfg = nemotron_h_family.build(harness.published(SPEC.name))
    assert (cfg.d_model, cfg.d_inner, cfg.d_conv, cfg.ssm_state,
            cfg.ssm_chunk, cfg.ssm_groups) == (2688, 4096, 6144, 128, 128, 8)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim) == (32, 2, 128)
    assert cfg.attn_scale == 128 ** -0.5
    assert (cfg.n_experts, len(cfg.held), cfg.experts_per_token,
            cfg.d_expert, cfg.d_expert_stored, cfg.d_shared,
            cfg.routed_scale) == (128, 32, 6, 1856, 1920, 3712, 2.5)
    assert not cfg.tie_head and not cfg.mixer_ffn
    assert cfg.cache_row == (256, 256) and cfg.cache_layers == 2
    (n, shape, dtype), (_, tail, _) = cfg.cache_state
    assert (n, shape, dtype) == (8, (128, 4096), jnp.float32)
    assert tail == (3, 6144)
    # a slot: 17.07 MB of state and tail whatever its length, 2,048 B a token
    assert round(8 * (128 * 4096 * 4 + 3 * 6144 * 2) / 1e6, 2) == 17.07
    assert cfg.cache_layers * 2 * sum(cfg.cache_row) == 2048
    ops, nbytes = nemotron_h_family.state_step_count(cfg, 1)
    assert nbytes == 2 * 128 * 4096 * 4 and ops / nbytes == 0.625
    assert nemotron_h_family.decode_count(cfg, 1) == (16384.0, 1024.0)
    # a pair is TWO products of 2688 x 1856, whatever is stored
    assert nemotron_h_family.expert_count(cfg, 1, 1) == (
        2.0 * 2 * 2688 * 1856, 2.0 * 2 * 2688 * 1856)
    # the padded matrices tile: the whole contraction side stays in VMEM
    assert held_experts.gmm_tiling(2688, 1920) == (128, 2688, 640)
    assert held_experts.gmm_tiling(1920, 2688) == (128, 1920, 896)


def test_the_cache_holds_two_row_layers_and_state_for_the_mixers_alone():
    cfg, _ = harness.model(SPEC)
    assert cache_family(cfg) is hybrid_ssm_moe_inference.FAMILY
    cache = cache_family(cfg).init_cache(cfg, 3, 64)
    n_ssm, n_attn = cfg.count("mamba"), cfg.count("attention")
    assert (n_ssm, n_attn, cfg.count("experts")) == (3, 1, 4)
    assert cache.k.shape == cache.v.shape == (n_attn, 3, 64, 2 * 16)
    state, tails = cache.state
    assert state.shape == (n_ssm, 3, 16, 128)
    assert tails.shape == (n_ssm, 3, 3, 128 + 2 * 2 * 16)


# ------------------------------------------------------ the grouped kernels

def _scan_inputs(rng, B, S, H, P, N, G):
    f = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    return (f(B, S, H, P), jnp.abs(f(B, S, H)) * 0.3,
            -jnp.exp(f(H)), f(B, S, G * N), f(B, S, G * N))


def _per_head_loop(state, v, dt, a, Bm, Cm, G):
    """Token by token and HEAD BY HEAD from ``state`` [B, N, H*P], float64:
    head ``h`` reads columns ``[g N, (g + 1) N)`` of ``B`` and ``C``, ``g =
    h // (H / G)``."""
    v, dt, a, Bm, Cm = (np.asarray(t, np.float64)
                        for t in (v, dt, a, Bm, Cm))
    B, S, H, P = v.shape
    N = Bm.shape[-1] // G
    h_all = np.array(state, np.float64)
    ys = np.zeros((B, S, H * P))
    for h in range(H):
        g = h // (H // G)
        lanes = slice(h * P, (h + 1) * P)
        hs = h_all[:, :, lanes]                              # [B, N, P]
        for t in range(S):
            b_t = Bm[:, t, g * N:(g + 1) * N]
            c_t = Cm[:, t, g * N:(g + 1) * N]
            hs = np.exp(dt[:, t, h] * a[h])[:, None, None] * hs \
                + b_t[:, :, None] * (dt[:, t, h, None] * v[:, t, h])[:, None]
            ys[:, t, lanes] = np.einsum("bnp,bn->bp", hs, c_t)
        h_all[:, :, lanes] = hs
    return ys, h_all


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
def test_eight_groups_in_the_chunk_scan_against_a_per_head_loop(
        monkeypatch, interpret):
    if interpret:
        monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(0)
    B, S, H, P, N, G, L = 2, 256, 16, 64, 128, 8, 2
    v, dt, a, Bm, Cm = _scan_inputs(rng, B, S, H, P, N, G)
    stack = jnp.asarray(rng.normal(size=(L, B, N, H * P)), jnp.float32)
    valid = np.array([S, 200])
    if interpret:
        assert ssm._tiles(N, H * P, G)
    y, out = ssm.ssd_chunk_scan(stack, 1, v, dt, a, Bm, Cm,
                                valid=jnp.asarray(valid), chunk=128,
                                groups=G)
    masked = np.where(np.arange(S)[None, :, None] < valid[:, None, None],
                      np.asarray(dt), 0.0)
    want_y, want_h = _per_head_loop(stack[1], v, masked, a, Bm, Cm, G)
    for b in range(B):
        np.testing.assert_allclose(np.asarray(y)[b, :valid[b]],
                                   want_y[b, :valid[b]], atol=2e-4,
                                   rtol=2e-4)
    np.testing.assert_allclose(np.asarray(out)[1], want_h, atol=2e-4,
                               rtol=2e-4)
    assert (np.asarray(out)[0] == np.asarray(stack)[0]).all()
    # group 0's B and C given to every head is another result
    tiled = lambda t: jnp.tile(t[..., :N], G)
    wrong, _ = ssm.ssd_chunk_scan(stack, 1, v, dt, a, tiled(Bm), tiled(Cm),
                                  valid=jnp.asarray(valid), chunk=128,
                                  groups=G)
    assert np.abs(np.asarray(wrong)[0] - want_y[0]).max() > 1.0


@pytest.mark.parametrize("interpret", [False, True], ids=["xla", "kernel"])
def test_eight_groups_in_the_decode_step_against_a_per_head_loop(
        monkeypatch, interpret):
    if interpret:
        monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(1)
    B, H, P, N, G, L = 3, 16, 64, 128, 8, 2
    v, dt, a, Bm, Cm = _scan_inputs(rng, B, 1, H, P, N, G)
    stack = jnp.asarray(rng.normal(size=(L, B, N, H * P)), jnp.float32)
    active = jnp.asarray([True, False, True])
    wide = lambda t: jnp.repeat(t[:, 0], P, axis=-1)
    y, out = ssm.ssm_decode_step(
        stack, 1, wide(dt) * v.reshape(B, -1), wide(jnp.exp(dt * a)),
        Bm[:, 0], Cm[:, 0], active=active, groups=G)
    want_y, want_h = _per_head_loop(stack[1], v, dt, a, Bm, Cm, G)
    live = [0, 2]
    np.testing.assert_allclose(np.asarray(y)[live], want_y[live, 0],
                               atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out)[1, live], want_h[live],
                               atol=1e-5, rtol=1e-5)
    assert not np.asarray(y)[1].any()
    assert (np.asarray(out)[1, 1] == np.asarray(stack)[1, 1]).all()
    assert (np.asarray(out)[0] == np.asarray(stack)[0]).all()


@pytest.mark.parametrize("groups,cb", [(1, 4096), (8, 4096), (8, 1024),
                                       (2, 1024), (16, 1024)])
def test_a_column_block_holds_whole_groups_or_lies_inside_one(groups, cb):
    HP = 4096
    gl, per, block = ssm._group_blocks(HP, cb, groups)
    assert gl == HP // groups and per == max(1, cb // gl)
    for c in range(HP // cb):
        first_group = c * cb // gl
        assert block(c) * per == first_group - first_group % per


# ----------------------------------------------------------- expert layer

def _expert_layer(cfg, params):
    """The first expert layer's parameters (layer 1: ``(M E) x 2``)."""
    return jax.tree_util.tree_map(lambda a: a[0], params["runs"][0][1])


def test_the_padding_of_an_experts_matrices_is_zero_and_adds_nothing():
    cfg, params = harness.model(SPEC)
    p = _expert_layer(cfg, params)
    f, fs = cfg.d_expert, cfg.d_expert_stored
    assert (f, fs) == (24, 128)
    assert not np.asarray(p["w_up"])[..., f:].any()
    assert not np.asarray(p["w_down"])[:, f:].any()
    assert np.asarray(p["w_up"])[..., :f].any()
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 7, cfg.d_model))
    cut = {**p, "w_up": p["w_up"][..., :f], "w_down": p["w_down"][:, :f]}
    np.testing.assert_array_equal(
        np.asarray(hybrid_ssm_moe.expert_ffn(x, p, cfg)[0]),
        np.asarray(hybrid_ssm_moe.expert_ffn(x, cut, cfg)[0]))


# ----------------------------------------------------------- planted faults

def _rotated(project):
    """``attention_project`` with a rotary embedding applied to q and k."""
    def rotate(t):
        S, D = t.shape[1], t.shape[-1]
        ang = jnp.arange(S)[:, None] * (
            10000.0 ** (-jnp.arange(0, D, 2) / D))[None]
        cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
        a, b = t[..., :D // 2], t[..., D // 2:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    def run(x, p, config):
        q, (k, v) = project(x, p, config)
        return rotate(q), (rotate(k), v)
    return run


def _biased_weights(h, w_router, bias, k, scale, normalize=True):
    """The sigmoid gate with the selection bias left in the weights."""
    scores = jax.nn.sigmoid(h.astype(jnp.float32) @ w_router) + bias
    weights, experts = jax.lax.top_k(scores, k)
    weights = weights / jnp.sum(weights, -1, keepdims=True)
    return held_experts.Routing(experts.astype(jnp.int32), weights * scale)


@contextlib.contextmanager
def _planted(monkeypatch, fault):
    """One departure from the published mathematics, in the program (or, for
    ``relu``, the same departure in the reference: the two then differ by
    it just the same)."""
    m = hybrid_ssm_moe
    with monkeypatch.context() as patch:
        if fault == "group0_for_every_head":
            inputs = m.ssm_scan_inputs

            def group0(u_act, dt, p, config):
                v, dt, a, Bm, Cm = inputs(u_act, dt, p, config)
                tiled = lambda t: jnp.tile(t[..., :config.ssm_state],
                                           config.ssm_groups)
                return v, dt, a, tiled(Bm), tiled(Cm)
            patch.setattr(m, "ssm_scan_inputs", group0)
        elif fault == "norm_over_all_channels":
            output = m.ssm_output
            patch.setattr(m, "ssm_output", lambda x, y, v, z, p, config:
                          output(x, y, v, z, p, dataclasses.replace(
                              config, ssm_groups=1)))
        elif fault == "relu_for_relu2":
            patch.setattr(reference, "_relu2", lambda h, up, down:
                          reference._matmul(jax.nn.relu(
                              reference._matmul(h, up)), down))
        elif fault == "bias_in_the_weight":
            patch.setattr(m, "route", _biased_weights)
        elif fault == "rotation_applied":
            patch.setattr(m, "attention_project",
                          _rotated(m.attention_project))
        yield


@pytest.mark.parametrize("fault", [
    "none", "group0_for_every_head", "norm_over_all_channels",
    "relu_for_relu2", "scale_left_out", "bias_in_the_weight",
    "rotation_applied"])
def test_a_planted_fault_reads_over_a_tolerance(monkeypatch, fault):
    """``apply`` against the reference with one departure planted: group
    0's ``B`` / ``C`` for every head, the gated norm over all channels
    instead of a group's, ``relu`` for ``relu^2``, the 2.5 left out, the
    selection bias used in the weight, a rotation applied.  Unplanted the
    two agree within ``ATOL`` / ``RTOL`` (5e-6 read on logits of 7);
    planted, some logit is off by 50 times ``ATOL`` or more (each reads 2
    to 8 whole units)."""
    file = harness.tiny_file(SPEC.name)
    cfg, params = harness.model(SPEC, seed=1, std=LOUD)
    if fault == "scale_left_out":
        cfg = dataclasses.replace(cfg, routed_scale=1.0)
    tokens = harness.tokens(cfg)
    with _planted(monkeypatch, fault):
        got = np.asarray(jax.jit(lambda p, t: hybrid_ssm_moe.apply(
            p, t, cfg))(params, tokens))[..., :cfg.vocab_size]
        # compiled inside the patch (traced with the fault planted), so not
        # the harness's cached program
        ref = np.asarray(jax.jit(lambda p, t: reference.forward(
            file, p, t, 40))(params, tokens))
    if fault == "none":
        assert harness.off(got, ref) <= ATOL
    else:
        assert harness.off(got, ref) > 50 * ATOL, harness.off(got, ref)

