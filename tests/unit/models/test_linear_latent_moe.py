"""The linear-attention / latent-attention family (``kimi_linear``: KDA layers
beside NoPE latent layers) against its plain reference at a tiny size on the
CPU: the uncached ``apply`` and the slot path (chunked prefill, then decode
through the latent bank and the per-slot state) against the reference's
token-by-token recurrence; bf16 under ``compare.py``'s limits; the expert
layer's shares against the uncut layer; the siblings ``build`` refuses; and
the control's faults, each of which must read over a tolerance (on weights
drawn ten times louder than the family's, ``LOUD``, so that the layers and
not the embedding make the logits).

The tolerances.  ``ATOL`` / ``RTOL`` (2e-5, 1e-4) are the hybrid family's and
for its reason: both sides compute in float32, the program's chunked (WY)
form sums a sub-chunk's terms in another order than the recurrence and
solves its triangular system by products, at the CPU's default precision,
which reads 2e-7 to 5e-6 here on logits of about 0.6; a fault below reads
1e-3 or more (``50 * ATOL`` is asked of it)."""

import dataclasses
import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.chip import kimi_linear_family
from benchmarks.chip.reference import compare
from benchmarks.chip.reference import kimi_linear_control as control
from benchmarks.chip.reference import kimi_linear_reference as reference
from deepspeed_tpu.models import (cache_family, latent_moe,
                                  linear_latent_moe,
                                  linear_latent_moe_inference)
from deepspeed_tpu.models.hybrid_ssm_moe import run_parts

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
NAME = "kimi-linear-48b-a3b-ep8"
CHUNK = 16              # the gateway's prefill chunk; the scan's is 8
ATOL, RTOL = 2e-5, 1e-4
LOUD = 0.2
KDA, LATENT = linear_latent_moe.KDA, linear_latent_moe.LATENT


def _published():
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           NAME + ".json")) as f:
        return json.load(f)


def _file(**keys):
    """The benchmark's configuration file at the rehearsal's tiny sizes."""
    file = _published()
    with open(os.path.join(ROOT, "tests", "unit", "chipbench", "tiny",
                           "configs", NAME + ".json")) as f:
        file.update(json.load(f))
    return {**file, **keys}


def _model(file, seed=0, std=None, dtype=jnp.float32):
    """``std``: weights drawn that much louder than the family's 0.02 (the
    fault tests: at 0.02 and this width the layers add a thousandth to the
    logits, and so does any fault in them)."""
    cfg = dataclasses.replace(kimi_linear_family.build(file), dtype=dtype,
                              kda_chunk=8)
    return cfg, _params(cfg, seed, std)


@functools.lru_cache(maxsize=None)
def _params(cfg, seed, std):
    """Drawn once a (config, seed, scale), in one program."""
    def draw(key):
        if std is None:
            params = kimi_linear_family.init(cfg, key, jnp.float32)
        else:
            params = linear_latent_moe.init(
                dataclasses.replace(cfg, param_dtype=jnp.float32), key,
                std=std)
        # a selection bias large enough to move choices at this size
        for run in params["runs"]:
            for part in run_parts(run):
                if "router_bias" in part:
                    part["router_bias"] = part["router_bias"] * 30
        return params
    return jax.jit(draw)(jax.random.PRNGKey(seed))


def _gateway(cfg, params, **serving):
    engine = deepspeed_tpu.init_inference(
        model=(cfg, params), config={"dtype": jnp.dtype(cfg.dtype).name})
    gateway = engine.serve(config={"slots": 6, "max_len": 128,
                                   "prefill_chunk": CHUNK,
                                   "queue_capacity": 8, **serving})
    gateway.shutdown(drain=False, timeout=60)
    return gateway


def _tokens(cfg, n=40):
    return jax.random.randint(jax.random.PRNGKey(2), (2, n), 0,
                              cfg.vocab_size)


@functools.lru_cache(maxsize=None)
def _reference_of_the_loud_model():
    """``(cfg, params, tokens, reference logits)`` of the fault tests: one
    forward of the reference for all of them."""
    file = _file()
    cfg, params = _model(file, seed=1, std=LOUD)
    tokens = _tokens(cfg)
    return cfg, params, tokens, np.asarray(
        reference.forward(file, params, tokens, 40))


def test_layer_kinds_come_from_the_two_lists_and_a_run_is_a_repeated_unit():
    cfg = kimi_linear_family.build(_published())
    K, L = KDA, LATENT
    # the published 27-entry lists cut to 8: KDA 1 2 3 5 6 7, latent 4 8
    assert cfg.kda_layers == (1, 2, 3, 5, 6, 7)
    assert cfg.full_attn_layers == (4, 8)
    assert cfg.labels == (K + "+dense", K, K, L, K, K, K, L)
    assert cfg.units == (((K + "+dense",), (0,), 1), ((K,), (1,), 2),
                         ((L,), (0,), 1), ((K,), (3,), 3), ((L,), (1,), 1))
    assert [("".join(u), n) for u, n in reference._units(
        reference._labels(_published()))] == [
        ("Kd", 1), ("Ke", 2), ("Le", 1), ("Ke", 3), ("Le", 1)]
    # the tiny file's own lists: a unit of three labels, twice
    tiny = kimi_linear_family.build(_file())
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
    cfg = kimi_linear_family.build(_published())
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


@pytest.mark.parametrize("key,value,said", [
    ("q_lora_rank", 1536, "kimi-k2.7-code-ep32's family"),
    ("mla_use_nope", False, "kimi-k2.7-code-ep32's family"),
    ("rope_scaling", {"type": "yarn", "factor": 64}, "rope_scaling"),
    ("num_expert_group", 8, "no expert groups"),
    ("num_nextn_predict_layers", 1, "MTP"),
    ("tie_word_embeddings", True, "matrix of its own"),
    ("moe_layer_freq", 2, "every layer after the leading dense ones"),
    ("model_type", "kimi_k2", "")])
def test_a_sibling_configuration_is_refused_by_name(key, value, said):
    with pytest.raises(AssertionError, match=said or None):
        kimi_linear_family.build({**_published(), key: value})


def test_the_cache_holds_a_latent_bank_and_a_state_leaf_together():
    cfg = dataclasses.replace(kimi_linear_family.build(_file()),
                              dtype=jnp.float32)
    fam = cache_family(cfg)
    assert fam is linear_latent_moe_inference.FAMILY
    cache = fam.init_cache(cfg, 3, 64)
    assert (cfg.count(KDA), cfg.count(LATENT)) == (5, 2)
    assert cache.k.shape == (2, 3, 64, 128) and cache.v is None
    state, tails = cache.state
    assert state.shape == (5, 3, 16, 64) and state.dtype == jnp.float32
    assert tails.shape == (5, 3, 3, 3 * 64)
    assert cache.ring is None
    assert cache.stats.shape == (5 + len(cfg.held) + 3,)


def test_apply_equals_the_reference():
    file = _file()
    cfg, params = _model(file, seed=1)
    tokens = _tokens(cfg)
    got = jax.jit(lambda p, t: linear_latent_moe.apply(p, t, cfg))(
        params, tokens)[..., :cfg.vocab_size]
    ref = reference.forward(file, params, tokens, 40)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=ATOL,
                               rtol=RTOL)


LENGTHS = (1, CHUNK, 3 * CHUNK + 5)


def _slot_path(file, cfg, params, lengths=LENGTHS, ticks=8):
    """``(gateway, [(got, reference)] a prompt)``: chunked prefill, then
    ``ticks`` decode ticks through the gateway's own programs and slot
    cache, against the reference's full forward, on logits."""
    gateway = _gateway(cfg, params)
    prompts = [np.random.default_rng(3 + n).integers(
        0, cfg.vocab_size, n).astype(np.int32) for n in lengths]
    replies, got = gateway.probe_logits(prompts, ticks)
    out = []
    for p, reply, logits in zip(prompts, replies, got):
        full = np.concatenate([p, np.asarray(reply, np.int32)])
        # compiled: op by op the reference compiles every primitive of
        # every new shape on its own
        out.append((np.asarray(logits)[:, :cfg.vocab_size], np.asarray(
            jax.jit(lambda p, t: reference.forward(file, p, t, ticks + 1))(
                params, full[None]))[0]))
    return gateway, out


def test_slot_path_equals_the_reference_full_forward():
    """Prompts that are and are not multiples of the prefill chunk (16) and
    of the scan's sub-chunk (8); the counters of the three probes together."""
    file = _file()
    cfg, params = _model(file)
    gateway, pairs = _slot_path(file, cfg, params)
    for n, (got, ref) in zip(LENGTHS, pairs):
        np.testing.assert_allclose(got, ref, atol=ATOL, rtol=RTOL,
                                   err_msg=f"prompt of {n}")
    # rows stepped = live slots x KDA layers; scan tokens real and padded;
    # the expert layers alone feed the pair counts
    named = dict(zip(gateway._batcher.state_counters,
                     gateway._batcher.counts("state_steps")))
    n_kda, ticks = cfg.count(KDA), 8
    real = sum(LENGTHS)
    padded = sum(-(-n // CHUNK) * CHUNK for n in LENGTHS)
    assert named == {"ssm_rows_stepped": len(LENGTHS) * ticks * n_kda,
                     "scan_tokens_real": real * n_kda,
                     "scan_tokens_padded": (padded - real) * n_kda}
    pairs = gateway._batcher.counts("moe_pairs")
    assert pairs[0] == pairs[3:-2].sum() > 0 == pairs[-1] \
        and len(pairs) == 5 + len(cfg.held)


def test_bf16_passes_the_cells_limits():
    """The program in bf16 against the float32 reference under
    ``compare.py``'s two limits, through the slot path (reads 0.003 /
    0.002 here; the limits are calibrated on the chip at the published
    widths)."""
    file = _file()
    cfg, params = _model(file, dtype=jnp.bfloat16)
    _, pairs = _slot_path(file, cfg, params, lengths=(3 * CHUNK + 5,))
    assert compare.agrees(*pairs[0])


# ----------------------------------------------------------- expert layer

def test_the_four_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Experts 0-3, 4-7, 8-11, 12-15 of 16 on four chips: the routed parts
    the four shares give, with the shared expert counted once, are the
    uncut layer's result."""
    file = _file(num_experts=16)
    cfg, params = _model(file)
    assert len(cfg.held) == cfg.n_experts == 16
    # the first expert layer: position 0 of the repeated unit, repetition 0
    p = jax.tree_util.tree_map(lambda a: a[0], params["runs"][1][0])
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 9, cfg.d_model))
    whole, counts = latent_moe.expert_ffn(x, p, cfg)
    assert counts[0] == counts[1] == 2 * 9 * cfg.experts_per_token
    routed, pairs = 0.0, 0
    for share in range(4):
        held = tuple(range(4 * share, 4 * share + 4))
        part = dataclasses.replace(cfg, held_experts=held)
        mine = {**p, "w_gu": p["w_gu"][held[0]:held[-1] + 1],
                "w_down": p["w_down"][held[0]:held[-1] + 1]}
        out, c = latent_moe.expert_ffn(x, mine, part)
        alone, _ = latent_moe.expert_ffn(
            x, {**mine, "w_down": mine["w_down"] * 0}, part)
        routed = routed + (out - alone)         # this share's routed part
        shared = alone - x                      # what every chip computes
        pairs += int(c[0])
    assert pairs == int(counts[0])
    np.testing.assert_allclose(np.asarray(x + shared + routed),
                               np.asarray(whole), atol=1e-6, rtol=1e-5)


# ------------------------------------------------------ the control's faults

def _off(got, ref):
    return float((np.abs(got - ref) - RTOL * np.abs(ref)).max())


@pytest.mark.parametrize("fault", ["none", "no_delta", "mean_decay",
                                   "no_conv", "no_gate", "zero", "int8"])
def test_a_planted_fault_reads_over_a_tolerance(fault):
    """``apply`` against the reference with one of
    ``kimi_linear_control``'s faults planted: the delta term left out, a
    head's decay the mean of its channels', the convolution on ``k`` left
    out, the output gate left out, the routed product zeroed, 8-bit
    matrices.  Unplanted the two agree within ``ATOL`` / ``RTOL``; planted,
    some logit is off by 50 times ``ATOL`` or more."""
    cfg, params, tokens, ref = _reference_of_the_loud_model()
    served = control.WEIGHTS.get(fault, lambda p: p)(params)
    with control.planted(fault):
        got = np.asarray(jax.jit(
            lambda p, t: linear_latent_moe.apply(p, t, cfg))(
                served, tokens))[..., :cfg.vocab_size]
    if fault == "none":
        assert _off(got, ref) <= ATOL
    else:
        assert _off(got, ref) > 50 * ATOL, _off(got, ref)


@pytest.mark.parametrize("fault", ["state_other", "bf16_state"])
def test_a_fault_in_the_carried_state_reads_over_a_tolerance(fault):
    """The two faults only a cache can show, through the slot path: a KDA
    layer that starts every call from the next layer's state, and ``S``
    rounded to bf16 after every chunk and every tick (three chunk edges and
    8 ticks of rounding)."""
    file = _file()
    cfg, params = _model(file, std=LOUD)
    with control.planted(fault):
        _, pairs = _slot_path(file, cfg, params, lengths=(3 * CHUNK + 5,))
    assert _off(*pairs[0]) > 50 * ATOL, _off(*pairs[0])
