"""The shortcut-connected double-layer family against its plain reference at
a tiny size on the CPU, in float32: the uncached ``apply`` and the slot path
(chunked prefill, then decode through two cache rows a layer) against the
reference's explicit, up-projected full forward; the shortcut's place; the
expert branch's shares against the uncut layer with the identity part
counted once; the published cut's bytes; what the family refuses; and the
controls' faults at the tiny size."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.chip import longcat_flash_family as family
from benchmarks.chip.reference import compare
from benchmarks.chip.reference import longcat_flash_reference as reference
from deepspeed_tpu.models import (cache_family, gpt_inference,
                                  shortcut_latent_moe as model,
                                  shortcut_latent_moe_inference as served)
from deepspeed_tpu.moe import held_experts

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
NAME = "longcat-flash-chat-ep32"
#: float32 on both sides: the absorbed and the up-projected form differ in
#: the order of their sums alone, and so do a chunked and a whole pass
ATOL, RTOL = 2e-5, 1e-4


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


def _reference(file, params, tokens, last):
    """The reference's logits, compiled (op by op it compiles every
    primitive of every new shape on its own)."""
    return jax.jit(lambda p, t: reference.forward(file, p, t, last))(
        params, jnp.asarray(tokens))


def _model(file, seed=0):
    cfg = dataclasses.replace(family.build(file), dtype=jnp.float32)
    return cfg, family.init(cfg, jax.random.PRNGKey(seed), jnp.float32)


def test_the_family_is_picked_by_its_config_and_owns_two_rows_a_layer():
    cfg, _ = _model(_file())
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


def test_absorbed_apply_equals_the_up_projected_reference():
    file = _file()
    cfg, params = _model(file, seed=1)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0,
                                cfg.vocab_size)
    got = model.apply(params, tokens, cfg)[..., :cfg.vocab_size]
    ref = _reference(file, params, tokens, 40)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=ATOL,
                               rtol=RTOL)


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


def test_slot_path_equals_the_reference_full_forward():
    """Chunked prefill (chunk boundaries crossed) and decode ticks through
    the gateway's own programs against the reference, on logits, with a
    selection bias that is not zero, tokens whose every choice is a
    zero-compute expert and tokens that chose none."""
    file = _file(moe_topk=2, published={"num_layers": 28,
                                        "n_routed_experts": 8,
                                        "vocab_size": 4096})
    cfg, params = _model(file)
    assert np.abs(np.asarray(params["blocks"]["moe"]["router_bias"])).min() > 0
    engine = deepspeed_tpu.init_inference(model=(cfg, params),
                                          config={"dtype": "float32"})
    gateway = engine.serve(config={"slots": 4, "max_len": 128,
                                   "prefill_chunk": 16,
                                   "queue_capacity": 8})
    gateway.shutdown(drain=False, timeout=60)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 33, 70)]
    chosen = _first_layer_choices(file, params, prompts[-1])
    zero = chosen >= cfg.n_experts
    assert zero.all(axis=1).any() and (~zero).all(axis=1).any()
    ticks = 5
    replies, got = gateway.probe_logits(prompts, ticks)
    for p, reply, logits in zip(prompts, replies, got):
        full = np.concatenate([p, np.asarray(reply, np.int32)])[None]
        ref = np.asarray(_reference(file, params, full, ticks + 1))[0]
        np.testing.assert_allclose(logits[:, :cfg.vocab_size], ref,
                                   atol=ATOL, rtol=RTOL)
    counts = gateway._batcher.counts("moe_pairs")
    assert len(counts) == 5 + len(cfg.held)
    assert counts[0] == counts[3:-2].sum() > 0 == counts[-2]
    # about half the router's outputs are zero-compute experts
    assert counts[1] > counts[-1] > counts[1] // 4
    assert counts[1] > counts[0] >= counts[2] > 0
    # the record the gateway makes of them at a harvested tick
    gateway.metrics.record_moe_pairs(counts)
    snap = gateway.metrics.snapshot()
    assert snap["moe_pairs_zero"] == counts[-1]
    assert snap["moe_pages_over_cap"] == 0
    assert snap["moe_expert_pairs"] == [int(c) for c in counts[3:-2]]


def test_the_shortcut_joins_after_the_second_dense_ffn(monkeypatch):
    """Moving ``s`` one sublayer earlier (before the second attention reads
    the stream) changes the logits by far more than the tolerance the sound
    run is held to."""
    file = _file()
    cfg, _ = _model(file)
    # every matrix drawn as the others are (the router too), so that the
    # expert branch is a large part of the stream at this tiny size
    params = model.init(cfg, jax.random.PRNGKey(2))
    tokens = np.random.default_rng(1).integers(
        0, cfg.vocab_size, (2, 24)).astype(np.int32)
    ref = np.asarray(_reference(file, params, tokens, 24))
    sound = np.asarray(model.apply(params, tokens, cfg))[..., :cfg.vocab_size]
    np.testing.assert_allclose(sound, ref, atol=ATOL, rtol=RTOL)
    monkeypatch.setattr(model, "SHORTCUT_JOINS_AFTER", 0)
    early = np.asarray(model.apply(params, tokens, cfg))[..., :cfg.vocab_size]
    # 250 times the tolerance at this size (two layers: only the second
    # attention and the second dense FFN read the moved branch); the chip's
    # reading at the published widths is the control's (``shortcut_early``)
    assert np.abs(early - ref).max() > 50 * ATOL


def test_the_shares_add_up_with_the_identity_part_counted_once():
    """32 experts and 16 zero-compute ones over 4 shares of 8: the four
    held parts, plus the identity part every share computes alike counted
    ONCE, are the uncut reference's expert branch."""
    file = _file(n_routed_experts=32, zero_expert_num=16, moe_topk=6,
                 published={"num_layers": 28, "n_routed_experts": 32,
                            "vocab_size": 4096})
    whole, params = _model(file, seed=4)
    moe = jax.tree_util.tree_map(lambda a: a[0], params["blocks"]["moe"])
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 9, whole.d_model))
    flat = h.reshape(18, -1)
    with jax.default_matmul_precision("highest"):
        uncut, _ = reference._moe(file, flat, moe, tuple(range(32)),
                                  lambda e: moe["w_gu"][e],
                                  lambda e: moe["w_down"][e])
    routing = held_experts.route_softmax_all(
        flat, moe["router"], moe["router_bias"], 6, whole.routed_scale)
    w_zero, n_zero = held_experts.zero_weight(routing, 32)
    identity = np.asarray(w_zero)[:, None] * np.asarray(flat)
    assert int(n_zero) > 0 and np.abs(identity).max() > 0
    total, pairs = 0.0, 0
    for share in range(4):
        held = tuple(range(8 * share, 8 * share + 8))
        cfg = dataclasses.replace(whole, held_experts=held)
        mine = {**moe, "w_gu": moe["w_gu"][jnp.asarray(held)],
                "w_down": moe["w_down"][jnp.asarray(held)]}
        out, counts = model.expert_branch(h, mine, cfg)
        # every share adds the whole identity part
        total = total + np.asarray(out).reshape(18, -1)
        pairs += int(counts[0])
        assert int(counts[1]) == 18 * 6 and int(counts[-1]) == int(n_zero)
    assert pairs + int(n_zero) == 18 * 6     # every pair is some share's
    np.testing.assert_allclose(total - 3 * identity, np.asarray(uncut),
                               atol=ATOL, rtol=RTOL)


def test_the_published_cut_weighs_what_the_configuration_says():
    """The builder's own layout at the published cut, two bytes a
    parameter: 4 double layers of 1,242.8 M held parameters, 16,384 rows of
    embedding and of head: 10.35 GB."""
    file = _published()
    cfg = family.build(file)
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


def test_what_the_family_does_not_serve_is_refused_at_construction():
    cfg, params = _model(_file())
    assert set(served.UNSUPPORTED) == {"speculative", "paging", "prefix",
                                       "int8", "draft"}
    with pytest.raises(NotImplementedError, match="scale banks"):
        served.FAMILY.init_cache(cfg, 2, 64, kv_dtype="int8")
    engine = deepspeed_tpu.init_inference(model=(cfg, params),
                                          config={"dtype": "float32"})
    base = {"slots": 2, "max_len": 64, "prefill_chunk": 16}
    with pytest.raises(NotImplementedError, match="serving.paging"):
        engine.serve(config={**base, "paging": {"enabled": True}})
    with pytest.raises(NotImplementedError, match="serving.speculative"):
        engine.serve(config={**base, "speculative": {
            "enabled": True, "draft": {"n_layer": 1, "d_model": 32,
                                       "n_head": 2}}})
    gateway = engine.serve(config=base)
    gateway.shutdown(drain=False, timeout=60)
    with pytest.raises(NotImplementedError, match="serving.prefix"):
        gateway._batcher.build_prefix(np.zeros((8,), np.int32))


@pytest.mark.parametrize("key,value,why", [
    ("zero_expert_type", "copy", "zero_expert_type"),
    ("attention_method", "MHA", "attention_method"),
    ("mla_scale_kv_lora", False, "mla_scale"),
    ("rope_scaling", {"factor": 10, "rope_type": "yarn"}, "rope_scaling"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("mtp_num_layers", 1, "MTP"),
    ("norm_topk_prob", True, "norm_topk_prob"),
    ("router_bias", True, "router_bias"),
    ("attention_bias", True, "attention_bias")])
def test_the_builder_refuses_a_sibling_by_name(key, value, why):
    family.build(_file())                    # the file itself is served
    with pytest.raises(AssertionError, match=why):
        family.build(_file(**{key: value}))


def test_bf16_passes_and_a_left_out_term_fails():
    """bf16 inside the program passes the comparison that decides
    ``correct``, at the benchmark's scales and with every matrix drawn as the
    others are; with those a dense FFN, the second attention or the
    identity part left out does not.  (At the benchmark's scales, the
    embedding at 1.0, the tiny size reads under the limits whatever is left
    out, as everything does there: ``compare.py``; the chip's readings at
    the published widths are the control's.)"""
    file = _file()
    cfg, params = _model(file, seed=3)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 48)).astype(np.int32)
    bf16 = dataclasses.replace(cfg, dtype=jnp.bfloat16)

    def system(params):
        return model.apply(params, tokens, bf16)[:, -16:, :cfg.vocab_size]
    assert compare.agrees(system(params),
                          _reference(file, params, tokens, 16))
    loud = model.init(cfg, jax.random.PRNGKey(3))
    ref = _reference(file, loud, tokens, 16)
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


@pytest.fixture(scope="module")
def control_readings():
    """``fault -> (largest, rms)`` through ``longcat_flash_control``'s faults
    and ``hybrid_ssm_moe_control.readings`` at the tiny size (the check's
    prompts through the server's slot path, the reference on the weights as
    drawn), each read once a process."""
    from benchmarks.chip import harness
    from benchmarks.chip.reference import hybrid_ssm_moe_control as base
    from benchmarks.chip.reference import longcat_flash_control as control
    cell = harness.Cell(
        name="tiny", chips=1, config=_file(), end_to_end=[], per_layer=[],
        root=ROOT, traffic={
            "serving": {"slots": 4, "max_len": 64, "prefill_chunk": 16},
            "check": {"prompt_lens": [5, 17, 33], "ticks": 3}})
    read = {}

    def of(fault):
        if fault not in read:
            kept = base.WEIGHTS, base.planted
            base.WEIGHTS, base.planted = control.WEIGHTS, control.planted
            try:
                read[fault] = base.readings(cell, 11, fault)
            finally:
                base.WEIGHTS, base.planted = kept
        return read[fault]

    return of


@pytest.mark.parametrize("fault", ["zero", "no_identity", "permute",
                                   "shortcut_early", "no_kv_scale",
                                   "cache_other", "int8", "bf16_router"])
def test_a_controls_fault_reads_worse_than_a_sound_run(control_readings,
                                                       fault):
    """A sound run is within the limits and the fault reads worse; what it
    patched is put back."""
    from benchmarks.chip.reference import longcat_flash_control as control
    assert fault in control.FAULTS
    worst, rms = control_readings("none")
    assert worst <= compare.TOLERANCE and rms <= compare.RMS_TOLERANCE
    assert control_readings(fault)[1] > rms
    assert model.SHORTCUT_JOINS_AFTER == 1
    assert set(control.UNSEEN) <= set(control.FAULTS)


def test_the_gates_choices_in_common_with_the_references():
    """``--in-common`` at the tiny size: a float32 program's gates choose
    what the reference's do, token for token, through a chunked prefill; a
    gate computed in bf16 does not."""
    from benchmarks.chip.reference import longcat_flash_control as control
    file = _file()
    cfg, params = _model(file, seed=5)
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
