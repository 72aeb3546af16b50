"""What the gated-short-convolution family alone has (``lfm2_moe``: a
convolution's tail as the only per-slot state, a dense FFN in the leading
layers beside expert layers that hold every expert; the probes every family
answers are ``test_family_conformance.py``'s, its kernels' shapes
``tests/unit/ops/test_grouped_head_of_64.py``'s): the kinds and runs, the
published cut's bytes, a tail carried between passes, taken inside a padded
one and left alone in a freed slot, and the selection bias."""

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.chip import lfm2_family
from deepspeed_tpu.models import cache_family, conv_moe, conv_moe_inference
from deepspeed_tpu.moe.held_experts import n_pair_counts, route
from tests.unit.models import family_harness as harness

SPEC = harness.SPECS["lfm2-8b-a1b"]
C, A = conv_moe.CONV, conv_moe.ATTENTION


def test_kinds_runs_and_the_published_cuts_bytes():
    """``layer_types`` gives the mixers, ``num_dense_layers`` the leading
    dense FFNs: a run of their own, so a run's FFN is one kind; the cut
    holds 4.67 B parameters, 9.33 GB in bf16, by the builder's own
    layout."""
    cfg = lfm2_family.build(harness.published(SPEC.name))
    assert cfg.layer_types == (C, C, A, C, C, C, A, C, C, C, A, C, C, C)
    assert cfg.labels[:3] == (C + "+dense", C + "+dense", A)
    assert cfg.units == (((C + "+dense",), (0,), 2),
                         ((A, C, C, C), (0, 2, 3, 4), 3))
    assert (cfg.count(C), cfg.count(A)) == (11, 3)
    # the published 24: the same two runs, then what layer_units makes of
    # (a, c, c, c) x 4 and (a, c, c) x 2
    whole = dataclasses.replace(cfg, layer_types=tuple(
        {"conv": C, "full_attention": A}[t] for t in
        ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 4
        + ["full_attention", "conv", "conv"] * 2))
    assert [(u, n) for u, _, n in whole.units] == [
        ((C + "+dense",), 2), ((A, C, C, C), 4), ((A, C, C), 2)]
    params = jax.eval_shape(lambda k: lfm2_family.init(cfg, k, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    dense, period = params["runs"]
    # the first two layers are dense and the third is not
    assert dense["w_gu"].shape == (2, 2048, 2 * 7168) \
        and dense["w_down"].shape == (2, 7168, 2048) \
        and "router" not in dense
    assert dense["w_in"].shape == (2, 2048, 3 * 2048) \
        and dense["conv_w"].shape == (2, 3, 2048) \
        and dense["w_out"].shape == (2, 2048, 2048)
    attention = period[0]
    assert attention["wq"].shape == attention["wo"].shape == \
        (3, 32, 64, 2048) and attention["wk"].shape == (3, 8, 64, 2048)
    assert attention["q_norm"].shape == (3, 64)
    for part in period:
        assert part["w_gu"].shape == (3, 32, 2048, 2 * 1792)
        assert part["router"].shape == (3, 2048, 32) \
            and part["router_bias"].shape == (3, 32)
    assert "head" not in params and params["wte"].shape == (65536, 2048)
    axes = conv_moe.logical_axes(cfg)
    assert jax.tree_util.tree_structure(
        jax.tree_util.tree_map(lambda a: 0, params)) == \
        jax.tree_util.tree_structure(jax.tree_util.tree_map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    leaves = jax.tree_util.tree_leaves(params)
    n = sum(int(np.prod(a.shape)) for a in leaves)
    assert round(n / 1e9, 3) == 4.667
    assert round(sum(a.size * a.dtype.itemsize for a in leaves) / 1e9, 2) \
        == 9.33
    # what a slot costs: 6,144 B a token in the three attention layers, and
    # two rows a convolution layer whatever its length
    assert cfg.cache_row == (512, 512) and cfg.cache_layers == 3
    (layers, shape, dtype), = cfg.cache_state
    assert (layers, shape, dtype) == (11, (2, 2048), jnp.bfloat16)
    fam = cache_family(cfg)
    assert fam is conv_moe_inference.FAMILY
    cache = jax.eval_shape(lambda: fam.init_cache(cfg, 256, 3072))
    assert cache.k.shape == cache.v.shape == (3, 256, 3072, 512)
    assert len(cache.state) == 1 and cache.state[0].shape == \
        (11, 256, 2, 2048)
    pool = 2 * cache.k.size * 2
    assert round(pool / 1e9, 2) == 4.83
    assert round(cache.state[0].size * 2 / 1e6) == 23
    assert cache.stats.shape == (n_pair_counts(32) + 3,)


def _passes(fam, cfg, params, toks, sizes, smax=96):
    """``toks`` [1, S] admitted in passes of ``sizes`` positions, the last
    padded to its size: ``(logits of the real positions, cache)``."""
    cache = fam.init_cache(cfg, 1, smax)
    S = toks.shape[1]
    logits, at = [], 0
    for i, size in enumerate(sizes):
        real = min(size, S - at)
        chunk = jnp.pad(toks[:, at:at + real], ((0, 0), (0, size - real)))
        valid = jnp.asarray([real], jnp.int32)
        if i == 0:
            lg, cache = fam.prefill(params, chunk, cfg, cache, valid=valid)
        else:
            lg, cache = fam.extend(params, chunk, cfg, cache,
                                   lengths=jnp.asarray([at], jnp.int32),
                                   valid=valid)
        logits.append(lg[:, :real])
        at += real
    return jnp.concatenate(logits, 1), cache


def test_one_chunk_three_chunks_and_padded_chunks_leave_one_tail():
    """A prompt of 37 admitted whole, in three passes (a tail carried
    between them) and in padded passes of 16 (the last holds 5 real
    positions: its tail is taken inside it) gives the same logits and the
    same tail in every convolution layer, which is ``(s_35, s_36)`` of the
    uncached pass."""
    cfg, params = harness.loud(SPEC)
    fam = cache_family(cfg)
    toks = jax.random.randint(jax.random.PRNGKey(9), (1, 37), 0,
                              cfg.vocab_size)
    want = np.asarray(conv_moe.apply(params, toks, cfg))
    tails = []
    for sizes in ((37,), (16, 16, 5), (16, 16, 16), (8, 32)):
        logits, cache = _passes(fam, cfg, params, toks, sizes)
        np.testing.assert_allclose(np.asarray(logits), want,
                                   atol=harness.ATOL, rtol=harness.RTOL)
        tails.append(np.asarray(cache.state[0]))
    for tail in tails[1:]:
        np.testing.assert_allclose(tail, tails[0], atol=harness.ATOL,
                                   rtol=harness.RTOL)
    assert tails[0].shape == (cfg.count(C), 1, 2, cfg.d_model) \
        and all(np.abs(t).max() > 0 for t in tails[0])
    # the padded pass's tail is NOT its end's: a pass that forgets
    # ``valid`` keeps the padding's rows
    _, forgot = fam.extend(
        params, jnp.pad(toks[:, 32:], ((0, 0), (0, 11))), cfg,
        _passes(fam, cfg, params, toks[:, :32], (16, 16))[1],
        lengths=jnp.asarray([32], jnp.int32))
    assert np.abs(np.asarray(forgot.state[0]) - tails[0]).max() > 1e-3


def test_a_freed_slots_tail_comes_back_bit_for_bit_through_a_tick():
    """Three slots, the middle one freed: a tick steps the live slots' tails
    (each to ``(s_t-1, s_t)``) and neither steps nor moves the freed one's,
    and counts the rows it stepped."""
    cfg, params = harness.loud(SPEC)
    fam = cache_family(cfg)
    cache = fam.init_cache(cfg, 3, 32)
    toks = jax.random.randint(jax.random.PRNGKey(2), (3, 8), 0,
                              cfg.vocab_size)
    _, cache = fam.prefill(params, toks, cfg, cache)
    before = np.asarray(cache.state[0])
    active = jnp.asarray([True, False, True])
    lengths = jnp.full((3,), 8, jnp.int32)
    counted = np.asarray(cache.stats)
    _, after = fam.decode_step(params, toks[:, 0], cfg, cache,
                               lengths=lengths, active=active)
    now = np.asarray(after.state[0])
    np.testing.assert_array_equal(now[:, 1], before[:, 1])
    for slot in (0, 2):
        # the older row is the one that was newest; the newest is new
        np.testing.assert_array_equal(now[:, slot, 0], before[:, slot, 1])
        assert np.abs(now[:, slot, 1] - before[:, slot, 1]).max() > 0
    groups = fam.stats_groups(cfg)
    grown = (np.asarray(after.stats) - counted)[groups["state_steps"]]
    assert dict(zip(fam.state_counters, grown)) == {
        "conv_rows_stepped": 2 * cfg.count(C), "conv_tokens_real": 0,
        "conv_tokens_padded": 0}


def test_the_selection_bias_moves_a_choice_and_no_weight():
    """``expert_bias`` joins the scores for the choice alone: a bias that
    lifts an expert nobody chose makes tokens choose it, and its weight is
    its own score over the chosen scores' sum, as if it had been chosen
    without a bias."""
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((32, 8)) * 0.3, jnp.float32)
    k = 2
    plain = route(h, w, jnp.zeros((8,)), k, 1.0)
    counts = np.bincount(np.asarray(plain.experts).ravel(), minlength=8)
    rare = int(counts.argmin())
    bias = jnp.zeros((8,)).at[rare].set(0.4)
    moved = route(h, w, bias, k, 1.0)
    took = (np.asarray(moved.experts) == rare).any(1)
    assert took.sum() > (np.asarray(plain.experts) == rare).any(1).sum()
    scores = np.asarray(jax.nn.sigmoid(jnp.dot(
        h, w, precision=jax.lax.Precision.HIGHEST)))
    chosen = np.take_along_axis(scores, np.asarray(moved.experts), 1)
    np.testing.assert_allclose(
        np.asarray(moved.weights), chosen / chosen.sum(1, keepdims=True),
        rtol=1e-6)
    # the model's own gate: a non-zero drawn bias, the same rule
    cfg, params = harness.loud(SPEC)
    part = params["runs"][1][0]
    assert float(jnp.abs(part["router_bias"]).min()) > 0
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 9, cfg.d_model))
    p = {n: v[0] for n, v in part.items()}
    with_bias, _ = conv_moe.expert_ffn(x, p, cfg)
    without, _ = conv_moe.expert_ffn(
        x, {**p, "router_bias": p["router_bias"] * 0}, cfg)
    assert float(jnp.abs(with_bias - without).max()) > 0


def test_the_gates_in_common_with_the_references():
    """``lfm2_control.py --in-common`` at the tiny size: a float32 program's
    gates choose what the reference's do and weigh it alike, token for
    token, through a chunked prefill whose last pass is padded.  The two
    faults the cell's logits do not see at the published widths move this
    reading: a gate computed in bf16 chooses otherwise somewhere and its
    rounded scores weigh over the limit (the same 3e-4 to 5e-4 at any
    width), a bias added to the weights moves no choice and weighs further
    over it."""
    from benchmarks.chip.reference import lfm2_control as control
    from benchmarks.chip.reference import lfm2_reference as reference
    file = harness.tiny_file(SPEC.name)
    cfg, params = harness.model(SPEC, seed=5)
    tokens = np.random.default_rng(12).integers(
        0, cfg.vocab_size, 200).astype(np.int32)
    want = tuple(np.asarray(a)[0] for a in jax.jit(
        lambda p, t: reference.gates(file, p, t))(params, tokens[None]))
    layers = cfg.n_layer - cfg.n_dense
    assert want[0].shape == want[1].shape == \
        (layers, 200, cfg.experts_per_token)

    def read(fault):
        with control.planted(fault):
            return control.gate_readings(
                control.program_gates(cfg, params, tokens, 16), want)

    sound = read("none")
    assert sound["in_common"] == [1.0] * layers and sound["tokens"] == 200
    assert max(sound["weights_off"]) < 1e-6 and sound["agree"]
    bf16 = read("bf16_router")
    assert min(bf16["in_common"]) < 1.0 and not bf16["agree"]
    assert bf16["weights_off"][0] > control.WEIGHTS_OFF_LIMIT
    biased = read("bias_weights")
    assert biased["in_common"] == [1.0] * layers    # the choice is not moved
    assert biased["weights_off"][0] > control.WEIGHTS_OFF_LIMIT \
        and not biased["agree"]
    assert conv_moe.route is route
