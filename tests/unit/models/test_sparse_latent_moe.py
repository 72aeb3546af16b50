"""The selected-latent / window-latent family (``dots3_note``) against its
plain reference at a tiny size on the CPU, in float32: the uncached ``apply``
and the slot path (chunked prefill through banks, index keys and rings, then
decode) against the reference's full forward with the selection by a full
sort and the window as a mask, on logits; the exact top-k; the kernels under
the interpreter; the slot ops over three kinds of cached state; the share
against the uncut model; the counters; and planted faults, each of which
must read over a tolerance.

The tiny file: a window of 9 in a ring of 16 cells (so the bias past the
window works every tick), ``index_topk`` 24, chunks of 16, a dense first
layer and both kinds of layer.  The module builds its configuration, its
weights and ONE stopped gateway once (fixtures of module scope): a case is a
``probe_logits`` on programs already compiled.

The tolerances.  ``ATOL`` / ``RTOL`` (2e-5, 1e-4) are the other expert
families': both sides compute in float32, the program's products run at the
CPU's default precision and its softmax is blocked another way, which reads
1e-7 to 4e-6 here on logits of about 0.7; a fault below reads 1e-3 or more
(on weights drawn ten times louder than the family's, ``LOUD``, so that the
layers and not the embedding make the logits).  The selection is exact, so
with both sides in float32 the same tokens are chosen: no tolerance there."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from benchmarks.chip import dots3_family
from benchmarks.chip.reference import dots3_control as control
from benchmarks.chip.reference import dots3_reference as reference
from deepspeed_tpu.models import (cache_family, sparse_latent_moe,
                                  sparse_latent_moe_inference)
from deepspeed_tpu.ops.pallas import decode_attention as da
from tests.unit.chipbench.common import check_configuration

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
NAME = "dots3-note-prev-ep32"
CHUNK, WINDOW, TOPK = 16, 9, 24         # the tiny file's
ATOL, RTOL = 2e-5, 1e-4
LOUD = 0.2
#: the numbers of the source's config.json (the catalog's row, whose
#: ``source_url`` the file's ``source`` is)
SOURCE = {
    "first_k_dense_replace": 1, "hidden_size": 5120, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
    "kv_lora_rank": 512, "max_position_embeddings": 524288,
    "moe_intermediate_size": 1536, "moe_layer_freq": 1,
    "n_routed_experts": 256, "n_shared_experts": 1,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 46, "num_key_value_heads": 128, "q_lora_rank": 1024,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_theta": 80000000, "routed_scaling_factor": 1,
    "sliding_window_size": 513, "swa_kv_lora_rank": 1024,
    "swa_num_attention_heads": 64, "swa_num_key_value_heads": 64,
    "swa_q_lora_rank": 1024, "swa_qk_nope_head_dim": 192,
    "swa_qk_rope_head_dim": 64, "swa_rope_theta": 50000,
    "swa_v_head_dim": 128, "v_head_dim": 128, "vocab_size": 152064}


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


def _gateway(cfg, params, **serving):
    engine = deepspeed_tpu.init_inference(model=(cfg, params),
                                          config={"dtype": "float32"})
    gateway = engine.serve(config={"slots": 4, "max_len": 128,
                                   "prefill_chunk": CHUNK,
                                   "queue_capacity": 8, **serving})
    gateway.shutdown(drain=False, timeout=60)
    return gateway


@pytest.fixture(scope="module")
def tiny():
    """``(file, config, loud weights)``, float32."""
    file = _file()
    cfg = dataclasses.replace(dots3_family.build(file), dtype=jnp.float32)
    return file, cfg, sparse_latent_moe.init(cfg, jax.random.PRNGKey(0),
                                             std=LOUD)


@pytest.fixture(scope="module")
def served(tiny):
    """One stopped gateway over the tiny model: every case probes it."""
    _, cfg, params = tiny
    return _gateway(cfg, params)


def _probe(gateway, file, cfg, params, n, ticks=8):
    """Logits of the slot path and of the reference for one seeded prompt
    of ``n`` tokens and ``ticks`` greedy ticks."""
    rng = np.random.default_rng(3 + n)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)]
    replies, got = gateway.probe_logits(prompts, ticks)
    full = np.concatenate([prompts[0], np.asarray(replies[0], np.int32)])
    ref = np.asarray(reference.forward(file, params, full[None],
                                       ticks + 1))[0]
    return got[0][:, :cfg.vocab_size], ref


# ------------------------------------------------------- the configuration

def test_the_file_is_the_sources_but_for_what_reduced_lists():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == NAME)
    file = _published()
    check_configuration(file, entry, SOURCE)
    assert entry["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                "vocab_size"]
    assert file["published"] == {k: SOURCE[k] for k in entry["reduced"]}
    # the first nine of the published list: two whole periods after the
    # dense layer
    assert file["layer_types"] == ["full_attention"] * 2 + (
        ["sliding_attention"] * 3 + ["full_attention"]) + \
        ["sliding_attention"] * 3
    assert 8 * file["vocab_size"] == SOURCE["vocab_size"]


def test_the_published_sizes():
    cfg = dots3_family.build(_published())
    assert (cfg.n_layer, cfg.count("full"), cfg.count("window")) == (9, 3, 6)
    assert cfg.dims("full") == (128, 1024, 512, 128, 64, 128, 8e7)
    assert cfg.dims("window") == (64, 1024, 1024, 192, 64, 128, 5e4)
    # three kinds of cached state: a 576-element latent row in 640 lanes
    # beside a 128-element index key, and a ring of 1,088 in 1,152 lanes
    assert cfg.cache_row == (640, 128) and cfg.cache_sweep_row == (640,)
    assert cfg.cache_ring == (6, 640) and cfg.cache_ring_row == (1152,)
    assert cfg.padded_vocab == 19072 and cfg.vocab_size == 19008
    assert [(unit, n) for unit, _, n in cfg.runs] == [
        (("full+dense",), 1), (("full", "window", "window", "window"), 2)]
    assert cfg.rescale(1024) == pytest.approx(5 ** 0.5) \
        and cfg.rescale(512) == pytest.approx(10 ** 0.5)
    plan = cache_family(cfg).sweep_plan(cfg, 16384)
    assert (plan.kernel, plan.block_k, plan.ring.block_k, plan.ring.Smax) \
        == (da.LATENT_SWEEP, 512, 128, 640)


@pytest.mark.parametrize("key,value,said", [
    ("n_group", 2, "group-limited"), ("rope_scaling", {"factor": 4},
                                      "rope_scaling"),
    ("tie_word_embeddings", True, "head"), ("attention_bias", True, "biases"),
    ("moe_layer_freq", 2, "moe_layer_freq"),
    ("attention_gate_type", "elementwise", "gate")])
def test_a_sibling_configuration_is_refused_by_name(key, value, said):
    with pytest.raises(AssertionError, match=said):
        dots3_family.build({**_published(), key: value})


def test_what_the_family_does_not_serve_is_refused_by_name(tiny):
    _, cfg, params = tiny
    fam = cache_family(cfg)
    assert fam is sparse_latent_moe_inference.FAMILY
    assert set(fam.unsupported) == {"paging", "prefix", "speculative",
                                    "int8", "draft"}
    engine = deepspeed_tpu.init_inference(model=(cfg, params),
                                          config={"dtype": "float32"})
    with pytest.raises(NotImplementedError, match="ring"):
        engine.serve(config={"slots": 2, "max_len": 64, "prefill_chunk": 16,
                             "paging": {"enabled": True}})
    with pytest.raises(NotImplementedError, match="compute dtype only"):
        fam.init_cache(cfg, 1, 64, kv_dtype="int8")


# ------------------------------------------- the program against the model

@pytest.mark.slow      # the slot path below holds the same mathematics
def test_apply_equals_the_reference(tiny):
    file, cfg, params = tiny
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 50), 0,
                                cfg.vocab_size)
    got = sparse_latent_moe.apply(params, tokens, cfg)[..., :cfg.vocab_size]
    ref = reference.forward(file, params, tokens, 50)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=ATOL,
                               rtol=RTOL)


@pytest.mark.parametrize("n", [
    4, WINDOW + 3, TOPK + 9,
    # two more of the last kind: tier 1 keeps the three that differ
    pytest.param(TOPK - 4, marks=pytest.mark.slow),
    pytest.param(70, marks=pytest.mark.slow)],
    ids=["under-the-window", "ring-laps-in-decode", "past-topk",
         "selection-starts-in-decode", "deep"])
def test_slot_path_equals_the_reference(tiny, served, n):
    """Chunked prefill, then 8 decode ticks through the gateway's own
    programs and all three kinds of cached state, against the reference's
    full forward, on logits: a prompt under the window (its ring never
    laps), one whose decode laps the ring of 16, one whose decode crosses
    ``index_topk`` (the first queries that leave a token out), one past it
    (every chunk after the second selects) and one deep in selection whose
    last chunk is padded."""
    file, cfg, params = tiny
    got, ref = _probe(served, file, cfg, params, n)
    np.testing.assert_allclose(got, ref, atol=ATOL, rtol=RTOL)


def test_the_counters_follow_the_selection_and_the_ring(tiny, served):
    file, cfg, params = tiny
    served.probe_logits([np.zeros((3,), np.int32)], 1)  # a tick is pulled
    before = {k: np.array(served._batcher.counts(k))
              for k in ("moe_pairs", "sparse_select")}
    n, ticks = 30, 8
    _probe(served, file, cfg, params, n, ticks)
    pairs = served._batcher.counts("moe_pairs") - before["moe_pairs"]
    assert pairs[0] == pairs[3:-2].sum() > 0 == pairs[-2] == pairs[-1] \
        and len(pairs) == 5 + len(cfg.held)
    padded = -(-n // CHUNK) * CHUNK
    assert pairs[1] == (padded + ticks * 4) * (cfg.n_layer - 1) \
        * cfg.experts_per_token
    grown = dict(zip(served._batcher.select_counters,
                     served._batcher.counts("sparse_select")
                     - before["sparse_select"]))
    # single-token passes alone: the probe's row at 30..37, and the three
    # idle slots the batcher ticks at their frontier of 0 (dead: no count)
    at = np.arange(n, n + ticks)
    assert grown == {
        "eligible": 2 * (at + 1).sum(),
        "selected": 2 * np.minimum(at + 1, TOPK).sum(),
        "streamed": 2 * ticks * 128,        # a slot of 128 is one block
        "ring_live": 3 * ticks * WINDOW}


@pytest.mark.slow      # two more gateways' compiles
def test_a_chunk_shorter_than_the_window_and_one_longer(tiny):
    """Chunks of 8 (under the window of 9: a query's band reaches into the
    ring and the chunk before) and of 40 (over the ring's 16 cells: only
    its last rows land, its first queries still see the ring as it was)."""
    file, cfg, params = tiny
    for chunk in (8, 40):
        gateway = _gateway(cfg, params, prefill_chunk=chunk)
        got, ref = _probe(gateway, file, cfg, params, 61)
        np.testing.assert_allclose(got, ref, atol=ATOL, rtol=RTOL)


def test_slot_write_read_and_reset_walk_the_three_kinds_of_state(tiny):
    _, cfg, params = tiny
    fam = cache_family(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (1, 40), 0,
                                cfg.vocab_size)
    pool = fam.init_cache(cfg, 3, 64)
    assert pool.k.shape == (2, 3, 64, 128) and pool.v.shape == (2, 3, 64, 16)
    assert [r.shape for r in pool.ring] == [(3, 3, 16, 128)]
    _, row = fam.prefill(params, tokens, cfg, fam.init_cache(cfg, 1, 64))
    pool = fam.write_slot(pool, 1, row)
    back = fam.read_slot(pool, 1, 40)
    for a, b in zip(jax.tree_util.tree_leaves((row.k, row.v, row.ring)),
                    jax.tree_util.tree_leaves((back.k, back.v, back.ring))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(jnp.abs(pool.ring[0][:, 1]).sum()) > 0
    blank = fam.reset_slot(pool, 1)
    for leaf in (blank.k, blank.v) + blank.ring:
        assert float(jnp.abs(leaf).sum()) == 0


# --------------------------------------------------------------- the share

def test_the_shares_add_up_to_the_uncut_layer_and_the_head_to_its_rows(tiny):
    """The share is tied to the model: the routed parts of 4 shares of 4
    experts plus the shared expert counted once equal the uncut reference's
    expert layer (16 experts), and the sliced head's logits are the whole
    head's rows."""
    from benchmarks.chip.reference.latent_moe_reference import _expert_layer
    file, cfg, _ = tiny
    whole = dataclasses.replace(cfg, held_experts=None)
    params = sparse_latent_moe.init(whole, jax.random.PRNGKey(7), std=LOUD)
    p = jax.tree_util.tree_map(lambda a: a[0], params["runs"][1])
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 12, cfg.d_model))
    uncut = _expert_layer({**file, "n_routed_experts": 16}, x[0], p,
                          tuple(range(16)))
    zero_shared = {**p, "ws_down": p["ws_down"] * 0}
    total = x[0]
    for share in range(4):
        ids = tuple(range(4 * share, 4 * share + 4))
        c = dataclasses.replace(cfg, held_experts=ids)
        part = {**(p if share == 0 else zero_shared),
                "w_gu": p["w_gu"][jnp.asarray(ids)],
                "w_down": p["w_down"][jnp.asarray(ids)]}
        total = total + sparse_latent_moe.ffn(x, part, c, "full")[0][0] - x[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=ATOL, rtol=RTOL)
    # the head over a slice of the vocabulary: the whole head's rows
    rows = slice(128, 256)
    sliced = {**params, "head": params["head"][rows]}
    np.testing.assert_allclose(
        np.asarray(sparse_latent_moe.lm_logits(sliced, x, cfg)),
        np.asarray(sparse_latent_moe.lm_logits(params, x, cfg)[..., rows]),
        atol=ATOL, rtol=RTOL)


# ----------------------------------------------------------- the selection

@pytest.mark.parametrize("case", ["plain", "ties", "zeros", "negative"])
def test_topk_bias_is_the_full_sorts_selection(case):
    """Exactly the ``min(t + 1, k)`` largest among the keys at or before
    the query, ties to the lower position: against a stable full sort, on
    scores with ties on the threshold, signed zeros and all-negative
    rows."""
    rng = np.random.default_rng(0)
    S, k = 96, 10
    s = rng.standard_normal((2, 40, S)).astype(np.float32)
    if case == "ties":
        s = np.round(s * 2) / 2
    elif case == "zeros":
        s = np.where(rng.random(s.shape) < 0.7, 0.0, s) \
            * np.where(rng.random(s.shape) < 0.5, -1.0, 1.0)
    elif case == "negative":
        s = -np.abs(s) - 1
    q_pos = 30 + np.arange(40)[None]
    got = np.asarray(da.topk_bias(jnp.asarray(s), q_pos, k)) == 0
    masked = np.where(np.arange(S) <= q_pos[..., None], s, -np.inf)
    order = np.argsort(-masked, axis=-1, kind="stable")
    rank = np.argsort(order, axis=-1, kind="stable")
    want = rank < np.minimum(q_pos + 1, k)[..., None]
    np.testing.assert_array_equal(got, want)
    assert (got.sum(-1) == np.minimum(q_pos + 1, k)).all()


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("pos", [100, 300, 600],
                         ids=["under-the-window", "between", "past-topk"])
def test_the_kernels_under_the_interpreter(interpreted, pos):
    """One full and one window layer's passes at ``pos``, a chunk of 64
    and then a single token, kernels under the interpreter against the dense
    formulas: the index's two score kernels, the latent sweep and the latent
    chunk kernel under a selection's bias and under a ring's (a window of
    130 in a ring of 256 that has lapped at 300 and 600 and not at 100;
    ``index_topk`` 384: contexts under the window, between it and the top-k,
    and past it)."""
    B, S, Hi, Di, H, W, R, window, topk = 2, 768, 8, 128, 8, 256, 256, 130, \
        384
    C = 64
    key = iter(jax.random.split(jax.random.PRNGKey(pos), 12))
    normal = lambda *shape: jax.random.normal(next(key), shape, jnp.float32)
    keys, bank = normal(1, B, S, Di), normal(1, B, S, W)
    for Sq in (C, 1):
        q_i, w = normal(B, Sq, Hi, Di), normal(B, Sq, Hi)
        q = normal(B, Sq, H, W) * 0.1
        at = jnp.asarray([pos, pos + 7])
        dense = jnp.sum(jnp.maximum(jnp.einsum(
            "bqhd,bkd->bqhk", q_i, keys[0]), 0) * w[..., None], 2)
        scores = da.index_scores(q_i, w, keys, pos=at, layer=0)
        q_pos = at[:, None] + jnp.arange(Sq)[None]
        seen = np.arange(S) <= np.asarray(q_pos)[..., None]
        np.testing.assert_allclose(np.where(seen, scores, 0),
                                   np.where(seen, dense, 0), atol=2e-4,
                                   rtol=1e-4)
        bias = da.topk_bias(scores, q_pos, topk)
        np.testing.assert_array_equal(np.asarray(bias),
                                      np.asarray(da.topk_bias(
                                          jnp.where(seen, dense, 0), q_pos,
                                          topk)))
        got = da.latent_cached_attention(q, bank, at, 0.1, 128, layer=0,
                                         bias=bias)
        want = da.latent_attention_reference(q, bank[0], at, 0.1, 128, bias)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=1e-4)
    # the ring: cells p mod R hold the rows of the last R positions
    rows = normal(B, S, W)
    ring = jnp.stack([jnp.zeros((R, W)).at[jnp.arange(max(p - R + 1, 0),
                                                      p + 1) % R].set(
        rows[b, max(p - R + 1, 0):p + 1]) for b, p in enumerate(
            np.asarray(at))])[None]
    q = normal(B, 1, H, W) * 0.1
    got = da.latent_cached_attention(
        q, ring, jnp.minimum(at, R - 1), 0.1, 128, layer=0,
        bias=da.ring_bias(at, R, window))
    band = (np.arange(S)[None] <= np.asarray(at)[:, None]) \
        & (np.arange(S)[None] > np.asarray(at)[:, None] - window)
    want = da.latent_attention_reference(
        q, rows, at, 0.1, 128, jnp.where(band, 0.0, -jnp.inf)[:, None])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-4)
    # a chunk beside the ring as it stood before it
    fresh, q = normal(B, C, W), normal(B, C, H, W) * 0.1
    before = jnp.stack([jnp.zeros((R, W)).at[jnp.arange(max(p - R, 0), p)
                                             % R].set(rows[b, max(p - R, 0):p])
                        for b, p in enumerate(np.asarray(at))])[None]
    got = da.latent_ring_attention(q, before, fresh, at, window, 0, 0.1, 128)
    whole = jnp.stack([rows[b].at[p:p + C].set(fresh[b])
                       for b, p in enumerate(np.asarray(at))])
    q_pos = np.asarray(at)[:, None] + np.arange(C)[None]
    band = (np.arange(S)[None, None] <= q_pos[..., None]) \
        & (np.arange(S)[None, None] > q_pos[..., None] - window)
    want = da.latent_attention_reference(
        q, whole, at, 0.1, 128, jnp.where(band, 0.0, -jnp.inf))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-4)


# ------------------------------------------------------------- the controls

@pytest.fixture(scope="module")
def sound(tiny, served):
    """The sound run's logits and the reference's, for one prompt deep in
    selection whose decode laps the ring."""
    file, cfg, params = tiny
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, 45).astype(np.int32)
    replies, got = served.probe_logits([prompt], 8)
    full = np.concatenate([prompt, np.asarray(replies[0], np.int32)])
    ref = np.asarray(reference.forward(file, params, full[None], 9))[0]
    np.testing.assert_allclose(got[0][:, :cfg.vocab_size], ref, atol=ATOL,
                               rtol=RTOL)
    return prompt, full, ref


@pytest.mark.parametrize("fault", ["latest", "ring_other"] + [
    # tier 1 holds one fault of the selection and one of the ring
    pytest.param(f, marks=pytest.mark.slow)
    for f in ("no_relu", "no_w", "no_gate", "window_512", "window_514",
              "zero")])
def test_a_planted_fault_reads_over_the_tolerance(tiny, sound, fault):
    """The controls of ``reference/dots3_control.py`` that plant a fault in
    the mathematics, those the chip's check sees and those it does not
    (``UNSEEN``), at the tiny size on loud weights: the selection replaced
    by the latest tokens, the index's ``relu`` or weights left out, the gate
    left out, the window off by one either way, a ring read from another
    layer, the routed product left out.  (The two of precision, ``int8`` and
    ``bf16_scores``, are the chip's to read: at 53 tokens no two scores lie
    within a part in 256 of the 24th.)"""
    assert fault in control.FAULTS + control.UNSEEN
    _, cfg, params = tiny
    prompt, _, ref = sound
    weights = control.WEIGHTS.get(fault, lambda p: p)(params)
    with control.planted(fault):
        _, got = _gateway(cfg, weights).probe_logits([prompt], 8)
    worst = np.abs(got[0][:, :cfg.vocab_size] - ref).max()
    assert worst > 1e-3, (fault, worst)


def _in_common(tiny, full, fault="none"):
    file, cfg, params = tiny
    with control.planted(fault):
        got = control.program_selections(cfg, params, full, CHUNK, 128)
    want = np.asarray(reference.selections(file, params, full[None]))
    return control.shares(got[:, :, :len(full)], want, cfg.index_topk)


def test_the_program_and_the_reference_choose_the_same_sets(tiny, sound):
    """The check on the sets themselves: in float32 the program's selection
    through its cache (every chunk's ``topk_bias`` recorded) IS the
    reference's full sort, query for query; with the selection replaced by
    the latest tokens the share in common falls far under the limit."""
    _, full, _ = sound
    same = _in_common(tiny, full)
    assert same["queries"] == 2 * (len(full) - TOPK)
    assert same["mean"] == same["least"] == 1.0
    wrong = _in_common(tiny, full, "latest")
    assert wrong["mean"] < control.IN_COMMON_LIMIT - 0.2
