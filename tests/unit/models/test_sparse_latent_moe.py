"""What the selected-latent / window-latent family alone has
(``dots3_note``; the probes every family answers are
``test_family_conformance.py``'s, on weights drawn at ``harness.LOUD``): the
file against the source's config, the published sizes, the counters of the
selection and the ring, chunks shorter and longer than the window, the slot
ops over three kinds of cached state, the sliced head, the exact top-k
against a full sort, the kernels under the interpreter, and the selected
sets against the reference's.

The tiny file: a window of 9 in a ring of 16 cells (so the bias past the
window works every tick), ``index_topk`` 24, chunks of 16, a dense first
layer and both kinds of layer.  The selection is exact, so with both sides
in float32 the same tokens are chosen: no tolerance there."""

import contextlib
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmarks.chip import dots3_family
from benchmarks.chip.reference import dots3_control as control
from benchmarks.chip.reference import dots3_reference as reference
from deepspeed_tpu.models import cache_family, latent_moe, sparse_latent_moe
from deepspeed_tpu.moe.held_experts import read_pair_counts
from deepspeed_tpu.ops.pallas import decode_attention as da
from tests.unit.chipbench.common import check_configuration
from tests.unit.models import family_harness as harness
from tests.unit.models.family_harness import ATOL, CHUNK, LOUD, RTOL

SPEC = harness.SPECS["dots3-note-prev-ep32"]
WINDOW, TOPK = 9, 24                    # the tiny file's
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


# ------------------------------------------------------- the configuration

def test_the_file_is_the_sources_but_for_what_reduced_lists():
    entry, file = harness.entry(SPEC.name), harness.published(SPEC.name)
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
    cfg = dots3_family.build(harness.published(SPEC.name))
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


# ------------------------------------------- the program against the model

def test_the_counters_follow_the_selection_and_the_ring():
    cfg, params = harness.model(SPEC, std=LOUD)
    served = harness.served(SPEC, std=LOUD)
    served.probe_logits([np.zeros((3,), np.int32)], 1)  # a tick is pulled
    before = harness.counters(served)
    n, ticks = 30, 8
    served.probe_logits(harness.prompts(cfg, (n,)), ticks)
    pairs = read_pair_counts(harness.grown(served, before, "moe_pairs"))
    padded = -(-n // CHUNK) * CHUNK
    assert pairs["routed"] == (padded + ticks * served._batcher.slots) \
        * (cfg.n_layer - 1) * cfg.experts_per_token
    grown = dict(zip(served._batcher.select_counters,
                     harness.grown(served, before, "sparse_select")))
    # single-token passes alone: the probe's row at 30..37, and the idle
    # slots the batcher ticks at their frontier of 0 (dead: no count)
    at = np.arange(n, n + ticks)
    assert grown == {
        "eligible": 2 * (at + 1).sum(),
        "selected": 2 * np.minimum(at + 1, TOPK).sum(),
        "streamed": 2 * ticks * 128,        # a slot of 128 is one block
        "ring_live": 3 * ticks * WINDOW}


@pytest.mark.slow      # two more gateways' compiles
def test_a_chunk_shorter_than_the_window_and_one_longer():
    """Chunks of 8 (under the window of 9: a query's band reaches into the
    ring and the chunk before) and of 40 (over the ring's 16 cells: only
    its last rows land, its first queries still see the ring as it was)."""
    cfg, params = harness.model(SPEC, std=LOUD)
    for chunk in (8, 40):
        (got, ref), = harness.slot_path_logits(
            SPEC, harness.gateway(cfg, params, prefill_chunk=chunk), params,
            (61,))
        np.testing.assert_allclose(got, ref, atol=ATOL, rtol=RTOL)


def test_slot_write_read_and_reset_walk_the_three_kinds_of_state():
    cfg, params = harness.model(SPEC, std=LOUD)
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


def test_the_sliced_head_gives_the_whole_heads_rows():
    """The head over a slice of the vocabulary: the whole head's rows."""
    cfg, params = harness.model(SPEC, std=LOUD)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 12, cfg.d_model))
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


# ------------------------------------- a chunk under a bias, up-projected

#: heads, rank, d_nope, d_rope, d_v of the two kinds of layer at lane-row
#: widths: a key part that is whole lane rows, and one of 192 that the
#: head-major copy lays out in 256
_UP_WIDTHS = {"full": (4, 128, 128, 64, 128), "window": (2, 128, 192, 64,
                                                         128)}
_UP_CHUNK, _UP_KEYS = 64, 768           # three key blocks of 256


@functools.lru_cache(maxsize=None)
def _up_programs(kind, ring):
    """One layer's chunk pass at ``_UP_WIDTHS[kind]`` in both forms, each
    ONE jitted program for every case of its static shape: ``(up-projected
    through the kernel, absorbed through the dense reference and W_kvb[v],
    weights)``.  The up-projected side goes the family's own way: the
    head-major copy (``latent_moe.head_major``), un-absorbed queries
    (``latent_moe.unabsorbed``) and, over a ring, ``latent_ring_attention``.
    ``bias`` None: the call with no bias at all."""
    H, R, d_nope, d_rope, d_v = _UP_WIDTHS[kind]
    dims = sparse_latent_moe.Dims(H, 32, R, d_nope, d_rope, d_v, 1e4)
    cfg = sparse_latent_moe.SparseLatentMoEConfig(dtype=jnp.float32)
    wkv_b = jax.random.normal(jax.random.PRNGKey(H), (2, R, H, d_nope + d_v),
                              jnp.float32) * 0.1
    heads = latent_moe.head_major(wkv_b, cfg, dims)
    assert heads.shape == (2, H, R, latent_moe.lane_rows(d_nope) + d_v)
    kw = dict(sm_scale=dims.softmax_scale, rank=R)

    def attend(q, rows, pos, bias, up):
        if ring:
            return da.latent_ring_attention(q, rows, bias, pos, 130, 1, up=up,
                                            **kw)
        return da.latent_cached_attention(q, rows, pos, layer=1, bias=bias,
                                          up=up, **kw)

    def up_projected(q_n, q_r, rows, pos, bias):
        return attend(latent_moe.unabsorbed(q_n, q_r), rows, pos, bias,
                      da.LatentUp(heads, 1, latent_moe.lane_rows(d_nope)))

    def absorbed(q_n, q_r, rows, pos, bias):
        q = jnp.concatenate(
            [jnp.einsum("bshe,rhe->bshr", q_n, wkv_b[1, ..., :d_nope]), q_r,
             jnp.zeros(q_n.shape[:3] + (dims.lanes - R - d_rope,))], -1)
        with _dense():
            weighed = attend(q, rows, pos, bias, None)
        return jnp.einsum("bshr,rhe->bshe", weighed, wkv_b[1, ..., d_nope:])

    return jax.jit(up_projected), jax.jit(absorbed), dims


@contextlib.contextmanager
def _dense():
    """The dense formulas in the kernels' place, while a program traces."""
    was, da.use_pallas = da.use_pallas, lambda: False
    try:
        yield
    finally:
        da.use_pallas = was


@pytest.mark.parametrize("case", [
    "full-selection", "full-everything", "full-alone", "full-ragged",
    "full-no-bias", "window-selection", "window-band-unlapped",
    "window-band-lapped"])
def test_a_chunk_under_a_bias_in_the_up_projected_form(interpreted,
                                                        monkeypatch, case):
    """The up-projected chunk kernel under a bias, the interpreter's run of
    it against the absorbed form's dense formula: a random selection, a
    selection that is everything (the bias all zeros: bit for bit the call
    with NO bias, which is the parent's kernel), one that is the query
    alone, ragged positions that end inside a key block, and a ring's band
    before and after its first lap, through the head-major copy that pads a
    192-wide key part to 256 lanes."""
    # the form for a chunk this short (the rule's own test:
    # ``test_latent_moe.py::test_the_calls_shape_picks_the_form``)
    monkeypatch.setattr(da, "latent_up_projects", lambda *a: True)
    kind, what = case.split("-", 1)
    ring = what.startswith("band")
    up_projected, absorbed, dims = _up_programs(kind, ring)
    B, C, S = 2, _UP_CHUNK, _UP_KEYS
    key = iter(jax.random.split(jax.random.PRNGKey(len(case)), 8))
    normal = lambda *shape: jax.random.normal(next(key), shape, jnp.float32)
    q_n, q_r = normal(B, C, dims.n_head, dims.d_nope), \
        normal(B, C, dims.n_head, dims.d_rope)
    if ring:
        # a ring as it stood before the chunk, the chunk's rows in the
        # bias's place (``_up_programs``), a window of 130: 320 cells not yet
        # lapped (with the chunk's rows three key blocks of 128), 576 lapped
        # (640 rows: padded to two blocks of 512)
        lapped = what == "band-lapped"
        at = jnp.asarray([600, 607] if lapped else [100, 107])
        rows = normal(2, B, 576 if lapped else 320, dims.lanes)
        bias = normal(B, C, dims.lanes)
    else:
        at = jnp.asarray([300, 263] if what == "ragged" else [256, 448])
        rows = normal(2, B, S, dims.lanes)
        q_pos = at[:, None] + jnp.arange(C)[None]
        own = jnp.arange(S)[None, None] == q_pos[..., None]
        chosen = {"selection": own | (normal(B, C, S) > 0.5),
                  "ragged": own | (normal(B, C, S) > 0.5),
                  "everything": jnp.ones((B, C, S), bool),
                  "alone": own, "no-bias": None}[what]
        bias = None if chosen is None else jnp.where(chosen, 0.0, -jnp.inf)
    got = up_projected(q_n, q_r, rows, at, bias)
    want = absorbed(q_n, q_r, rows, at, bias)
    if what in ("selection", "band-unlapped", "band-lapped"):   # the kernel
        from tests.unit.ops.traced_sweeps import _deep
        assert [e.params["name"] for e in _deep(jax.make_jaxpr(up_projected)(
            q_n, q_r, rows, at, bias).jaxpr)
            if e.primitive.name == "pallas_call"] == [da.LATENT_UP_CHUNK]
    assert got.shape == (B, C, dims.n_head, dims.d_v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=1e-4)
    if what == "everything":
        np.testing.assert_array_equal(
            np.asarray(got), np.asarray(up_projected(q_n, q_r, rows, at,
                                                     None)))


def test_a_served_chunk_takes_the_form_its_shape_picks(monkeypatch):
    """The family's own route to the up-projected form (``step``'s
    head-major copies, ``attention_project``'s un-absorbed queries and
    ``LatentUp``, the attend hooks, ``attention_output`` without
    ``W_kvb[v]``) gives the absorbed route's logits: a prompt pass and two
    further chunks through all three kinds of cached state with the rule
    saying yes to every chunk, against the same passes with the rule as it
    is (at the tiny widths: absorbed).  ``chunk_form`` says one name a
    pass: ``up_projected`` only where both kinds of layer are."""
    cfg, params = harness.model(SPEC, std=LOUD)
    fam = cache_family(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (1, 3 * CHUNK), 0,
                                cfg.vocab_size)

    def passes(params, tokens):
        logits, cache = fam.prefill(params, tokens[:, :CHUNK], cfg,
                                    fam.init_cache(cfg, 1, 128))
        out = [logits]
        for i in (1, 2):
            logits, cache = fam.extend(
                params, tokens[:, i * CHUNK:(i + 1) * CHUNK], cfg, cache)
            out.append(logits)
        return jnp.concatenate(out, 1)

    assert fam.chunk_form(cfg, CHUNK) == "absorbed"
    # (a new function a form: one jitted twice is traced once)
    want = jax.jit(lambda p, t: passes(p, t))(params, tokens)
    monkeypatch.setattr(
        da, "latent_up_projects",
        lambda Sq, H, *a: Sq > 1 and H == cfg.w_n_head)
    assert fam.chunk_form(cfg, CHUNK) == "absorbed"     # window layers alone
    monkeypatch.setattr(da, "latent_up_projects", lambda Sq, *a: Sq > 1)
    assert fam.chunk_form(cfg, CHUNK) == "up_projected"
    assert fam.chunk_form(cfg, 1) == "absorbed"
    asked = []
    up = latent_moe.up_projection
    monkeypatch.setattr(latent_moe, "up_projection", lambda *a: (
        asked.append(up(*a) is not None), up(*a))[1])
    got = jax.jit(lambda p, t: passes(p, t))(params, tokens)
    assert asked and all(asked)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=ATOL,
                               rtol=RTOL)


# ------------------------------------------------------------- the controls

def _in_common(cfg, params, full, fault="none"):
    with control.planted(fault):
        got = control.program_selections(cfg, params, full, CHUNK, 128)
    want = np.asarray(jax.jit(lambda p, t: reference.selections(
        harness.tiny_file(SPEC.name), p, t))(params, full[None]))
    return control.shares(got[:, :, :len(full)], want, cfg.index_topk)


def test_the_program_and_the_reference_choose_the_same_sets():
    """The check on the sets themselves, for one prompt deep in selection
    whose decode laps the ring: in float32 the program's selection through
    its cache (every chunk's ``topk_bias`` recorded) IS the reference's full
    sort, query for query; with the selection replaced by the latest tokens
    the share in common falls far under the limit."""
    cfg, params = harness.model(SPEC, std=LOUD)
    prompt, = harness.prompts(cfg, (45,))
    replies, _ = harness.served(SPEC, std=LOUD).probe_logits([prompt], 8)
    full = np.concatenate([prompt, np.asarray(replies[0], np.int32)])
    same = _in_common(cfg, params, full)
    assert same["queries"] == 2 * (len(full) - TOPK)
    assert same["mean"] == same["least"] == 1.0
    wrong = _in_common(cfg, params, full, "latest")
    assert wrong["mean"] < control.IN_COMMON_LIMIT - 0.2
