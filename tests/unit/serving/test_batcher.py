"""Slot ops + SlotBatcher: per-row admission into a live cache, batched
ragged decode ticks, and the no-recompile contract."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt, gpt_inference, gpt_moe, \
    gpt_moe_inference
from deepspeed_tpu.serving import ServingConfig, SlotBatcher

CFG = gpt.GPTConfig(vocab_size=256, max_seq_len=128, n_layer=2, n_head=4,
                    d_model=64, dtype=jnp.float32, vocab_round_to=128)


def _engine(**kw):
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    cfg = {"dtype": "float32"}
    cfg.update(kw)
    return deepspeed_tpu.init_inference(model=(CFG, params), config=cfg)


# ------------------------------------------------------------- slot ops

MOE_CFG = gpt_moe.GPTMoEConfig(vocab_size=128, max_seq_len=64, n_layer=2,
                               n_head=2, d_model=32, dtype=jnp.float32,
                               vocab_round_to=128, num_experts=2)


FAMILY_NAMES = ("init_cache", "prefill", "extend", "decode_step",
                "write_slot", "read_slot", "reset_slot")


def test_cache_family_picks_by_config():
    """One helper maps a config to its family, a ``gpt_inference.Family``;
    both families expose the seven names the engine, batcher, pager and
    fleet call, defined once on the class."""
    from deepspeed_tpu.models import cache_family
    assert cache_family(CFG) is gpt_inference.DENSE
    assert cache_family(MOE_CFG) is gpt_moe_inference.FAMILY
    for fam in (gpt_inference.DENSE, gpt_moe_inference.FAMILY):
        assert isinstance(fam, gpt_inference.Family)
        assert all(callable(getattr(fam, n)) for n in FAMILY_NAMES), fam
    for name in FAMILY_NAMES:
        assert name not in vars(gpt_moe_inference), name
    eng = _engine()
    assert eng._family is gpt_inference.DENSE


#: ``(family, n, cut) -> harness.passes_both_ways``: one program for both
_BOTH_WAYS = {}


@pytest.mark.parametrize("which", ["prefill", "extend", "prefill-in-chunks"])
@pytest.mark.parametrize("family", ["dense", "moe"])
def test_a_pass_asked_for_no_head_returns_what_the_head_takes(family, which):
    """``head=False`` for the two families of ``gpt_inference`` itself (the
    expert families': ``test_family_conformance.py``): the stream of which
    the family's ``logits`` are the default's logits, and the same cache;
    through GPT-MoE's own prompt pass too, whose walk over a prompt longer
    than its gate's bound concatenates the chunks' streams."""
    from tests.unit.models import family_harness as harness
    fam, mod, cfg = {"dense": (gpt_inference.DENSE, gpt, CFG),
                     "moe": (gpt_moe_inference.FAMILY, gpt_moe, MOE_CFG)}[
                         family]
    n, cut = (21, 8)
    if which == "prefill-in-chunks":
        cfg = dataclasses.replace(cfg, max_seq_len=192)
        n, cut, which = 150, 140, "prefill"
        assert cut > gpt_moe_inference._PREFILL_CHUNK
    if (family, n, cut) not in _BOTH_WAYS:
        _BOTH_WAYS[family, n, cut] = harness.passes_both_ways(
            fam, cfg, mod.init(cfg, jax.random.PRNGKey(0)), n=n, cut=cut,
            max_len=cfg.max_seq_len)
    harness.check_a_pass_without_its_head(
        cfg, (1, cut if which == "prefill" else n - cut),
        _BOTH_WAYS[family, n, cut][which])


@pytest.mark.parametrize("fam, mod, cfg", [
    pytest.param(gpt_inference.DENSE, gpt, CFG, id="dense"),
    pytest.param(gpt_moe_inference.FAMILY, gpt_moe, MOE_CFG, id="moe")])
def test_write_read_reset_slot(fam, mod, cfg):
    """write_slot inserts a batch-1 cache at one row and ONLY that row;
    read_slot round-trips it; reset_slot zeroes it.  One set of slot ops
    serves both families: the MoE pool is the dense pool, ``n_layer``
    deep."""
    params = mod.init(cfg, jax.random.PRNGKey(0))
    big = fam.init_cache(cfg, 3, 32)
    assert big.k.shape == (cfg.n_layer, 3, 32, cfg.n_head * cfg.head_dim)
    t = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    _, small = fam.prefill(params=params, tokens=t, config=cfg,
                           cache=fam.init_cache(cfg, 1, 32))
    # every layer of the stack (dense and MoE sublayers alike) was filled
    assert np.asarray(small.k[:, 0, :8]).any(axis=(1, 2)).all()
    big2 = fam.write_slot(big, jnp.asarray(1), small)
    for bank in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(getattr(big2, bank)[:, 1]),
            np.asarray(getattr(small, bank)[:, 0]), err_msg=bank)
        # other rows untouched (still zero)
        assert not np.asarray(getattr(big2, bank)[:, 0]).any()
        assert not np.asarray(getattr(big2, bank)[:, 2]).any()
    back = fam.read_slot(big2, jnp.asarray(1), length=8)
    np.testing.assert_array_equal(np.asarray(back.k), np.asarray(small.k))
    assert int(back.length) == 8 and back.batch == 1
    wiped = fam.reset_slot(big2, jnp.asarray(1))
    assert not np.asarray(wiped.k[:, 1]).any()
    assert not np.asarray(wiped.v[:, 1]).any()
    # geometry violations are loud
    with pytest.raises(ValueError, match="max_len"):
        fam.write_slot(fam.init_cache(cfg, 3, 16), 0, small)
    with pytest.raises(ValueError, match="int8"):
        fam.write_slot(fam.init_cache(cfg, 3, 32, kv_dtype="int8"), 0, small)


def test_write_slot_int8_scales():
    params = gpt.init(CFG, jax.random.PRNGKey(0))
    big = gpt_inference.init_cache(CFG, 2, 32, kv_dtype="int8")
    t = jax.random.randint(jax.random.PRNGKey(2), (1, 6), 0, 256)
    _, small = gpt_inference.prefill(
        params, t, CFG, gpt_inference.init_cache(CFG, 1, 32,
                                                 kv_dtype="int8"))
    big2 = gpt_inference.write_slot(big, jnp.asarray(0), small)
    np.testing.assert_array_equal(np.asarray(big2.k_scale[:, 0]),
                                  np.asarray(small.k_scale[:, 0]))
    assert gpt_inference.read_slot(big2, jnp.asarray(0)).int8


_CHUNK_WRITES = {
    name: jax.jit(getattr(gpt_inference, name))
    for name in ("_chunk_slice", "_chunk_scatter")}


@pytest.mark.parametrize("pos", [0, 7, 16, 24, 25, 31, 39, 40, 47, 1000])
@pytest.mark.parametrize("dtype,width,chunk", [
    (jnp.bfloat16, 64, 16), (jnp.int8, 64, 16), (jnp.float32, 4, 16),
    (jnp.bfloat16, 64, 5), (jnp.bfloat16, 64, 40)],
    ids=["bf16", "int8", "scales", "verify-window", "whole-row"])
def test_one_rows_chunk_by_update_slice_is_the_scatter(dtype, width, chunk,
                                                       pos):
    """``extend``'s batch-1 ragged write, one update slice a bank a layer
    (``_chunk_slice``), leaves the bank as the scatter of its ``C`` rows
    does (``_chunk_scatter``: ``B > 1``'s, and the parent's at every ``B``),
    bit for bit, at every frontier: inside the row of 40 cells, ending on
    its last cell, over its end (the rows past it fall off; a clamped slice
    would have landed on the cells before the frontier), and past it."""
    rng = np.random.default_rng(pos + chunk)
    bank = jnp.asarray(rng.integers(-100, 100, (3, 1, 40, width)), dtype)
    val = jnp.asarray(rng.integers(-100, 100, (1, chunk, width)), dtype)
    at = jnp.asarray([pos], jnp.int32)
    got, want = (np.asarray(f(bank, jnp.int32(1), val, at), np.float32)
                 for f in (_CHUNK_WRITES["_chunk_slice"],
                           _CHUNK_WRITES["_chunk_scatter"]))
    np.testing.assert_array_equal(got, want)
    landed = max(0, min(pos + chunk, 40) - pos)
    changed = (got != np.asarray(bank, np.float32)).any(-1)
    assert not changed[[0, 2]].any() and not changed[1, 0, :pos].any()
    assert changed[1, 0].sum() <= landed
    np.testing.assert_array_equal(got[1, 0, pos:pos + landed],
                                  np.asarray(val, np.float32)[0, :landed])


# -------------------------------------------------------------- batcher

def test_batcher_admit_tick_release_matches_sequential():
    """Admit two rows, tick a few times, release one, admit a third into
    the freed slot: every row's tokens match its own batch-1 run, and no
    program compiled more than once."""
    eng = _engine()
    bat = SlotBatcher(eng, ServingConfig.from_dict(
        {"slots": 2, "max_len": 64, "prefill_chunk": 8}))
    assert bat.max_len == 64
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, (L,)).astype(np.int32)
               for L in (5, 11, 7)]

    def reference(p, n):
        s = eng.start_session(batch=1, max_len=64)
        s.append(jnp.asarray(p[None]))
        return np.asarray(s.generate(max_new_tokens=n))[0].tolist()

    key = jax.random.PRNGKey(9)
    got = {0: [], 1: []}
    bat.admit(0, prompts[0], key, True, 1.0)
    bat.admit(1, prompts[1], key, True, 1.0)
    for _ in range(4):
        toks = bat.tick()
        got[0].append(int(toks[0]))
        got[1].append(int(toks[1]))
    assert got[0] == reference(prompts[0], 4)
    assert got[1] == reference(prompts[1], 4)

    # slot 0 retires; a new prompt lands in it while slot 1 keeps decoding
    bat.release(0)
    bat.admit(0, prompts[2], key, True, 1.0)
    got = {0: [], 1: []}
    for _ in range(3):
        toks = bat.tick()
        got[0].append(int(toks[0]))
        got[1].append(int(toks[1]))
    assert got[0] == reference(prompts[2], 3)
    assert got[1] == reference(prompts[1], 7)[4:]
    counts = bat.compile_counts()
    assert all(v <= 1 for v in counts.values()), counts


@pytest.mark.parametrize("kv", ["auto", "int8"])
def test_donated_pool_strands_no_reader(kv):
    """The tick and the admission donate the slot pool.  A slot read out of
    it between ticks (what a park or a migration keeps) outlives the ticks
    that follow, and re-admitted it continues the reply: every token
    matches the same sequence through the session path."""
    from deepspeed_tpu.serving.batcher import PrefixEntry
    eng = _engine(kv_cache_dtype=kv)
    bat = SlotBatcher(eng, ServingConfig.from_dict(
        {"slots": 2, "max_len": 64, "prefill_chunk": 8}))
    bat.prewarm()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, (n,)).astype(np.int32) for n in (9, 6)]

    def reference(p, n):
        s = eng.start_session(batch=1, max_len=64)
        s.append(jnp.asarray(p[None]))
        return np.asarray(s.generate(max_new_tokens=n))[0].tolist()

    want = [reference(p, 6) for p in prompts]
    key = jax.random.PRNGKey(3)
    for row, p in enumerate(prompts):
        bat.admit(row, p, key, True, 1.0)
    got = np.stack([bat.tick() for _ in range(2)])
    assert got.T.tolist() == [w[:2] for w in want]

    # row 0 leaves the pool as a park would take it: K/V of the prompt and
    # of the first reply token (the second is emitted, not yet written)
    held = len(prompts[0]) + 1
    read = jax.jit(lambda c, row, n: gpt_inference.read_slot(c, row, n))
    parked = read(bat.cache, jnp.int32(0), jnp.int32(held))
    bat.release(0)
    more = np.stack([bat.tick() for _ in range(2)])      # donate the pool twice
    assert more[:, 1].tolist() == want[1][2:4]
    assert int(parked.length) == held and np.asarray(parked.k).any()

    # ... and comes back: the re-admission prefills only the emitted token
    so_far = np.concatenate([prompts[0], np.asarray(want[0][:2], np.int32)])
    bat.admit(0, so_far, key, True, 1.0,
              prefix=PrefixEntry(cache=parked, length=held))
    last = np.stack([bat.tick() for _ in range(2)])
    assert last[:, 0].tolist() == want[0][2:4]
    assert last[:, 1].tolist() == want[1][4:6]
    counts = bat.compile_counts()
    assert all(v <= 1 for v in counts.values()), counts


def test_batcher_prefix_fork_admission():
    """A pooled prefix admits through zero-copy fork: prefix prefilled
    once, remainder extended at the true frontier — output equals the
    whole prompt admitted flat."""
    eng = _engine()
    bat = SlotBatcher(eng, ServingConfig.from_dict(
        {"slots": 2, "max_len": 64, "prefill_chunk": 8}))
    rng = np.random.default_rng(1)
    system = rng.integers(0, 256, (12,)).astype(np.int32)
    turn = rng.integers(0, 256, (6,)).astype(np.int32)
    whole = np.concatenate([system, turn])
    key = jax.random.PRNGKey(4)

    entry = bat.build_prefix(system)
    assert entry.length == 12
    bat.admit(0, whole, key, True, 1.0, prefix=entry)
    bat.admit(1, whole, key, True, 1.0)          # flat, no prefix
    a, b = [], []
    for _ in range(5):
        toks = bat.tick()
        a.append(int(toks[0]))
        b.append(int(toks[1]))
    assert a == b
    # a prefix at least as long as the prompt is a usage error
    with pytest.raises(ValueError, match="shorter than"):
        bat.admit(0, system, key, True, 1.0,
                  prefix=bat.build_prefix(whole))


def test_batcher_overflow_and_tick_guards():
    eng = _engine()
    bat = SlotBatcher(eng, ServingConfig.from_dict(
        {"slots": 1, "max_len": 16, "prefill_chunk": 8}))
    with pytest.raises(RuntimeError, match="before any admission"):
        bat.tick()
    with pytest.raises(ValueError, match="overflows"):
        bat.admit(0, np.zeros(20, np.int32), jax.random.PRNGKey(0), True,
                  1.0)


def test_live_block_share_counts_the_live_blocks_of_the_live_rows():
    """``ServingMetrics.live_block_share`` by hand: a 4-slot pool of
    512-token slots in blocks of 256 (two a slot; what the rule gives a
    row is ``test_decode_sweep.py``'s), two layers of which the second is
    banded to 64 tokens; three live rows and a freed slot, which counts in
    the grid and never in the live blocks."""
    from deepspeed_tpu.ops.pallas.decode_attention import (decode_block_k,
                                                           sweep_block_counts)
    from deepspeed_tpu.serving.metrics import ServingMetrics
    windows = ((None, 1), (64, 1))
    # frontiers 100 | 300 | 511: the global layer steps 1 + 2 + 2 blocks, the
    # banded one 1 + 2 (the band [237, 300] straddles the edge) + 1
    live, grid = sweep_block_counts([100, 300, 511], 4, 512, 256, windows)
    assert (live, grid) == (5 + 4, 2 * 4 * 2)
    m = ServingMetrics()
    assert m.snapshot()["live_block_share"] == 0.0
    m.record_tick(active=3, slots=4, tokens=3, kv_blocks=(live, grid))
    # next tick: the row at 511 has finished and freed its slot
    m.record_tick(active=2, slots=4, tokens=2,
                  kv_blocks=sweep_block_counts([101, 301], 4, 512, 256,
                                               windows))
    snap = m.snapshot()
    assert (snap["kv_blocks_live"], snap["kv_blocks_grid"]) == (9 + 6, 32)
    assert snap["live_block_share"] == 15 / 32
    # a cache that does not tile runs no kernel: nothing to count
    assert sweep_block_counts([10], 4, 96, decode_block_k(96, 128)) == (0, 0)


def test_sweep_block_counts_agree_with_the_device_sweep(monkeypatch):
    """The device list, rows dead and live, banded and not, is each live
    row's blocks from its band's start to its frontier, in order, then the
    last entry repeated; the host's count is its length."""
    from deepspeed_tpu.ops.pallas import decode_attention as da
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(3)
    for window in (None, 200, 1):
        pos = rng.integers(0, 1024, 8)
        active = rng.random(8) < 0.6
        rows, blocks, n = da.decode_sweep(
            jnp.asarray(pos), 8, 1024, 256, jnp.asarray(active), window)
        live, grid = da.sweep_block_counts(
            [int(p) for p in pos[active]], 8, 1024, 256, ((window, 1),))
        assert int(n[0]) == live and rows.shape == blocks.shape == (grid,)
        want = [(b, k) for b in range(8) if active[b]
                for k in range(max((pos[b] - (window or 1024) + 1) // 256, 0),
                               pos[b] // 256 + 1)]
        want += want[-1:] * (grid - live)
        assert list(zip(rows.tolist(), blocks.tolist())) == want


@pytest.mark.parametrize("copy_rows", [None, 16, 32],
                         ids=["whole-blocks", "tile-16", "tile-32"])
@pytest.mark.parametrize("window", [None, 200, 1],
                         ids=["global", "band-200", "band-1"])
def test_sweep_token_counts_agree_with_the_device_sweep(monkeypatch, window,
                                                        copy_rows):
    """``(live, streamed)`` counted on the host from the lengths agree with
    a count made from the device's work list and ``pos``: an entry streams
    its block as far as its row's frontier, rounded up to the copy's tile
    (the whole block where the kernel copies whole blocks), and a query sees
    ``pos + 1`` tokens, a banded one its window of them."""
    from deepspeed_tpu.ops.pallas import decode_attention as da
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(5)
    pos = np.concatenate([rng.integers(0, 1024, 10),
                          [0, 15, 16, 255, 256, 1023]])
    active = rng.random(len(pos)) < 0.7
    B = len(pos)
    rows, blocks, n = da.decode_sweep(
        jnp.asarray(pos), B, 1024, 256, jnp.asarray(active), window)
    tail = copy_rows or 256
    streamed = 0
    for row, block in zip(rows.tolist()[:int(n[0])],
                          blocks.tolist()[:int(n[0])]):
        tokens = min(int(pos[row]) + 1 - block * 256, 256)
        streamed += -(-tokens // tail) * tail
    live = sum(min(int(p) + 1, window or 1024) for p in pos[active])
    got = da.sweep_token_counts([int(p) for p in pos[active]], 1024, 256,
                                ((window, 3),), copy_rows)
    assert got == (3 * live, 3 * streamed)
    assert da.sweep_token_counts([10], 96, da.decode_block_k(96, 128)) == \
        (0, 0)


def _replay(seed, requests, prompts, outputs, smax, block, layers, copy_rows):
    """A cell's draw (``prompts`` and ``outputs``: uniform, both ends
    included) replayed through ``ServingMetrics`` at its geometry:
    ``layers`` layer calls a tick over slots of ``smax`` in blocks of
    ``block``, a reply's every tick."""
    from deepspeed_tpu.ops.pallas.decode_attention import sweep_token_counts
    from deepspeed_tpu.serving.metrics import ServingMetrics
    rng = np.random.default_rng(seed)
    m = ServingMetrics()
    for _ in range(requests):
        prompt = rng.integers(prompts[0], prompts[1] + 1)
        out = rng.integers(outputs[0], outputs[1] + 1)
        # the tick that decodes token t reads the row up to prompt + t
        m.record_tick(active=1, slots=1, tokens=int(out),
                      kv_by_kind={"full": sweep_token_counts(
                          [int(prompt) + t for t in range(out)], smax, block,
                          ((None, layers),), copy_rows) + (layers,)})
    return m.snapshot()


def _decode_sat_replay(copy_rows):
    """``decode-sat``: prompts 64-256, outputs 128-384, 24 layers over
    slots of 1,024 in blocks of 256."""
    return _replay(45, 400, (64, 256), (128, 384), 1024, 256, 24, copy_rows)


@pytest.mark.parametrize("case", ["tile-edges", "parent-geometry",
                                  "this-kernel"])
def test_streamed_over_live(case):
    """``snapshot()["streamed_over_live"]``: 1.0 for rows that end on an
    edge of the copy's tile, and ``decode-sat``'s replayed draw at whole
    blocks (the parent's kernel: 1.42) and at the 16-row tile (1.03)."""
    from deepspeed_tpu.ops.pallas.decode_attention import (decode_copy_rows,
                                                           sweep_token_counts)
    from deepspeed_tpu.serving.metrics import ServingMetrics
    assert decode_copy_rows(2) == 16 and decode_copy_rows(1) == 32
    if case == "tile-edges":
        m = ServingMetrics()
        assert m.snapshot()["streamed_over_live"] == 0.0
        m.record_tick(active=4, slots=4, tokens=4,
                      kv_by_kind={"full": sweep_token_counts(
                          [15, 255, 271, 1023], 1024, 256, ((None, 2),),
                          16) + (2,)})
        snap = m.snapshot()
        assert snap["kv_tokens_live"] == 2 * (16 + 256 + 272 + 1024)
        assert snap["kv_tokens_streamed"] == snap["kv_tokens_live"]
        assert snap["streamed_over_live"] == 1.0
        return
    snap = _decode_sat_replay(None if case == "parent-geometry" else 16)
    lo, hi = (1.38, 1.46) if case == "parent-geometry" else (1.02, 1.05)
    assert lo <= snap["streamed_over_live"] <= hi, snap["streamed_over_live"]


@pytest.mark.parametrize("block,lo,hi", [(256, 1.012, 1.020),
                                         (1024, 1.055, 1.070)],
                         ids=["blocks-of-256", "the-rules-1024"])
def test_streamed_over_live_of_agent_sats_whole_blocks(block, lo, hi):
    """``agent-sat``'s draw (prompts 5,120-7,168, outputs 3,072-5,120,
    uniform) replayed at its geometry, two grouped layer calls a tick over
    slots of 16,384 whose sweep streams whole blocks: the tail of a row's
    last block is 1.6% of what its query sees at 256 tokens and 6% at the
    rule's 1,024 (PERF.md 6, PR 46: the larger block costs that and wins
    by the steps it spares)."""
    from deepspeed_tpu.ops.pallas.decode_attention import decode_block_k
    assert decode_block_k(16384, 2 * 128) == 1024
    got = _replay(46, 64, (5120, 7168), (3072, 5120), 16384, block, 2,
                  None)["streamed_over_live"]
    assert lo <= got <= hi, got


def test_the_batcher_counts_the_tokens_its_familys_kernel_streams():
    """``SlotBatcher.sweep_by_kind``: the dense family's kernel ends its copy
    on a tile, so a row at 100 streams 112 tokens a layer where its block,
    the whole 512-token slot of this 128-wide row, holds 512."""
    cfg = gpt.GPTConfig(vocab_size=128, n_layer=2, n_head=4, d_model=128,
                        max_seq_len=512, dtype=jnp.float32,
                        vocab_round_to=128)
    eng = deepspeed_tpu.init_inference(
        model=(cfg, gpt.init(cfg, jax.random.PRNGKey(0))),
        config={"dtype": "float32"})
    bat = SlotBatcher(eng, ServingConfig.from_dict(
        {"slots": 2, "max_len": 512, "prefill_chunk": 8}))
    assert bat.sweep_blocks([100, 300]) == (2 * 2, 2 * 2 * 1)
    assert bat.sweep_by_kind([100, 300]) == {
        "full": (2 * (101 + 301), 2 * (112 + 304), 2)}
    assert bat.sweep_by_kind([]) == {"full": (0, 0, 2)}


# ------------------------------------------------- the one-launch admission

CHUNK, SLOT = 8, 64


def _tiny(name, dtype=jnp.bfloat16):
    """``(cfg, params)`` of a family of the benchmark at the tiny sizes of
    its configuration ``name``, computing and served in ``dtype`` (the
    families' harness: drawn once a process, in one program)."""
    from tests.unit.models import family_harness as harness
    return harness.model(harness.SPECS[name], dtype=dtype, weights=dtype)


def _latent():
    """The latent-attention family at the benchmark's tiny sizes."""
    return _tiny("kimi-k2.7-code-ep32")


_SERVED = {}


def _two_batchers(eng, serving):
    """``(fused, plain)`` over one engine: each keeps its own slots, and the
    two launch ONE set of compiled programs (a program is its engine's and
    its geometry's, not its batcher's: compiled twice it is the same)."""
    fused, plain = SlotBatcher(eng, serving), SlotBatcher(eng, serving)
    plain.registry, plain._p = fused.registry, fused._p
    return fused, plain


def _served(family, kv):
    """``(fused, plain)``: two batchers over one bf16 engine of ``family``
    with cache type ``kv``, built once a module.  ``fused`` admits;
    ``plain`` is handed, row by row, what today's launches leave."""
    if (family, kv) not in _SERVED:
        if family == "latent":
            cfg, params = _latent()
        else:
            mod, cfg = (gpt, CFG) if family == "dense" else (gpt_moe, MOE_CFG)
            params = mod.init(cfg, jax.random.PRNGKey(0))
        eng = deepspeed_tpu.init_inference(
            model=(cfg, params),
            config={"dtype": "bfloat16", "kv_cache_dtype": kv})
        serving = ServingConfig.from_dict(
            {"slots": 3, "max_len": SLOT, "prefill_chunk": CHUNK})
        _SERVED[family, kv] = _two_batchers(eng, serving)
    return _SERVED[family, kv]


#: chunk programs that take ``valid``, a batcher of a family that needs it
_TOLD_THE_PADDING = {}
#: the family's ``logits`` as a program of its own, a batcher
_HEADS = {}


def _launch_by_launch(bat, row, tokens, key, greedy, temperature,
                      prefix=None):
    """An admission as it was before it was one program: a fresh batch-1
    cache (or the prefix's), a ``prefill`` / ``extend`` launch a chunk (the
    registered pair, which run no head), the family's ``logits`` of the last
    real token's row of the last launch's output, ``write_slot`` and the six
    binds."""
    fam, cfg, params = bat._fam, bat._cfg, bat._engine.params
    start = 0 if prefix is None else prefix.length
    cache = prefix.cache if prefix is not None else fam.init_cache(
        cfg, 1, bat.max_len, kv_dtype=bat._kv_dtype)
    new = np.asarray(tokens[start:], np.int32)
    C = bat.chunk
    prefill, extend = bat._p["prefill"], bat._p["extend"]
    if bat.cache.state is not None or bat.cache.ring is not None:
        # a recurrence or a ring must be told where a padded chunk ends,
        # which the registered pair (a prefix's builders) cannot say
        prefill, extend = _TOLD_THE_PADDING.setdefault(id(bat), (
            jax.jit(lambda p, t, c, valid: fam.prefill(
                p, t, cfg, c, valid=valid, head=False)),
            jax.jit(lambda p, t, c, l, valid: fam.extend(
                p, t, cfg, c, lengths=l, valid=valid, head=False))))
    for at in range(0, len(new), C):
        chunk = np.zeros((1, C), np.int32)
        real = len(new[at:at + C])
        chunk[0, :real] = new[at:at + C]
        valid = (jnp.asarray([real], jnp.int32),) \
            if prefill is not bat._p["prefill"] else ()
        if start + at == 0:
            x, cache = prefill(params, jnp.asarray(chunk), cache, *valid)
        else:
            x, cache = extend(
                params, jnp.asarray(chunk), cache,
                jnp.asarray([start + at], jnp.int32), *valid)
    head = _HEADS.setdefault(id(bat), jax.jit(
        lambda p, x: fam.logits(p, x, cfg)))
    vec = head(params, x[0, len(new) - 1 - at][None])[0]
    if bat._last is None:
        bat._last = jnp.zeros((bat.slots,) + vec.shape, vec.dtype)
    bat.cache = fam.write_slot(bat.cache, jnp.int32(row), cache)
    bat.lengths = bat.lengths.at[row].set(len(tokens))
    bat._last = bat._last.at[row].set(vec)
    bat.keys = bat.keys.at[row].set(key)
    bat.greedy = bat.greedy.at[row].set(greedy)
    bat.temp = bat.temp.at[row].set(temperature)
    bat.active = bat.active.at[row].set(True)


@pytest.mark.parametrize("prompt", [
    "one", "chunk", "chunk+1", "slot", "prefix", "prefix-ragged"])
@pytest.mark.parametrize("family,kv", [
    ("dense", "auto"), ("dense", "int8"), ("moe", "auto"), ("moe", "int8"),
    ("latent", "auto")],
    ids=["bf16-dense", "int8-dense", "bf16-moe", "int8-moe", "bf16-latent"])
def test_one_launch_admission_equals_the_launches_it_replaced(family, kv,
                                                              prompt):
    """One ``admit`` program leaves a slot as ``_chunked_prefill``'s
    launches, ``write_slot`` and ``bind`` left it: the row's cache up to the
    frontier, its length, frontier logits, key, sampling mode, and the eight
    greedy tokens that follow, bit for bit, for prompts of 1, ``C``, ``C +
    1`` and ``max_len`` tokens and for prompts that continue a prefix
    (ending on a chunk's edge, and not)."""
    n, cut = {"one": (1, 0), "chunk": (CHUNK, 0), "chunk+1": (CHUNK + 1, 0),
              "slot": (SLOT, 0), "prefix": (3 * CHUNK + 5, 2 * CHUNK),
              "prefix-ragged": (2 * CHUNK + 3, CHUNK - 3)}[prompt]
    _same_slot(*_served(family, kv), n, cut, ticks=8, tol=0)


def _same_slot(fused, plain, n, cut, ticks=6, tol=2e-4):
    """Admit a prompt of ``n`` tokens (past a prefix of ``cut``, each side's
    of its own making: the same chunks) into ``fused`` by its one launch and
    into ``plain`` launch by launch, and hold the two slots together within
    ``tol`` (0: bit for bit): the binds, the frontier logits, every leaf of
    the row up to the frontier, ``ticks`` greedy tokens; one launch, and no
    program of ``fused`` compiled twice."""
    fam, vocab = fused._fam, fused._cfg.vocab_size
    rng = np.random.default_rng(n + 7 * cut)
    tokens = rng.integers(0, vocab, (n,)).astype(np.int32)
    key = jax.random.PRNGKey(n)
    row = n % fused.slots
    for bat in (fused, plain):
        for r in range(bat.slots):
            bat.release(r)
    prefix = [None, None]
    if cut:
        prefix = [bat.build_prefix(tokens[:cut]) for bat in (fused, plain)]
    before = fused.admit_launches
    assert fused.admit(row, tokens, key, True, 0.7, prefix=prefix[0]) == n
    assert fused.admit_launches - before == 1
    _launch_by_launch(plain, row, tokens, key, True, 0.7, prefix=prefix[1])

    def close(a, b, what):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol, err_msg=what)

    for name in ("lengths", "keys", "greedy", "temp", "active"):
        np.testing.assert_array_equal(
            np.asarray(getattr(fused, name)),
            np.asarray(getattr(plain, name)), err_msg=name)
    assert int(fused.lengths[row]) == n and bool(fused.active[row])
    close(fused._last[row], plain._last[row], "frontier logits")
    got, want = (fam.read_slot(bat.cache, row, n) for bat in (fused, plain))
    for bank in ("k", "v", "k_scale", "v_scale"):
        if getattr(want, bank) is not None:
            close(getattr(got, bank)[:, 0, :n], getattr(want, bank)[:, 0, :n],
                  bank)
    for name in ("state", "ring"):
        for a, b in zip(getattr(got, name) or (), getattr(want, name) or ()):
            close(a, b, name)
    if n + ticks <= fused.max_len:
        replies = [[int(bat.tick()[row]) for _ in range(ticks)]
                   for bat in (fused, plain)]
        assert replies[0] == replies[1]
    counts = fused.compile_counts()
    assert all(v <= 1 for v in counts.values()), counts


@pytest.mark.parametrize("family,kv", [
    ("dense", "auto"), ("moe", "int8"), ("latent", "auto")],
    ids=["bf16-dense", "int8-moe", "bf16-latent"])
def test_one_admission_program_serves_every_prompt_length(family, kv):
    """Five prompt lengths, one chunk to the whole slot, a sampled row among
    them: one compile of ``admit``, one launch an admission, and no launch
    by ``_chunked_prefill``'s programs at all."""
    from deepspeed_tpu.serving.metrics import ServingMetrics
    fused, _ = _served(family, kv)
    rng = np.random.default_rng(5)
    before = fused.admit_launches
    for i, n in enumerate((2, CHUNK, CHUNK + 1, 3 * CHUNK + 2, SLOT - 1)):
        fused.release(i % 3)
        fused.admit(i % 3, rng.integers(0, 100, (n,)).astype(np.int32),
                    jax.random.PRNGKey(i), i != 3, 0.5 + i)
        fused.tick()
    assert fused.admit_launches - before == 5
    counts = fused.compile_counts()
    assert counts["admit"] == counts["tick"] == 1, counts
    assert counts["admit_wide"] == counts["admit_prefix_wide"] == 0, counts
    m = ServingMetrics()
    m.count("admitted", 5)
    m.count("admit_launches", fused.admit_launches - before)
    assert m.snapshot()["launches_per_admission"] == 1.0


# ------------------------------------------------- the ladder of widths

@pytest.mark.parametrize("family", [
    "dense", "moe", "latent_moe_family:kimi-k2.7-code-ep32.json",
    "hybrid_ssm_moe_family:granite-4.0-h-small-ep4.json",
    "nemotron_h_family:nemotron-3-nano-30b-a3b-ep4.json",
    "mellum_family:mellum2-12b-a2.5b-ep4.json",
    "dots3_family:dots3-note-prev-ep32.json"])
def test_the_frontier_logits_row_is_the_prefills(family):
    """``SlotBatcher._logits_row`` asks only the family's embedding and head
    for the frontier logits' shape and type (no body of the model is traced
    for it at a server's start): for every family, in the type its cells
    serve in, that is the row its ``prefill`` returns and the admission
    writes."""
    if ":" in family:
        cfg, params = _tiny(family.split(":")[1][:-len(".json")])
    else:
        mod, cfg = (gpt, CFG) if family == "dense" else (gpt_moe, MOE_CFG)
        cfg = dataclasses.replace(cfg, dtype=jnp.bfloat16)
        params = mod.init(cfg, jax.random.PRNGKey(0))
    eng = deepspeed_tpu.init_inference(model=(cfg, params),
                                       config={"dtype": "bfloat16"})
    bat = SlotBatcher(eng, ServingConfig.from_dict(
        {"slots": 2, "max_len": SLOT, "prefill_chunk": CHUNK}))
    fam = bat._fam
    lg, _ = jax.eval_shape(
        lambda p, t: fam.prefill(p, t, cfg, fam.init_cache(
            cfg, 1, bat.max_len, kv_dtype=bat._kv_dtype)),
        eng.params, jax.ShapeDtypeStruct((1, bat.chunk), jnp.int32))
    row = bat._logits_row()
    assert (row.shape, row.dtype) == (lg.shape[2:], lg.dtype)


#: a dense model small enough to run slots of GPT-2's length on the CPU
LONG = gpt.GPTConfig(vocab_size=256, max_seq_len=1024, n_layer=2, n_head=2,
                     d_model=32, dtype=jnp.float32, vocab_round_to=128)

#: ``(fused, plain)`` a key of :func:`_laddered`
_LADDERED = {}


def _laddered(family, widths="wide"):
    """``(fused, plain)`` as :func:`_served` gives them, in float32 (a wide
    pass sums in another order than the narrow ones it stands for): the
    dense model at the cell's geometry, chunks of 128 in slots of 1,024,
    where the ladder is 256, 128; a family with per-slot state and one
    with rings at their tiny sizes, chunks of 8 in slots of 64, for which
    the caller patches the widths small; and so any family of the unified
    suite by its configuration's name, or ``"tiny-dense"`` / ``"tiny-moe"``.
    A pair of batchers a ``widths`` (a key only): a program keeps the
    widths it was traced with."""
    if (family, widths) not in _LADDERED:
        geometry = {"slots": 2, "max_len": SLOT, "prefill_chunk": CHUNK}
        if family == "dense":
            cfg, params = LONG, gpt.init(LONG, jax.random.PRNGKey(0))
            geometry = {"slots": 2, "max_len": 1024, "prefill_chunk": 128}
        elif family in ("tiny-dense", "tiny-moe"):
            mod, cfg = (gpt, CFG) if family == "tiny-dense" else (
                gpt_moe, MOE_CFG)
            params = mod.init(cfg, jax.random.PRNGKey(0))
        else:
            cfg, params = _tiny({
                "state": "granite-4.0-h-small-ep4",
                "ring": "mellum2-12b-a2.5b-ep4"}.get(family, family),
                dtype=jnp.float32)
        eng = deepspeed_tpu.init_inference(model=(cfg, params),
                                           config={"dtype": "float32"})
        serving = ServingConfig.from_dict(geometry)
        _LADDERED[family, widths] = _two_batchers(eng, serving)
    return _LADDERED[family, widths]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class _Counts:
    """What :func:`_counting_family` keeps where a family keeps a cache."""
    passes: jax.Array
    wide: jax.Array
    narrow: jax.Array
    rows: jax.Array
    seen: jax.Array
    length: jax.Array


def _counting_family(vocab=4):
    """A stand-in for a model family whose "cache" counts what the admission
    program asks of it: the passes it ran, the tokens of those wider than
    the chunk (handed over where a family's ``cfg`` goes) and of those
    narrower, the rows of them all, how often each position was computed as
    a real token, and in a pass's output (which its "head" hands on as it
    is) the position each row stands at."""
    import types

    def init_cache(cfg, batch, max_len, kv_dtype=None):
        return _Counts(jnp.int32(0), jnp.int32(0), jnp.int32(0),
                       jnp.int32(0), jnp.zeros((max_len,), jnp.int32),
                       jnp.int32(0))

    def run(tokens, cache, pos0, valid, chunk):
        w = tokens.shape[1]
        at = pos0 + jnp.arange(w)
        seen = cache.seen.at[at].add(
            (jnp.arange(w) < valid[0]).astype(jnp.int32), mode="drop")
        x = jnp.broadcast_to(at.astype(jnp.float32)[None, :, None],
                             (1, w, vocab))
        return x, _Counts(cache.passes + 1,
                           cache.wide + (w if w > chunk else 0),
                           cache.narrow + (w if w < chunk else 0),
                           cache.rows + w, seen, pos0 + w)

    return types.SimpleNamespace(
        init_cache=init_cache,
        embed=lambda p, t, cfg, positions: jnp.zeros(t.shape + (vocab,)),
        logits=lambda p, x, cfg: x,
        prefill=lambda p, t, cfg, c, valid=None, head=True: run(
            t, c, 0, valid, cfg),
        extend=lambda p, t, cfg, c, lengths=None, valid=None, head=True:
            run(t, c, lengths[0], valid, cfg),
        write_slot=lambda pool, row, cache: cache)


def _admit_counting(chunk, max_len, start=0):
    """The admission program over :func:`_counting_family`, compiled once:
    ``n -> (what the family counted, the frontier logits' position)`` for a
    prompt of ``n`` tokens past ``start``."""
    from deepspeed_tpu.serving.batcher import admission
    fam = _counting_family()
    slots = jnp.zeros((2,), jnp.int32)
    prefix = () if not start else (fam.init_cache(chunk, 1, max_len),)
    admit = jax.jit(admission(fam, chunk, max_len, None))

    def of(n):
        out = admit(
            None, None, slots, jnp.zeros((2, 4)),
            jnp.zeros((2, 2), jnp.uint32), slots > 0, jnp.ones((2,)),
            slots > 0, jnp.zeros((-(-max_len // chunk), chunk), jnp.int32),
            jnp.array([1, start, n, 1, 0, 0, 0], jnp.int32),
            jax.random.PRNGKey(0), *prefix)
        return out[0], int(out[-1][0])

    return of


@pytest.mark.parametrize("chunk,max_len,start,widths", [
    (128, 1024, 0, (256, 128)), (128, 1024, 77, (256, 128)),
    (64, 1024, 0, (256, 64)), (256, 1024, 0, (256,)),
    (512, 1024, 0, (512, 256)), (512, 2048, 300, (512, 256)),
    (1024, 3072, 0, (1024, 512)), (1024, 4096, 77, (1024, 512)),
    (128, 192, 0, (128,)), (8, 64, 0, (8,)), (8, 64, 5, (8,))])
def test_the_hosts_pass_count_is_the_programs_trip_counts(chunk, max_len,
                                                          start, widths):
    """``ladder_passes`` (what ``serve.prefill`` carries as ``passes``,
    ``wide`` and ``narrow``, and ``padded`` follows) against the admission
    program's own loops, counted by a stand-in family, for prompts on both
    sides of every boundary of the ladder (a wide pass takes the chunks a
    prompt is padded to, so its last may hold padding; a narrow one is the
    last, over what is left of the prompt): every real token is computed
    once, at its own position, the frontier logits are the last one's, a
    pass is 256 tokens wide where that is a whole multiple of the chunk,
    wider than it and fits the slot, and a last pass is half the chunk, if
    that is no narrower than 256, where it holds the prompt's end."""
    from deepspeed_tpu.serving.batcher import ladder_passes, pass_widths
    assert pass_widths(chunk, max_len) == widths
    # a fresh row's first chunk is the ``prefill`` only where the ladder
    # stops at the chunk: under a narrow width it starts empty
    first = 0 if start or widths[-1] < chunk else 1
    edges = {1, chunk, max_len - start}
    for w in widths:
        for k in (1, 2, 3):
            edges.update({k * w, first * chunk + k * w, k * chunk + w})
    admit = _admit_counting(chunk, max_len, start)
    for n in sorted({e + d for e in edges for d in (-1, 0, 1)}):
        if not 0 < n <= max_len - start:
            continue
        counted, at = admit(n)
        passes, wide, narrow = ladder_passes(n, chunk, widths, first)
        assert (int(counted.passes), int(counted.wide),
                int(counted.narrow)) == (passes, wide, narrow), n
        assert int(counted.rows) == -(-n // chunk) * chunk - (
            chunk - narrow if narrow else 0), n
        seen = np.asarray(counted.seen)
        assert (seen[start:start + n] == 1).all() and seen.sum() == n, n
        assert at == start + n - 1, n


@pytest.mark.parametrize("chunk,max_len,widths", [
    (128, 1024, (256, 128)), (256, 1024, (256,)), (512, 1024, (512, 256)),
    (1024, 3072, (1024, 512)), (1024, 1024, (1024, 512)),
    (1024, 4096, (1024, 512)), (1024, 4097, (1024,)),
    (1024, 8192, (1024,)), (1024, 16384, (1024,)), (512, 2048, (512, 256)),
    (512, 5120, (512,)), (512, 6144, (512,)), (512, 384, (384,)),
    (768, 3072, (768, 384)), (640, 4096, (640,)), (96, 4096, (96,)),
    (510, 1024, (510,)), (513, 1024, (513,)), (2048, 8192, (2048, 1024))])
def test_the_ladder_goes_down_from_the_chunk_by_its_half(chunk, max_len,
                                                          widths):
    """``pass_widths``: after the chunk its half, where that is whole and
    no narrower than 256 rows (a pass under the ridge costs what the ridge
    costs) and the slot at most four chunks long (half a chunk is then a
    share of a prompt worth a body of the model at a server's start: of
    the serving cells' geometries 1,024 in 3,072 alone), and no quarter; at
    128 and 256 what it returned before it went down; a chunk the batcher
    has cut to a slot shorter than it is that slot's."""
    from deepspeed_tpu.serving.batcher import pass_widths
    assert pass_widths(min(chunk, max_len), max_len) == widths


@pytest.mark.parametrize("n,first,want", [
    # (passes, wide, narrow) at a chunk of 1,024 over (1024, 512, 256)
    (255, 1, (1, 0, 256)), (256, 1, (1, 0, 256)), (257, 1, (1, 0, 512)),
    (512, 1, (1, 0, 512)), (513, 1, (1, 0, 0)), (1024, 1, (1, 0, 0)),
    (1025, 1, (2, 0, 256)), (1280, 1, (2, 0, 256)), (1281, 1, (2, 0, 512)),
    (1536, 1, (2, 0, 512)), (1537, 1, (2, 0, 0)), (2048, 1, (2, 0, 0)),
    (3000, 1, (3, 0, 0)), (3072, 1, (3, 0, 0)),
    # after a prefix every pass is an ``extend``: the same tail rule
    (1, 0, (1, 0, 256)), (256, 0, (1, 0, 256)), (257, 0, (1, 0, 512)),
    (1024, 0, (1, 0, 0)), (1025, 0, (2, 0, 256)), (1537, 0, (2, 0, 0))])
def test_a_last_pass_is_as_narrow_as_what_is_left_of_the_prompt(n, first,
                                                                want):
    """``ladder_passes`` over a ladder of TWO narrow widths (what
    ``pass_widths`` would give with the quarter it was measured with and
    lost): the narrowest that holds the prompt's end, fresh or after a
    prefix."""
    from deepspeed_tpu.serving.batcher import ladder_passes
    assert ladder_passes(n, 1024, (1024, 512, 256), first) == want


def test_a_704_token_document_is_four_passes_not_six():
    from deepspeed_tpu.serving.batcher import ladder_passes, pass_widths
    widths = pass_widths(128, 1024)
    assert widths == (256, 128)
    assert ladder_passes(704, 128, widths, 1) == (4, 512, 0)
    assert ladder_passes(704, 128, (128,), 1) == (6, 0, 0)
    # a wide pass takes the chunks a prompt is PADDED to: 129 tokens past
    # the first chunk are the two chunks they were, in one pass
    assert ladder_passes(256, 128, widths, 1) == (2, 0, 0)
    assert ladder_passes(257, 128, widths, 1) == (2, 256, 0)
    assert ladder_passes(384, 128, widths, 1) == (2, 256, 0)
    assert ladder_passes(385, 128, widths, 1) == (3, 256, 0)
    # continuing a prefix, the wide passes come first
    assert ladder_passes(704, 128, widths) == (3, 768, 0)
    # the widths compose: a ladder of two takes the widest first
    assert ladder_passes(704, 128, (512, 256, 128), 1) == (3, 512, 0)
    assert ladder_passes(1024, 128, (512, 256, 128), 1) == (4, 768, 0)
    # ... and in both directions: the chunks but the last by the wide
    # widths, the prompt's end by the narrowest that holds it
    both = (64, 32, 16, 8, 4)
    assert ladder_passes(16 + 64 + 3, 16, both, 1) == (3, 64, 4)
    assert ladder_passes(16 + 64 + 3, 16, both) == (3, 64, 4)
    assert ladder_passes(16 + 96 + 5, 16, both, 1) == (4, 96, 8)
    assert ladder_passes(16 + 96 + 9, 16, both, 1) == (4, 96, 0)
    assert ladder_passes(16 + 96 + 9, 16, both) == (2, 128, 0)


def _chunk_loops(chunk, max_len, prefix=False, primitive="while"):
    """The traced-trip-count loops (or, for ``"cond"``, the switches) of
    the admission's jaxpr at the top level of the program (a family's own
    scans lie below)."""
    from deepspeed_tpu.serving.batcher import admission
    fam = gpt_inference.DENSE
    cfg = LONG
    shapes = jax.eval_shape(lambda: (
        gpt.init(cfg, jax.random.PRNGKey(0)),
        fam.init_cache(cfg, 2, max_len), fam.init_cache(cfg, 1, max_len)))
    params, pool, row = shapes
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    jaxpr = jax.make_jaxpr(admission(fam, cfg, max_len, None))(
        params, pool, i32(2), f32(2, cfg.padded_vocab),
        jax.ShapeDtypeStruct((2, 2), jnp.uint32),
        jax.ShapeDtypeStruct((2,), bool), f32(2),
        jax.ShapeDtypeStruct((2,), bool),
        i32(-(-max_len // chunk), chunk), i32(7),
        jax.ShapeDtypeStruct((2,), jnp.uint32),
        *((row,) if prefix else ()))
    return [e for e in jaxpr.jaxpr.eqns if e.primitive.name == primitive]


@pytest.mark.parametrize("prefix", [False, True], ids=["admit", "prefix"])
@pytest.mark.parametrize("chunk,max_len,loops,switches", [
    (128, 1024, 2, 0), (256, 1024, 1, 0), (512, 1024, 1, 1),
    (1024, 1024, 1, 1), (1024, 3072, 1, 1), (512, 8192, 1, 0),
    (1024, 16384, 1, 0), (128, 192, 1, 0), (8, 64, 1, 0)])
def test_the_ladder_is_absent_where_the_chunk_is_the_ridge_or_the_slot_short(
        chunk, max_len, loops, switches, prefix):
    """The admission holds ONE chunk loop and nothing else at a
    ``prefill_chunk`` of 256, in slots shorter than 256 (nearly every
    test's) and in slots longer than four chunks (every serving cell's but
    one), as it did before the ladder.  Two loops at 128 in slots of 1,024
    (256 above the chunk); at 512 and 1,024 in slots of at most four
    chunks (``lfm2-serve-assist-sat``'s 1,024 in 3,072) the one loop and
    ONE ``lax.switch`` after it, the last pass at half the chunk or none."""
    assert [len(_chunk_loops(chunk, max_len, prefix, primitive))
            for primitive in ("while", "cond")] == [loops, switches]


@pytest.mark.parametrize("n,cut", [
    (127, 0), (128, 0), (129, 0), (383, 0), (384, 0), (385, 0), (639, 0),
    (640, 0), (641, 0), (896, 0), (1024, 0),
    (77 + 255, 77), (77 + 256, 77), (77 + 257, 77), (77 + 512, 77),
    (77 + 513, 77), (77 + 700, 77)])
def test_wide_passes_leave_the_slot_as_the_chunks_they_stand_for(n, cut):
    """An admission that runs the whole 256-token passes its prompt holds
    leaves the slot as launches of 128 leave it (``_chunked_prefill``'s
    programs, ``write_slot`` and the binds): the frontier logits, the row's
    cache up to the frontier and the six greedy tokens that follow, for
    prompts on both sides of every boundary and for prompts that continue a
    prefix whose length is a multiple of no width; no program compiles a
    second time over all of them."""
    _same_slot(*_laddered("dense"), n, cut)


@pytest.mark.parametrize("family", ["state", "ring"])
def test_wide_passes_of_a_state_family_and_of_a_ring_family(monkeypatch,
                                                            family):
    """The ladder with its widths patched small (32 and 16 over chunks of 8
    in slots of 64) for a family that keeps state per slot (a wide pass
    hands the recurrence four chunks' tokens at once) and for one whose
    window layers keep rings of 16 cells (a 32-token pass is longer than
    the ring): the slot as launches of 8 leave it, for prompts with and
    without a whole wide pass, with and without a prefix."""
    from deepspeed_tpu.serving import batcher
    monkeypatch.setattr(batcher, "WIDE_PASSES", (32, 16))
    fused, plain = _laddered(family)
    assert batcher.pass_widths(fused.chunk, fused.max_len) == (32, 16, 8)
    cuts = (0,) if fused.unsupported("prefix") else (0, 5)
    for n in (7, 23, 24, 25, 40, 41, 57, 64):
        for cut in cuts:
            if n - cut > 0 and (cut == 0 or n > 8):
                _same_slot(fused, plain, n, cut, tol=2e-3)


#: every family the batcher serves, by what :func:`_laddered` draws it from
_EVERY_FAMILY = (
    "tiny-dense", "tiny-moe", "kimi-k2.7-code-ep32",
    "granite-4.0-h-small-ep4", "nemotron-3-nano-30b-a3b-ep4",
    "mellum2-12b-a2.5b-ep4", "dots3-note-prev-ep32",
    "kimi-linear-48b-a3b-ep8", "longcat-flash-chat-ep32", "lfm2-8b-a1b",
    "solar-open2-250b-ep8")


@pytest.mark.parametrize("family", _EVERY_FAMILY)
def test_a_narrow_last_pass_leaves_the_slot_as_the_chunk_leaves_it(
        monkeypatch, family):
    """The ladder downward with its ridge patched small and its slots'
    rule long (widths of 8 and 4 at chunks of 8 in slots of 64), for every
    family the unified suite
    serves: a prompt whose end half a chunk holds leaves the slot as
    launches of 8 leave it: the frontier logits, the row's length, its
    cache up to the frontier, per-slot state (a convolution's tail, an SSM
    or delta-rule state, a ring: ``valid`` inside the narrow pass is what
    carries it) and the six greedy tokens that follow, with and without a
    prefix; a fresh row starts empty and every pass is the family's
    ``extend``, the first at position 0, against launches that start with
    its ``prefill``; one program serves them all."""
    from deepspeed_tpu.serving import batcher
    monkeypatch.setattr(batcher, "NARROW_FLOOR", 2)
    monkeypatch.setattr(batcher, "NARROW_SLOT_CHUNKS", SLOT // CHUNK)
    fused, plain = _laddered(family, "narrow")
    assert batcher.pass_widths(fused.chunk, fused.max_len) == (8, 4)
    cuts = (0,) if fused.unsupported("prefix") else (0, 5)
    for n in (1, 3, 4, 5, 8, 9, 11, 12, 13, 16, 26, 28, 29, 58, 64):
        for cut in cuts:
            if n - cut > 0:
                _same_slot(fused, plain, n, cut, tol=2e-3)


#: ``batcher -> whole-prompt prefill``, jitted once a family
_WHOLE_PROMPT = {}


def _whole_prompts_last_logits(bat, tokens):
    """Row ``n - 1`` of the logits ONE ``prefill`` of the whole prompt
    returns (the family's head over every row, as every pass of an admission
    ran it before the head ran once): the prompt padded to the slot, told
    where it ends."""
    fam, cfg = bat._fam, bat._cfg
    whole = _WHOLE_PROMPT.setdefault(id(bat), jax.jit(
        lambda p, t, valid: fam.prefill(
            p, t, cfg, fam.init_cache(cfg, 1, bat.max_len), valid=valid)[0]))
    padded = np.zeros((1, bat.max_len), np.int32)
    padded[0, :len(tokens)] = tokens
    return np.asarray(whole(bat._engine.params, padded, jnp.asarray(
        [len(tokens)], jnp.int32)))[0, len(tokens) - 1]


@pytest.mark.parametrize("prompt", [
    "under-a-pass", "on-a-passes-edge", "passes-then-a-narrow-one",
    "a-narrow-pass-alone", "after-a-prefix", "the-whole-slot"])
@pytest.mark.parametrize("family", _EVERY_FAMILY)
def test_the_admissions_row_is_the_whole_prompts_last_logits(
        monkeypatch, family, prompt):
    """The head runs ONCE an admission, on the one row the passes kept:
    for every family the frontier logits an admission binds (and returns)
    are row ``n - 1`` of the logits a whole-prompt ``prefill`` gives with
    its head over every row, and the first greedy token is that row's
    argmax; for a prompt inside one pass, one that ends on a pass's edge,
    one of several passes whose end a narrow pass takes, one that is a
    narrow pass alone, one that continues a prefix (refused in the family's
    words where it serves none) and one that fills the slot; from the slot's
    own row of the pool (the dense and GPT-MoE families) and from an empty
    row cache (every other), at widths of 8 and 4 in slots of 64."""
    from deepspeed_tpu.serving import batcher
    monkeypatch.setattr(batcher, "NARROW_FLOOR", 2)
    monkeypatch.setattr(batcher, "NARROW_SLOT_CHUNKS", SLOT // CHUNK)
    fused, _ = _laddered(family, "narrow")
    assert batcher.pass_widths(fused.chunk, fused.max_len) == (8, 4)
    assert fused._in_place == (family in ("tiny-dense", "tiny-moe"))
    n, cut = {"under-a-pass": (6, 0), "on-a-passes-edge": (16, 0),
              "passes-then-a-narrow-one": (27, 0),
              "a-narrow-pass-alone": (3, 0), "after-a-prefix": (19, 5),
              "the-whole-slot": (SLOT, 0)}[prompt]
    vocab = fused._cfg.vocab_size
    tokens = np.random.default_rng(n).integers(0, vocab, (n,)).astype(
        np.int32)
    if cut and fused.unsupported("prefix"):
        with pytest.raises(NotImplementedError, match="prefix"):
            fused.build_prefix(tokens[:cut])
        return
    for r in range(fused.slots):
        fused.release(r)
    fused.admit(1, tokens, jax.random.PRNGKey(n), True, 1.0,
                prefix=fused.build_prefix(tokens[:cut]) if cut else None)
    want = _whole_prompts_last_logits(fused, tokens)
    np.testing.assert_allclose(np.asarray(fused._last[1]), want,
                               rtol=2e-4, atol=2e-4)
    if n < fused.max_len:
        assert int(fused.tick()[1]) == int(np.argmax(want[:vocab]))
    assert all(v <= 1 for v in fused.compile_counts().values())


@pytest.mark.parametrize("family,dtype,first,second", [
    ("dense", "float32", [204, 102, 130, 237, 216], [204, 102, 130, 237, 216]),
    ("dense", "bfloat16", [204, 102, 130, 237, 216],
     [204, 102, 130, 237, 216]),
    ("moe", "float32", [102, 51, 65, 118, 108], [102, 51, 77, 118, 108]),
    ("moe", "bfloat16", [102, 51, 65, 118, 108], [102, 51, 77, 118, 108])],
    ids=["dense-float32", "dense-bfloat16", "moe-float32", "moe-bfloat16"])
def test_a_seeded_batchs_greedy_tokens_are_the_parents(family, dtype, first,
                                                       second):
    """Five seeded prompts of 3 to 59 tokens in slots of 64, chunks of 8:
    the first two greedy tokens of every row are what the tree gave while
    every pass of an admission ran its head over all its rows (the PARENT
    of PR 65, ``git archive 4599934``; all eleven families' five tokens a
    row were compared once by hand, in bf16 and in float32: PERF.md 6)."""
    mod, cfg = (gpt, CFG) if family == "dense" else (gpt_moe, MOE_CFG)
    cfg = dataclasses.replace(cfg, dtype=jnp.dtype(dtype))
    eng = deepspeed_tpu.init_inference(
        model=(cfg, mod.init(cfg, jax.random.PRNGKey(0))),
        config={"dtype": dtype})
    bat = SlotBatcher(eng, ServingConfig.from_dict(
        {"slots": 5, "max_len": SLOT, "prefill_chunk": CHUNK}))
    rng = np.random.default_rng(11)
    for row, n in enumerate((3, 8, 13, 27, 59)):
        bat.admit(row, rng.integers(0, cfg.vocab_size, (n,)).astype(
            np.int32), jax.random.PRNGKey(row), True, 1.0)
    assert [[int(t) for t in bat.tick()] for _ in range(2)] == [first, second]


def test_the_ladder_in_both_directions_at_once(monkeypatch):
    """Widths of 32 and 16 over chunks of 8 and of 4 under them, for a
    family with per-slot state: the whole wide passes first, then the
    chunks but the last, then the prompt's end at half a chunk where that
    holds it."""
    from deepspeed_tpu.serving import batcher
    monkeypatch.setattr(batcher, "WIDE_PASSES", (32, 16))
    monkeypatch.setattr(batcher, "NARROW_FLOOR", 2)
    monkeypatch.setattr(batcher, "NARROW_SLOT_CHUNKS", SLOT // CHUNK)
    fused, plain = _laddered("state", "both")
    assert batcher.pass_widths(fused.chunk, fused.max_len) == (32, 16, 8, 4)
    for n in (2, 10, 26, 27, 41, 43, 47, 57, 59, 64):
        _same_slot(fused, plain, n, 0, tol=2e-3)


def test_the_fold_inside_the_admission_is_the_hosts_fold():
    """``fold=``: the row samples from ``jax.random.fold_in(key, fold)``,
    bit for bit, for folds over the whole of 32 unsigned bits; without it
    the key is bound as handed over."""
    fused, _ = _served("dense", "auto")
    base = jax.random.PRNGKey(7)
    tokens = np.arange(5, dtype=np.int32)
    for fold in (0, 1, 12345, 2**31 + 5, 2**32 - 1):
        fused.release(1)
        fused.admit(1, tokens, base, False, 0.9, fold=fold)
        np.testing.assert_array_equal(
            np.asarray(fused.keys[1]),
            np.asarray(jax.random.fold_in(base, fold)), err_msg=str(fold))
    fused.release(1)
    fused.admit(1, tokens, base, False, 0.9)
    np.testing.assert_array_equal(np.asarray(fused.keys[1]),
                                  np.asarray(base))
    assert fused.compile_counts()["admit"] == 1


def test_the_gateway_counts_one_launch_an_admission_and_a_prefix_build_more():
    """``launches_per_admission`` is 1.0 on the plain path, and on the
    prefix path once the prefix exists: the admission that builds the pooled
    prefix pays the builder's launches (one a chunk) beside its own."""
    eng = _engine()
    gw = eng.serve(config={"slots": 2, "max_len": SLOT,
                           "prefill_chunk": CHUNK, "queue_capacity": 8})
    rng = np.random.default_rng(2)
    for n in (3, CHUNK + 4, 5 * CHUNK):
        gw.submit(rng.integers(0, 256, (n,)).astype(np.int32),
                  max_new_tokens=2).result(timeout=120)
    snap = gw.snapshot()
    assert (snap["admitted"], snap["admit_launches"],
            snap["launches_per_admission"]) == (3, 3, 1.0)
    system = rng.integers(0, 256, (2 * CHUNK,)).astype(np.int32)
    for n in (3, 6):
        tail = rng.integers(0, 256, (n,)).astype(np.int32)
        gw.submit(np.concatenate([system, tail]), max_new_tokens=2,
                  prefix_len=2 * CHUNK).result(timeout=120)
    gw.shutdown()
    snap = gw.snapshot()
    # 3 plain + (2 chunks to build the prefix + 1) + 1 on the pooled prefix
    assert (snap["admitted"], snap["admit_launches"]) == (5, 3 + 3 + 1)
    assert snap["prefix_builds"] == 1 and snap["prefix_hits"] == 1
    assert snap["recompiles"] == 0
    counts = snap["compile_counts"]
    assert counts["admit"] == counts["admit_prefix"] == 1, counts


# --------------------------------- an admission on the slot's own row (PR 63)

#: slots of 38 cells in chunks of 8 (the last chunk passes the row's end),
#: one wide width over the chunk and its half under it
_ROW_SLOTS, _ROW_LEN = 3, 38


def _row_and_pool_admissions(monkeypatch, cfg):
    """``admit(pool, state, row, tokens) -> (pool, state, logits row)`` of
    the dense family at ``cfg`` twice over, jitted: IN PLACE on the slot's
    own row of the pool (the family as it is), and through the batch-1 row
    cache (the same family with ``pool_rows`` off), the ladder patched so
    that BOTH start from an empty row and run the same passes (16, 8 and 4
    tokens wide), so what they leave can be held together bit for bit."""
    from deepspeed_tpu.serving import batcher
    monkeypatch.setattr(batcher, "WIDE_PASSES", (16,))
    monkeypatch.setattr(batcher, "NARROW_FLOOR", 2)
    monkeypatch.setattr(batcher, "NARROW_SLOT_CHUNKS", 8)
    assert batcher.pass_widths(CHUNK, _ROW_LEN) == (16, 8, 4)
    fam = gpt_inference.DENSE
    apart = dataclasses.replace(fam, pool_rows=False)
    params = gpt.init(cfg, jax.random.PRNGKey(0))

    def admit_of(family):
        fn = jax.jit(batcher.admission(family, cfg, _ROW_LEN, None))

        def admit(pool, state, row, tokens):
            chunks = np.zeros((-(-_ROW_LEN // CHUNK), CHUNK), np.int32)
            chunks.reshape(-1)[:len(tokens)] = tokens
            out = fn(params, pool, *state, chunks,
                     np.array([row, 0, len(tokens), 1, 0, 0, 0], np.int32),
                     jax.random.PRNGKey(len(tokens)))
            return out[0], out[1:7], out[7]
        return admit

    def fresh():
        return fam.init_cache(cfg, _ROW_SLOTS, _ROW_LEN), (
            jnp.zeros((_ROW_SLOTS,), jnp.int32),
            jnp.zeros((_ROW_SLOTS, cfg.padded_vocab), jnp.float32),
            jnp.zeros((_ROW_SLOTS, 2), jnp.uint32),
            jnp.ones((_ROW_SLOTS,), bool), jnp.ones((_ROW_SLOTS,)),
            jnp.zeros((_ROW_SLOTS,), bool))

    return fam, params, admit_of(fam), admit_of(apart), fresh


def _same_bits(a, b, what):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=what)


@pytest.mark.parametrize("first,then", [
    (5, None), (8, None), (16, None), (32, None), (19, None), (27, None),
    (37, None), (38, None), (38, 5), (33, 16), (30, 19)],
    ids=["one_chunk", "a_whole_chunk", "a_wide_pass", "two_wide_passes",
         "a_padded_narrow_pass", "a_padded_chunk", "the_clamp",
         "the_rows_last_cell", "after_a_longer_tenant",
         "a_wide_pass_after_a_longer", "a_narrow_pass_after_a_longer"])
def test_an_admission_in_place_is_the_row_caches_bit_for_bit(
        monkeypatch, first, then):
    """The admission on the slot's own row of the pool against the
    row-cache admission of the same tree (float32, the CPU): the slot's
    cells up to its frontier, the frontier logits, lengths, keys and the
    other binds, bit for bit, for prompts of one chunk, of whole wide
    passes, of a padded last pass, of a last chunk that passes the row's
    end (``_chunk_slice``'s clamp) and up to the row's last cell; and in a
    row whose last tenant was LONGER: the stale cells past the new frontier
    (the row cache's are zeros) are read by nobody, so one tick each gives
    the same logits bit for bit too."""
    fam, params, in_place, apart, fresh = _row_and_pool_admissions(
        monkeypatch, CFG)
    rng = np.random.default_rng(first)
    row = first % _ROW_SLOTS
    pools, states = zip(fresh(), fresh())
    for n in (first,) + ((then,) if then else ()):
        tokens = rng.integers(0, CFG.vocab_size, (n,)).astype(np.int32)
        (pool_a, state_a, vec_a), (pool_b, state_b, vec_b) = (
            admit(pool, state, row, tokens)
            for admit, pool, state in zip((in_place, apart), pools, states))
        pools, states = (pool_a, pool_b), (state_a, state_b)
        _same_bits(vec_a, vec_b, "frontier logits")
        for name, a, b in zip(("lengths", "last", "keys", "greedy", "temp",
                               "active"), state_a, state_b):
            _same_bits(a, b, name)
        assert int(state_a[0][row]) == n
        for bank in ("k", "v"):
            _same_bits(getattr(pool_a, bank)[:, row, :n],
                       getattr(pool_b, bank)[:, row, :n], bank)
            # ... and no other row was touched
            others = [r for r in range(_ROW_SLOTS) if r != row]
            assert not np.asarray(getattr(pool_a, bank)[:, others]).any()
    if then:
        # the last tenant's cells are still there, past the frontier (and
        # past the last pass's padding, which both paths write)
        stale = slice(-(-then // CHUNK) * CHUNK, first)
        assert np.asarray(pools[0].k[:, row, stale]).any()
        assert not np.asarray(pools[1].k[:, row, stale]).any()
    if n < _ROW_LEN:
        nxt = jnp.argmax(states[0][1][:, :CFG.vocab_size], -1).astype(
            jnp.int32)
        ticks = [fam.decode_step(params, nxt, CFG, pool, lengths=state[0],
                                 active=state[5])
                 for pool, state in zip(pools, states)]
        _same_bits(ticks[0][0][row], ticks[1][0][row], "a tick's logits")
        _same_bits(ticks[0][1].k[:, row, :n + 1], ticks[1][1].k[:, row, :n + 1],
                   "a tick's cell")


def _pool_of(case):
    """``(family, pool)`` that an admission must NOT take in place."""
    from deepspeed_tpu.models import cache_family
    if case == "int8":
        return gpt_inference.DENSE, gpt_inference.DENSE.init_cache(
            CFG, 2, SLOT, kv_dtype="int8")
    if case == "another_attention":
        return dataclasses.replace(gpt_inference.DENSE, pool_rows=False), \
            gpt_inference.DENSE.init_cache(CFG, 2, SLOT)
    cfg, _ = _tiny(_CELLS_FAMILIES[case])
    fam = cache_family(cfg)
    return fam, jax.eval_shape(lambda: fam.init_cache(cfg, 2, SLOT))


#: what a cache holds beside two equal banks -> a configuration of the
#: benchmark's that serves such a cache (all eight of its other families)
_CELLS_FAMILIES = {"state": "granite-4.0-h-small-ep4",
                   "state_single_part": "nemotron-3-nano-30b-a3b-ep4",
                   "ring": "mellum2-12b-a2.5b-ep4",
                   "latent": "kimi-k2.7-code-ep32",
                   "latent_and_ring": "dots3-note-prev-ep32",
                   "latent_and_state": "kimi-linear-48b-a3b-ep8",
                   "latent_two_rows": "longcat-flash-chat-ep32",
                   "conv_tail": "lfm2-8b-a1b"}


@pytest.mark.parametrize("case", list(_CELLS_FAMILIES) + [
    "int8", "another_attention", "prefix"])
def test_what_a_row_cache_is_still_for(case):
    """A cache with per-slot state, with rings, of one latent bank or with
    an int8 cache's scale banks (every family of the benchmark but the
    dense one: their cells' admissions say ``in_place`` 0), a family whose
    attention does not read a row of a pool, and ANY admission that
    continues a pooled prefix keep the batch-1 row cache:
    ``gpt_inference.in_place`` says so from what the cache holds, the
    traced admission makes its row cache (``admit_row_cache``) and writes
    the slot (``admit_slot_write``), and the dense bf16 pool's plain
    admission has neither scope."""
    from deepspeed_tpu.serving.batcher import admission
    fam, pool = (gpt_inference.DENSE, gpt_inference.DENSE.init_cache(
        CFG, 2, SLOT)) if case == "prefix" else _pool_of(case)
    assert gpt_inference.in_place(fam, pool) == (case == "prefix")
    if case in _CELLS_FAMILIES:
        # it is what the cache holds that says no, whatever the family says
        assert pool.state is not None or pool.ring is not None \
            or pool.v is None
        assert not gpt_inference.in_place(gpt_inference.DENSE, pool)
        return      # their admissions run in the families' own suites
    assert pool.int8 == (case == "int8")
    kv = "int8" if case == "int8" else None
    params = jax.eval_shape(lambda: gpt.init(CFG, jax.random.PRNGKey(0)))
    shape = jax.ShapeDtypeStruct
    vectors = (shape((2,), jnp.int32), shape((2, CFG.padded_vocab),
                                             jnp.float32),
               shape((2, 2), jnp.uint32), shape((2,), bool),
               shape((2,), jnp.float32), shape((2,), bool),
               shape((SLOT // CHUNK, CHUNK), jnp.int32),
               shape((7,), jnp.int32), shape((2,), jnp.uint32))
    prefix = (jax.eval_shape(lambda: fam.init_cache(CFG, 1, SLOT)),) \
        if case == "prefix" else ()

    def scopes(*more):
        text = jax.jit(admission(fam, CFG, SLOT, kv)).lower(
            params, pool, *vectors, *more).as_text(debug_info=True)
        return {s for s in ("admit_row_cache", "admit_slot_write")
                if s in text}

    assert scopes(*prefix) == {"admit_slot_write"} | (
        set() if prefix else {"admit_row_cache"})
    if case == "prefix":    # the same pool with no prefix: in place
        assert scopes() == set()
