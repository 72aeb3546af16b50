"""Slot ops + SlotBatcher: per-row admission into a live cache, batched
ragged decode ticks, and the no-recompile contract."""

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
    """One helper maps a config to its cache family; both families expose
    the seven names the engine, batcher, pager and fleet call, and the
    cache class and slot ops are the same objects in both."""
    from deepspeed_tpu.models import cache_family
    assert cache_family(CFG) is gpt_inference
    assert cache_family(MOE_CFG) is gpt_moe_inference
    for fam in (gpt_inference, gpt_moe_inference):
        assert all(callable(getattr(fam, n)) for n in FAMILY_NAMES), fam
    for name in ("init_cache", "write_slot", "read_slot", "reset_slot"):
        assert getattr(gpt_moe_inference, name) is getattr(gpt_inference,
                                                           name)
    eng = _engine()
    assert eng._family is gpt_inference


@pytest.mark.parametrize("fam, mod, cfg", [
    pytest.param(gpt_inference, gpt, CFG, id="dense"),
    pytest.param(gpt_moe_inference, gpt_moe, MOE_CFG, id="moe")])
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
    512-token slots, 4 heads of 32 (block_k 256, two blocks a slot), two
    layers of which the second is banded to 64 tokens; three live rows and a
    freed slot, which counts in the grid and never in the live blocks."""
    from deepspeed_tpu.ops.pallas.decode_attention import (decode_block_k,
                                                           sweep_block_counts)
    from deepspeed_tpu.serving.metrics import ServingMetrics
    assert decode_block_k(512, 4 * 32) == 256
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
