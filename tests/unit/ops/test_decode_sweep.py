"""The decode kernel's flat sweep (``ops/pallas/decode_attention.py``) run
under the interpreter, in the fast tier: the serving tests here take the
dense path (no chip, no interpreter), so without this file the driver's run
would compile the sweep for a described v5e and never execute its body."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import gpt
from deepspeed_tpu.ops.pallas.decode_attention import (
    cached_attention, cached_attention_reference, chunk_block_k,
    decode_block_k, decode_sweep, dequantize_kv, quantize_kv)

SMAX = 512
# the sweeps below keep two blocks a slot: the rule gives this narrow row
# (4 heads of 32) one block of 512 since PR 46, and a work list built for
# 256 hands the kernel 256 (``sweep_block_k``)
BLOCK = 256


@pytest.fixture
def pallas_interpret(monkeypatch):
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")


# the flat sweep under the tick's ``active`` mask: rows (frontier, live?) of a
# pool of four 512-token slots, block_k 256, so frontiers sit on both sides
# of the block edge; scalar-``pos`` cases take the first row's frontier
_MASK_CASES = {
    "all-live": [(7, True), (255, True), (256, True), (300, True)],
    "some-dead": [(130, True), (0, False), (400, True), (0, False)],
    "all-dead": [(0, False), (300, False), (0, False), (511, False)],
    "one-live-at-0": [(0, True), (300, False), (0, False), (511, False)],
    "row-at-the-end": [(SMAX - 1, True), (40, True), (0, False),
                       (SMAX - 1, True)],
}
# every edge of the rule a row's last block is copied by: the copy's 16-row
# tile, half a block, the 256-token block, the slot's end; dead rows among
# them
_EDGES = [(0, True), (14, True), (300, False), (15, True), (16, True),
          (127, True), (128, True), (255, True), (0, False), (256, True),
          (257, True), (511, True), (SMAX - 1, True)]
_MASK_CASES.update({
    "tile-edges": _EDGES,
    # the pool holds 1e30-scaled rows in every token beyond a row's frontier
    "tile-edges-garbage": _EDGES,
    # a call on a pool of infinities runs first, in the same program
    "tile-edges-twice": _EDGES,
})


def _repeats(slopes, per_row, mask) -> bool:
    """The cases of ``test_masked_decode_sweep`` that repeat another's kind
    and run only with ``-m slow`` (tier 1's wall, PR 51: 40 of 128): the
    ALiBi bias with a scalar frontier (the bias with per-row frontiers and
    the scalar frontier without it both stay), and the bias over the two
    masks with at most one live row."""
    return slopes and (not per_row or mask in ("all-dead", "one-live-at-0"))


@pytest.mark.parametrize("kind,window,slopes,per_row,mask", [
    pytest.param(kind, window, slopes, per_row, mask,
                 id="-".join([kind, "window" if window else "global",
                              "slopes" if slopes else "plain",
                              "rowpos" if per_row else "scalar", mask]),
                 marks=[pytest.mark.slow] * _repeats(slopes, per_row, mask))
    # the mask varies fastest: the cases of one program (what is static: the
    # rest, and a mask's number of rows) run one after another
    for kind in ("bf16", "int8") for window in (None, 48)
    for slopes in (False, True) for per_row in (False, True)
    for mask in sorted(_MASK_CASES)])
def test_masked_decode_sweep(pallas_interpret, kind, window, slopes, per_row,
                             mask):
    """The single-token sweep told which rows are live, on layer 2 of a pool
    of 3: live rows match the dense reference AND, bit for bit, the sweep of
    the whole grid that PR 25 left (same blocks, same order); dead rows
    return zeros.  A row's last block is multiplied whole, its dead tail
    masked: what the pool (``-garbage``) or an earlier call's buffers
    (``-twice``) hold past the frontier changes no bit."""
    _masked_decode_sweep(kind, window, slopes, per_row, mask, BLOCK)


@pytest.mark.parametrize("mask", sorted(_MASK_CASES))
@pytest.mark.parametrize("window", [None, 48], ids=["global", "window"])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_masked_decode_sweep_at_the_rules_block(pallas_interpret, kind,
                                                window, mask):
    """The same rows with no work list handed in: ``cached_attention``
    builds one for the rule's block, the whole 512-token slot of this
    128-wide row, and the kernel takes its block from it."""
    assert decode_block_k(SMAX, 4 * 32) == 512
    _masked_decode_sweep(kind, window, False, True, mask, None)


def _fold(x):
    return x.reshape(x.shape[:3] + (-1,))


@functools.lru_cache(maxsize=None)
def _sweep_programs(kind, windowed, slopes, per_row, B, block):
    """``(sweep, twice, reference, dense grid)``, each ONE jitted program a
    process for what is static here (the banks' type, a window or none,
    slopes or none, a scalar or a per-row frontier, the pool's rows, the
    work list's block): a case's frontiers, live flags, window, banks and
    garbage are ARGUMENTS, so the cases of a mask, its ``-garbage`` and its
    ``-twice`` trace, lower and compile nothing of their own."""
    from tests.unit.ops.dense_grid_decode import dense_grid_decode
    H, D = 4, 32
    slope = gpt.alibi_slopes(H) if slopes else None

    def sweep(lay, act, pos, win, q, k, v, scales):
        work = block and decode_sweep(pos, B, SMAX, block, act, win)
        return cached_attention(q, _fold(k), _fold(v), pos, window=win,
                                slopes=slope, layer=lay, active=act,
                                sweep=work, **scales)

    def twice(lay, act, pos, win, q, first, inf, k, v, scales):
        return (sweep(lay, act, pos, win, q, *first, inf),
                sweep(lay, act, pos, win, q, k, v, scales))

    def reference(q, k, v, pos, win):
        return cached_attention_reference(q, k, v, pos, window=win,
                                          slopes=slope)

    def dense(q, k, v, lay, pos, win, banks):
        return dense_grid_decode(
            q.reshape(B, 1, H * D), _fold(k), _fold(v), lay, pos,
            1.0 / D ** 0.5, block or decode_block_k(SMAX, H * D), H, *banks,
            window=win, slopes=slope)
    return tuple(jax.jit(f) for f in (sweep, twice, reference, dense))


def _masked_decode_sweep(kind, window, slopes, per_row, mask, block):
    """``block``: the work list's, built in the program and handed to the
    kernel; None leaves list and block to ``cached_attention``."""
    rows = _MASK_CASES[mask]
    L, B, Smax, H, D = 3, len(rows), SMAX, 4, 32
    dtype = jnp.bfloat16 if kind == "bf16" else jnp.float32
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(kq, (B, 1, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (L, B, Smax, H, D), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, (L, B, Smax, H, D), jnp.float32).astype(dtype)
    pos = jnp.asarray([p for p, _ in rows] if per_row else rows[0][0],
                      jnp.int32)
    active = np.asarray([a for _, a in rows])
    win = None if window is None else jnp.int32(window)
    sweep, twice, reference, dense = _sweep_programs(
        kind, window is not None, slopes, per_row, B, block)

    scales, banks = {}, ()
    if kind == "int8":
        (k, k_s), (v, v_s) = quantize_kv(k), quantize_kv(v)
        scales = dict(k_scale=_fold(k_s), v_scale=_fold(v_s))
        banks = (_fold(k_s), _fold(v_s))
        ref_k = dequantize_kv(k, k_s, jnp.float32)
        ref_v = dequantize_kv(v, v_s, jnp.float32)
    else:
        ref_k, ref_v = k.astype(jnp.float32), v.astype(jnp.float32)

    layer = jnp.int32(2)
    live = jnp.asarray(active)
    got = np.asarray(sweep(layer, live, pos, win, q, k, v, scales),
                     np.float32)
    if mask.endswith("-garbage"):
        beyond = (jnp.arange(Smax)[None, :] >
                  jnp.broadcast_to(pos, (B,))[:, None])[None, :, :, None, None]
        huge = jnp.where(beyond, 1e30, 1.0)
        if kind == "int8":      # the codes stay codes: the scales carry it
            again = sweep(layer, live, pos, win, q, k, v, dict(
                k_scale=_fold(k_s * huge), v_scale=_fold(v_s * huge)))
        else:
            again = sweep(layer, live, pos, win, q, (k * huge).astype(dtype),
                          (v * huge).astype(dtype), scales)
        np.testing.assert_array_equal(got, np.asarray(again, np.float32))
    if mask.endswith("-twice"):
        inf = {name: jnp.full_like(x, jnp.inf) for name, x in scales.items()}
        first = (jnp.full_like(k, jnp.inf), jnp.full_like(v, jnp.inf)) \
            if kind == "bf16" else (k, v)
        np.testing.assert_array_equal(got, np.asarray(twice(
            layer, live, pos, win, q, first, inf, k, v, scales)[1],
            np.float32))
    assert got.shape == q.shape
    assert not got[~active].any(), "a dead row's result is zeros"
    want = np.asarray(reference(q.astype(jnp.float32), ref_k[2], ref_v[2],
                                pos, win))
    tol = 2e-2 if kind == "bf16" else 2e-5
    np.testing.assert_allclose(got[active], want[active], atol=tol, rtol=tol)
    was = np.asarray(dense(q, k, v, layer, pos, win, banks),
                     np.float32).reshape(got.shape)
    np.testing.assert_array_equal(got[active], was[active])


# --------------------------------------------------- the block's size (PR 46)

# width of the row: the sweep's block at slots of 96, 512, 1,024 and 16,384
# tokens.  About 2**18 elements a step: a row of 256 or narrower takes the
# largest block there is, a 1,024-wide one 256, wider ones the floor.
_SLOTS = (96, 512, 1024, 16384)
_SWEEP_BLOCK = {
    128: (None, 512, 1024, 1024),
    256: (None, 512, 1024, 1024),    # Nemotron's grouped row: 2 heads of 128
    512: (None, 512, 512, 512),
    768: (None, 256, 256, 256),      # GPT-2 small
    1024: (None, 256, 256, 256),     # gpt2-medium, Granite's grouped row
    2048: (None, 128, 128, 128),     # OPT-1.3B
}


@pytest.mark.parametrize("smax", _SLOTS)
@pytest.mark.parametrize("width", sorted(_SWEEP_BLOCK))
def test_the_sweeps_block_follows_the_rows_width(width, smax):
    """``decode_block_k`` is one function of the slot's length and the row's
    width; the chunk kernel's key block follows the slot's length alone (a
    step takes one key-value head, 128 wide whatever the row): the largest
    up to 1,024 keys that leaves the row two blocks."""
    want = _SWEEP_BLOCK[width][_SLOTS.index(smax)]
    assert decode_block_k(smax, width) == want
    assert chunk_block_k(smax) == \
        (None, 256, 512, 1024)[_SLOTS.index(smax)]
    if want:
        assert smax % want == 0 and (want * width <= 1 << 18 or want == 128)


def _tick_families():
    from deepspeed_tpu.models import hybrid_ssm_moe
    M, E, A = "mamba", "experts", "attention"
    small = dict(vocab_size=256, n_layer=2, n_head=4, d_model=128,
                 dtype=jnp.float32)
    hybrid = dict(vocab_size=256, d_model=64, ssm_heads=4, ssm_head_dim=32,
                  ssm_state=16, ssm_chunk=16, head_dim=128, n_experts=4,
                  experts_per_token=2, d_expert=32, d_shared=32,
                  dtype=jnp.float32)
    return {
        # 4 heads of 32: a 128-wide row
        "dense-128": (gpt, gpt.GPTConfig(max_seq_len=512, **small), 512,
                      None),
        "dense-128-int8": (gpt, gpt.GPTConfig(max_seq_len=512, **small), 512,
                           "int8"),
        # GPT-Neo's alternating band: a work list a layer, built in one go
        "dense-128-banded": (gpt, gpt.GPTConfig(
            max_seq_len=1024, local_attention_window=64, **small), 1024,
            None),
        # 16 heads of 64, both gpt2-medium cells' row
        "dense-1024": (gpt, gpt.GPTConfig(
            max_seq_len=1024, **{**small, "n_head": 16, "d_model": 1024,
                                 "n_layer": 1}), 1024, None),
        # Granite's grouped row (8 key-value heads of 128) and Nemotron's
        # (2), the second a single-part stack with two attention layers
        "hybrid-1024": (hybrid_ssm_moe, hybrid_ssm_moe.HybridSSMMoEConfig(
            max_seq_len=1024, layer_types=(M, A, M), n_head=16, n_kv_head=8,
            **hybrid), 1024, None),
        "hybrid-256": (hybrid_ssm_moe, hybrid_ssm_moe.HybridSSMMoEConfig(
            max_seq_len=1024, layer_types=(M, E, A, E, M, A, E), n_head=8,
            n_kv_head=2, mixer_ffn=False, **hybrid), 1024, None),
    }


@pytest.mark.parametrize("family", ["dense-128", "dense-128-int8",
                                    "dense-128-banded", "dense-1024",
                                    "hybrid-1024", "hybrid-256"])
def test_a_tick_hands_the_kernel_the_block_its_work_list_was_built_for(
        pallas_interpret, family):
    """``Family.sweep_plan`` (what the batcher counts live blocks and
    streamed tokens by), ``gpt_inference._sweeps`` (the tick's work list)
    and ``cached_attention`` (the kernel) of a family agree on the block,
    and it is the rule's for the family's row: a list built for one block
    and a kernel built for another would read wrong rows in silence."""
    from deepspeed_tpu.models import cache_family
    from deepspeed_tpu.models.gpt_inference import cache_row
    from tests.unit.ops.traced_sweeps import sweep_calls
    model, cfg, smax, kv_dtype = _tick_families()[family]
    slots = 3
    fam = cache_family(cfg)
    params = jax.eval_shape(lambda: model.init(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: fam.init_cache(cfg, slots, smax,
                                                  kv_dtype=kv_dtype))
    rows = jax.ShapeDtypeStruct((slots,), jnp.int32)
    tick = jax.make_jaxpr(
        lambda p, c, tok, lengths, active: fam.decode_step(
            p, tok, cfg, c, lengths=lengths, active=active))(
        params, cache, rows, rows, jax.ShapeDtypeStruct((slots,), jnp.bool_))
    width = cache_row(cfg)[0]
    want = decode_block_k(smax, width)
    assert want == _SWEEP_BLOCK[width][_SLOTS.index(smax)]
    assert fam.sweep_plan(cfg, smax).block_k == want
    calls = sweep_calls(tick.jaxpr, slots, smax, width)
    assert calls and all(c[1:] == (want, want) for c in calls), calls
    assert {c[0] for c in calls} == {
        "gqa_decode_attention" if family.startswith("hybrid")
        else "decode_attention"}


@pytest.mark.parametrize("block", [256, 1024])
def test_the_grouped_sweep_at_agent_sats_row(pallas_interpret, block):
    """``_gqa_decode`` at ``nemotron3n-serve-agent-sat``'s geometry, slots of
    16,384 tokens of a 256-wide row under 32 query heads, in blocks of 256
    (the size until PR 46) and of 1,024 (the rule's): ragged rows whose
    frontiers sit on both sides of the first 1,024-token edge and deep in
    the slot (6,100 of 16,384: past five blocks of 1,024; it stood at 12,100
    until PR 59, twice the interpreter's steps and no other kind of block), a
    dead row among them, against the dense formula."""
    from deepspeed_tpu.ops.pallas.decode_attention import _gqa_decode
    smax, Hq, Hkv, D = 16384, 32, 2, 128
    G = Hq // Hkv
    assert decode_block_k(smax, Hkv * D) == 1024
    pos = jnp.asarray([1022, 1023, 7000, 1024, 1025, 6100], jnp.int32)
    active = jnp.asarray([True, True, False, True, True, True])
    B = len(pos)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(46), 3)
    q = jax.random.normal(kq, (B, Hq, D), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(kk, (2, B, smax, Hkv * D),
                          jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(kv, k.shape, jnp.float32).astype(jnp.bfloat16)
    got = jax.jit(lambda q, k, v: _gqa_decode(
        q, k, v, 1, pos, decode_sweep(pos, B, smax, block, active),
        D ** -0.5, block, G))(q, k, v)
    got = np.asarray(got, np.float32).reshape(B, Hq, D)

    # one query head against its key-value head (no head is repeated out to
    # the sixteen that share it), in ONE program for the 32
    head = jax.jit(lambda qh, kh, vh: cached_attention_reference(
        qh[:, None, None].astype(jnp.float32), kh, vh, pos,
        D ** -0.5)[:, 0, 0])
    one = lambda x, kv_head: x[1].reshape(B, smax, Hkv, D)[
        :, :, kv_head][:, :, None].astype(jnp.float32)
    banks = [(one(k, g), one(v, g)) for g in range(Hkv)]
    want = np.stack([np.asarray(head(q[:, h], *banks[h // G]))
                     for h in range(Hq)], 1)
    live = np.asarray(active)
    # results of 0.035 in the mean, 0.23 at most; bf16 probabilities read
    # 6e-4 off the float32 formula
    np.testing.assert_allclose(got[live], want[live], atol=2e-3, rtol=0)
