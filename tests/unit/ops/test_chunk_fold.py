"""The chunk-attention kernel's folded step (PR 49): a grid step takes a
key-value head's whole GROUP of query heads against one key block.
Interpret mode, float32, against ``cached_attention_reference``; the live
range a step's block is clamped to against a brute-force walk of the mask."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.ops.pallas.decode_attention import (
    cached_attention, cached_attention_reference, chunk_block_k,
    chunk_block_q, ring_attention)

WINDOW = 1024      # the served window: a ring of 1,024 cells
SQ = 256           # a chunk: one query tile up to 8 heads a group, two at 16


@pytest.fixture
def pallas_interpret(monkeypatch):
    """Route kernels through Pallas interpret mode so the kernel bodies run."""
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")


def _rows(key, *shape):
    return jax.random.normal(key, shape, jnp.float32)


def _grouped(x, G):
    """[B, S, Hkv, D] -> [B, S, Hkv*G, D]: the reference reads a key-value
    head per query head."""
    return jnp.repeat(x, G, axis=2)


def _pos(ragged, base):
    """A batch of two: both rows at ``base``, or the second row ragged, 300
    positions behind (another block, another place inside it)."""
    return jnp.asarray([base, base - 300], jnp.int32) if ragged \
        else jnp.asarray(base, jnp.int32)


# The kernel's call and its comparator as ONE jitted program each: what is
# static is the group size (and, to the tracer, the operands' shapes and
# whether there is a ``valid_from``); the frontiers, the first real keys and
# the rows are arguments, so the cases that differ in where a frontier
# stands share a trace, a lowering and a compile.

@functools.partial(jax.jit, static_argnames="G")
def _plain_pair(q, k, v, pos, valid_from, G):
    return (cached_attention(q, k, v, pos, kv_heads=k.shape[2],
                             valid_from=valid_from),
            cached_attention_reference(q, _grouped(k, G), _grouped(v, G),
                                       pos, valid_from=valid_from))


@functools.partial(jax.jit, static_argnames=("G", "window"))
def _ring_pair(q, ring_k, ring_v, fresh_k, fresh_v, hist_k, hist_v, pos, G,
               window):
    return (ring_attention(q, ring_k[None], ring_v[None], fresh_k, fresh_v,
                           pos, window, 0, kv_heads=hist_k.shape[2]),
            cached_attention_reference(q, _grouped(hist_k, G),
                                       _grouped(hist_v, G), pos,
                                       window=window))


def _plain(G, D, pos, valid_from, seed=0):
    """A chunk over whole rows of 2,048: ``(got, want)``."""
    B, Smax, Hkv = 2, 2048, 2
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = _rows(kq, B, SQ, Hkv * G, D)
    k, v = _rows(kk, B, Smax, Hkv, D), _rows(kv, B, Smax, Hkv, D)
    return _plain_pair(q, k, v, pos, valid_from, G)


def _ring(G, D, pos, seed=0, window=WINDOW, sq=SQ, history=None):
    """A chunk beside a ring (``ring_attention``): position ``p`` of the
    history in cell ``p mod R``, the cells no token has reached (and, once
    lapped, nothing else) holding junk: ``(got, want)``, the reference a
    banded pass over the history laid out whole (``history`` tokens of it
    where given: what lies past a chunk's last query no query sees, and one
    length for every frontier is one program)."""
    B, Hkv, R = 2, 2, window
    p = np.broadcast_to(np.asarray(pos), (B,))
    S = history or int(p.max()) + sq
    kq, kk, kv, kj = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = _rows(kq, B, sq, Hkv * G, D)
    hist_k, hist_v = _rows(kk, B, S, Hkv, D), _rows(kv, B, S, Hkv, D)
    ring_k = np.asarray(_rows(kj, B, R, Hkv * D)) * 50.0      # junk, loud
    ring_v = ring_k.copy()
    for b in range(B):
        at = np.arange(max(p[b] - R, 0), p[b])
        ring_k[b, at % R] = np.asarray(hist_k)[b, at].reshape(len(at), -1)
        ring_v[b, at % R] = np.asarray(hist_v)[b, at].reshape(len(at), -1)
    fresh_k, fresh_v = (jnp.stack([h[b, p[b]:p[b] + sq] for b in range(B)])
                        for h in (hist_k, hist_v))
    return _ring_pair(q, jnp.asarray(ring_k), jnp.asarray(ring_v), fresh_k,
                      fresh_v, hist_k, hist_v, jnp.asarray(pos), G, window)


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all()
    # the single-token sweep's tests' tolerance in float32
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("D", [128, 64])
@pytest.mark.parametrize("past", [0, 1], ids=["on-edge", "one-past"])
@pytest.mark.parametrize("ragged", [False, True], ids=["scalar", "ragged"])
@pytest.mark.parametrize("band", ["none", "unlapped", "lapped", "valid_from"])
@pytest.mark.parametrize("G", [1, 4, 8, 16])
def test_the_folded_step_matches_the_reference(pallas_interpret, G, band,
                                               ragged, past, D):
    """One body for every group size: ``G`` query heads a key-value head
    under one another, whole rows or a ring's band, a scalar or a per-row
    frontier standing on a key block's edge or one past it."""
    if band == "none":
        got, want = _plain(G, D, _pos(ragged, 1024 + past), None, seed=G)
    elif band == "valid_from":
        # the first real key inside a block, and, ragged, on a block's edge
        first = jnp.asarray([300, 256], jnp.int32) if ragged \
            else jnp.asarray(300, jnp.int32)
        got, want = _plain(G, D, _pos(ragged, 1024 + past), first, seed=G)
        # the reference hides the same keys, so the comparison is whole
    else:
        base = (512 if band == "unlapped" else 2048) + past
        got, want = _ring(G, D, np.asarray(_pos(ragged, base)), seed=G,
                          history=2048 + 1 + SQ)
    _close(got, want)


@pytest.mark.parametrize("G,dtype", [(8, jnp.float32), (8, jnp.bfloat16),
                                     (16, jnp.float32)])
def test_a_window_layers_call_at_the_cells_shape(pallas_interpret, G, dtype):
    """``code-sat``'s window layers: a chunk of 1,024 beside a ring of 1,024
    once lapped, 2,048 keys in the call; in bf16 the products are the
    tick's (bf16 x bf16 in float32, probabilities rounded to bf16)."""
    if dtype == jnp.float32:
        return _close(*_ring(G, 128, np.asarray([3072, 3072 + 511]), seed=3,
                             sq=1024))
    B, Hkv, R, sq = 2, 2, WINDOW, 1024
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    q = _rows(keys[0], B, sq, Hkv * G, 128).astype(dtype)
    k = _rows(keys[1], B, R + sq, Hkv, 128).astype(dtype)
    v = _rows(keys[2], B, R + sq, Hkv, 128).astype(dtype)
    pos = jnp.full((B,), R, jnp.int32)
    got = cached_attention(q, k, v, pos, window=WINDOW, kv_heads=Hkv,
                           valid_from=jnp.zeros((B,), jnp.int32))
    want = cached_attention_reference(
        *(x.astype(jnp.float32) for x in (q, _grouped(k, G), _grouped(v, G))),
        pos, window=WINDOW)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("G", [1, 4])
def test_an_edge_block_that_masks_some_rows_wholly(pallas_interpret, G):
    """A band of 32 keys under a chunk that straddles two key blocks: the
    first block is live (the early rows see it) and FULLY masked for the
    late rows, whose running max must stay at its floor and not -inf."""
    B, Smax, Hkv, D, sq = 2, 512, 2, 128, 128
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(4), 3)
    q = _rows(kq, B, sq, Hkv * G, D)
    k, v = _rows(kk, B, Smax, Hkv, D), _rows(kv, B, Smax, Hkv, D)
    pos = jnp.asarray([200, 230], jnp.int32)
    # one query tile, and both of its key blocks live
    assert (chunk_block_k(Smax), chunk_block_q(sq, G, 256)) == (256, sq)
    assert tuple(int(b) for b in da._chunk_live_range(
        200, sq, 256, 32, None)) == (0, 1)
    got = cached_attention(q, k, v, pos, window=jnp.int32(32), kv_heads=Hkv)
    want = cached_attention_reference(q, _grouped(k, G), _grouped(v, G), pos,
                                      window=32)
    _close(got, want)


# -------------------------------------------------------- a tile's live range

def _brute(pos, Sq, Smax, block_q, block_k, window, first):
    """The key blocks in which any pair of each query tile is visible, by
    walking the mask itself."""
    q = pos + np.arange(Sq)[:, None]
    k = np.arange(Smax)[None, :]
    seen = (q - k >= 0)
    if window is not None:
        seen &= (q - k < window)
    if first is not None:
        seen &= (k >= first)
    return [[ki for ki in range(Smax // block_k)
             if seen[qi * block_q:(qi + 1) * block_q,
                     ki * block_k:(ki + 1) * block_k].any()]
            for qi in range(Sq // block_q)]


_CALLS = [
    # pos, Sq, Smax, block_q, block_k, window, first
    (0, 1024, 8192, 256, 256, None, None),
    (3072, 1024, 8192, 128, 1024, None, None),
    (3073, 1024, 8192, 128, 512, None, None),
    (1024, 1024, 2048, 128, 1024, 1024, 0),         # a lapped ring
    (1024, 1024, 2048, 128, 1024, 1024, 1024),      # a ring no token reached
    (1024, 1024, 2048, 256, 256, 1024, 1024 - 300),  # ... and one part full
    (1024, 256, 1280, 128, 256, 1024, 511),
    (5120, 1024, 16384, 64, 1024, None, None),      # agent-sat: G 16
    (1536, 512, 5120, 256, 1024, None, None),       # rag-sat: G 4
    (200, 128, 512, 128, 256, 32, None),            # a band under a block
    (37, 8, 256, 8, 256, 16, None),
    (700, 128, 1024, 128, 128, 300, 650),
]


@pytest.mark.parametrize("call", _CALLS, ids=[
    "-".join(str(x) for x in c) for c in _CALLS])
def test_a_tiles_live_range_is_the_masks(call):
    """``_chunk_live_range``, which says both which steps of a tile compute
    and which block a dead step's index clamps to: the blocks in which the
    mask, walked pair by pair, shows any pair of the tile."""
    pos, Sq, Smax, block_q, block_k, window, first = call
    for qi, live in enumerate(_brute(*call)):
        lo, hi = da._chunk_live_range(pos + qi * block_q, block_q, block_k,
                                      window, first)
        assert list(range(int(lo), min(int(hi), Smax // block_k - 1) + 1)) \
            == live


# ------------------------------------- a chunk over a row of the pool (PR 63)

@functools.partial(jax.jit, static_argnames=("Hkv", "windowed"))
def _row_pair(q, k, v, pos, rows, window, Hkv, windowed):
    """A chunk over rows of layer 1 of a pool of two layers: the call with
    ``row=`` beside the reference over those rows sliced out."""
    B, G = q.shape[0], q.shape[2] // Hkv
    window = window if windowed else None
    at = jnp.broadcast_to(rows, (B,))
    own = [x[1][at].reshape(B, x.shape[2], Hkv, -1) for x in (k, v)]
    return (cached_attention(q, k, v, pos, layer=jnp.int32(1), row=rows,
                             kv_heads=Hkv, window=window),
            cached_attention_reference(q, _grouped(own[0], G),
                                       _grouped(own[1], G), pos,
                                       window=window))


@pytest.mark.parametrize("band", [None, 200, 1100], ids=[
    "whole_row", "band_under_a_block", "band_over_a_block"])
@pytest.mark.parametrize("pos", ["scalar", "per_row"])
@pytest.mark.parametrize("Hkv,G,D,sq", [(16, 1, 64, 256), (16, 1, 64, 128),
                                        (4, 8, 128, 128)],
                         ids=["H16_D64", "H16_D64_a_chunk", "Hkv4_D128_G8"])
def test_a_chunk_over_a_row_of_the_pool_where_it_lies(pallas_interpret, Hkv,
                                                      G, D, sq, pos, band):
    """``cached_attention(row=)`` through ``_row_chunk``: the folded banks
    ``[L, slots, S, Hkv * D]`` addressed ``(layer, row)`` by the index maps
    and the queries folded too, against the reference over the same rows
    sliced out: two ungrouped heads of 64 in a lane block of 128
    (``gpt2-medium``'s 16), and a key-value head of 128 with its group of 8
    (the grouped families' full layers), at a frontier one key past a
    block's edge and, per row, inside another block; under no band, a band
    shorter and one longer than a key block.  The rows are not in order
    and one is the pool's last: a call that read row ``b`` would fail."""
    Smax, slots = 2048, 5
    per_row = pos == "per_row"
    B = 2 if per_row else 1
    keys = jax.random.split(jax.random.PRNGKey(Hkv + sq), 3)
    q = _rows(keys[0], B, sq, Hkv * G, D)
    k, v = (_rows(key, 2, slots, Smax, Hkv * D) for key in keys[1:])
    assert da.row_chunk_lanes(Hkv, G, D) == ((128, 128) if D == 64
                                             else (128, 1024))
    rows = jnp.asarray([4, 1], jnp.int32) if per_row else jnp.int32(3)
    at = jnp.asarray([1025, 725], jnp.int32) if per_row else jnp.int32(1025)
    _close(*_row_pair(q, k, v, at, rows, jnp.int32(band or 0), Hkv,
                      band is not None))


def test_what_the_row_kernel_does_not_serve_reads_its_row_out(
        pallas_interpret, monkeypatch):
    """A call with ``row=`` that the row kernel does not serve goes the way
    of a call without, over its rows sliced out of the layer: an int8
    cache's codes and scales, ALiBi's slopes, grouped heads of 64 (their
    queries would want a lane shift); and ``ring_attention`` (its
    ``valid_from``, a pool of its own unrolling) never names a row.  Each
    still runs the heads-major kernel (``_chunk``) and agrees with the
    reference."""
    calls = []
    chunk, row_chunk = da._chunk, da._row_chunk
    monkeypatch.setattr(da, "_chunk", lambda *a, **kw: (
        calls.append("heads_major"), chunk(*a, **kw))[1])
    monkeypatch.setattr(da, "_row_chunk", lambda *a, **kw: (
        calls.append("row"), row_chunk(*a, **kw))[1])
    Smax, slots, sq = 512, 3, 128
    keys = jax.random.split(jax.random.PRNGKey(9), 3)
    pos, row = jnp.asarray([130], jnp.int32), jnp.int32(2)

    def pool(Hkv, D):
        return (_rows(key, 2, slots, Smax, Hkv * D) for key in keys[1:])

    # grouped heads of 64
    q = _rows(keys[0], 1, sq, 8, 64)
    k, v = pool(2, 64)
    assert da.row_chunk_lanes(2, 4, 64) is None
    got = cached_attention(q, k, v, pos, layer=1, row=row, kv_heads=2)
    own = [x[1, 2][None].reshape(1, Smax, 2, 64) for x in (k, v)]
    _close(got, cached_attention_reference(
        q, _grouped(own[0], 4), _grouped(own[1], 4), pos))
    # ALiBi
    q = _rows(keys[0], 1, sq, 4, 64)
    k, v = pool(4, 64)
    slopes = jnp.asarray([0.5, 0.25, 0.125, 0.0625], jnp.float32)
    got = cached_attention(q, k, v, pos, layer=1, row=row, slopes=slopes)
    own = [x[1, 2][None].reshape(1, Smax, 4, 64) for x in (k, v)]
    _close(got, cached_attention_reference(q, *own, pos, slopes=slopes))
    # the int8 cache
    codes = [da.quantize_kv(x.reshape(2, slots, Smax, 4, 64)) for x in (k, v)]
    got = cached_attention(
        q, *(c.reshape(2, slots, Smax, -1) for c, _ in codes), pos, layer=1,
        row=row, k_scale=codes[0][1][..., 0], v_scale=codes[1][1][..., 0])
    own = [da.dequantize_kv(c, s, jnp.float32)[1, 2][None] for c, s in codes]
    _close(got, cached_attention_reference(q, *own, pos))
    assert calls == ["heads_major"] * 3
    # a plain call with a row is the row kernel's; a ring's is not
    cached_attention(q, k, v, pos, layer=1, row=row)
    _ring(1, 64, np.asarray([700, 700]), seed=1, window=256, sq=128)
    assert calls[3:] == ["row", "heads_major"]
    # and one token names no row: it sweeps every row by its work list
    with pytest.raises(NotImplementedError):
        cached_attention(q[:, :1], k, v, pos, layer=1, row=row)
