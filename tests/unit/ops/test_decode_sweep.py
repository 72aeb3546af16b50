"""The decode kernel's flat sweep (``ops/pallas/decode_attention.py``) run
under the interpreter, in the fast tier: the serving tests here take the
dense path (no chip, no interpreter), so without this file the driver's run
would compile the sweep for a described v5e and never execute its body."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import gpt
from deepspeed_tpu.ops.pallas.decode_attention import (
    cached_attention, cached_attention_reference, dequantize_kv, quantize_kv)

SMAX = 512


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


@pytest.mark.parametrize("mask", sorted(_MASK_CASES))
@pytest.mark.parametrize("per_row", [False, True], ids=["scalar", "rowpos"])
@pytest.mark.parametrize("slopes", [False, True], ids=["plain", "slopes"])
@pytest.mark.parametrize("window", [None, 48], ids=["global", "window"])
@pytest.mark.parametrize("kind", ["bf16", "int8"])
def test_masked_decode_sweep(pallas_interpret, kind, window, slopes, per_row,
                             mask):
    """The single-token sweep told which rows are live, on layer 2 of a pool
    of 3: live rows match the dense reference AND, bit for bit, the sweep of
    the whole grid that PR 25 left (same blocks, same order); dead rows
    return zeros.  A row's last block is multiplied whole, its dead tail
    masked: what the pool (``-garbage``) or an earlier call's buffers
    (``-twice``) hold past the frontier changes no bit."""
    from tests.unit.ops.dense_grid_decode import dense_grid_decode
    from deepspeed_tpu.ops.pallas.decode_attention import decode_block_k
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
    slope = gpt.alibi_slopes(H) if slopes else None

    def fold(x):
        return x.reshape(x.shape[:3] + (-1,))

    scales, banks = {}, ()
    if kind == "int8":
        (k, k_s), (v, v_s) = quantize_kv(k), quantize_kv(v)
        scales = dict(k_scale=fold(k_s), v_scale=fold(v_s))
        banks = (fold(k_s), fold(v_s))
        ref_k = dequantize_kv(k, k_s, jnp.float32)
        ref_v = dequantize_kv(v, v_s, jnp.float32)
    else:
        ref_k, ref_v = k.astype(jnp.float32), v.astype(jnp.float32)

    layer = 2

    def sweep(lay, act, k, v, scales):
        return cached_attention(q, fold(k), fold(v), pos, window=win,
                                slopes=slope, layer=lay, active=act, **scales)

    got = jax.jit(lambda lay, act: sweep(lay, act, k, v, scales))(
        jnp.int32(layer), jnp.asarray(active))
    got = np.asarray(got, np.float32)
    if mask.endswith("-garbage"):
        beyond = (jnp.arange(Smax)[None, :] >
                  jnp.broadcast_to(pos, (B,))[:, None])[None, :, :, None, None]
        huge = jnp.where(beyond, 1e30, 1.0)
        if kind == "int8":      # the codes stay codes: the scales carry it
            dirty = dict(k_scale=fold(k_s * huge), v_scale=fold(v_s * huge))
            again = jax.jit(lambda lay, act: sweep(lay, act, k, v, dirty))
        else:
            again = jax.jit(lambda lay, act: sweep(
                lay, act, (k * huge).astype(dtype), (v * huge).astype(dtype),
                scales))
        np.testing.assert_array_equal(got, np.asarray(
            again(jnp.int32(layer), jnp.asarray(active)), np.float32))
    if mask.endswith("-twice"):
        inf = {name: jnp.full_like(x, jnp.inf) for name, x in scales.items()}
        first = (jnp.full_like(k, jnp.inf), jnp.full_like(v, jnp.inf)) \
            if kind == "bf16" else (k, v)
        both = jax.jit(lambda lay, act: (sweep(lay, act, *first, inf),
                                         sweep(lay, act, k, v, scales)))
        np.testing.assert_array_equal(got, np.asarray(
            both(jnp.int32(layer), jnp.asarray(active))[1], np.float32))
    assert got.shape == q.shape
    assert not got[~active].any(), "a dead row's result is zeros"
    want = np.asarray(cached_attention_reference(
        q.astype(jnp.float32), ref_k[layer], ref_v[layer], pos,
        window=win, slopes=slope))
    tol = 2e-2 if kind == "bf16" else 2e-5
    np.testing.assert_allclose(got[active], want[active], atol=tol, rtol=tol)
    was = dense_grid_decode(
        q.reshape(B, 1, H * D), fold(k), fold(v), layer, pos, 1.0 / D ** 0.5,
        decode_block_k(Smax, H * D), H, *banks, window=win, slopes=slope)
    was = np.asarray(was, np.float32).reshape(got.shape)
    np.testing.assert_array_equal(got[active], was[active])
