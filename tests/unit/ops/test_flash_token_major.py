"""The flash kernels on token-major rows (PR 43): operands and results are
``[B, S, H*D]``, two heads of 64 to a 128-lane block (one where ``D`` is a
multiple of 128), the packed qkv product read in place through three index
maps, ``dq`` accumulated in VMEM and written once.  Forward and ``jax.grad``
against the dense reference in interpret mode, for the packed call and the
three-array call and every kind of caller; the heads that do not tile a row
fall back to a head a row.  Fast tier, like ``test_flash_causal_strips.py``.
"""

import functools
import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import (flash_attention, flash_attention_packed,
                                      mha_reference)

fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")


@pytest.fixture(autouse=True)
def pallas_interpret(monkeypatch):
    """Route kernels through Pallas interpret mode so the kernel bodies run."""
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    yield


def _dense_window(q, k, v, window):
    """Banded-causal reference: ``0 <= q_pos - k_pos < window``."""
    D = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    dist = jnp.arange(q.shape[1])[:, None] - jnp.arange(k.shape[1])[None, :]
    s = jnp.where((dist >= 0) & (dist < window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


# name -> (B, Sq, Sk, H, D, block, flash kwargs, whether the heads tile the
# row).  One block a head and 2 x 2 blocks (the train cells' shapes, scaled),
# pairs of 64 and whole heads of 128, and every kind of caller.
_CASES = {
    "pairs-one-block": (2, 256, 256, 4, 64, 256, dict(causal=True), True),
    "pairs-2x2-blocks": (2, 256, 256, 4, 64, 128, dict(causal=True), True),
    "d128-one-block": (1, 256, 256, 2, 128, 256, dict(causal=True), True),
    "d128-2x2-blocks": (1, 256, 256, 2, 128, 128, dict(causal=True), True),
    "quads-of-32": (1, 256, 256, 4, 32, 128, dict(causal=True), True),
    "non-causal": (1, 256, 256, 2, 64, 128, dict(causal=False), True),
    "odd-head-count": (1, 256, 256, 3, 64, 128, dict(causal=True), False),
    "one-head-of-64": (2, 256, 256, 1, 64, 128, dict(causal=True), False),
    "head-of-96": (1, 256, 256, 4, 96, 128, dict(causal=True), False),
    "kv_lens": (2, 256, 256, 2, 64, 128, dict(
        causal=False, kv_lens=jnp.asarray([100, 256], jnp.int32)), True),
    "static-window": (1, 512, 512, 2, 64, 128, dict(causal=True, window=200),
                      True),
    "traced-window": (1, 512, 512, 2, 64, 128, dict(causal=True, window=200),
                      True),
    "cross-length": (1, 128, 256, 2, 64, 128, dict(causal=True), True),
}
_THREE_ONLY = ("kv_lens", "static-window", "traced-window", "cross-length")


# the packed call is self-attention with no lengths or window:
# gpt._self_attention hands those callers' three arrays
_CALLS = [(case, form) for case in _CASES for form in ("packed", "three")
          if form == "three" or case not in _THREE_ONLY]


def _operands(case):
    B, Sq, Sk, H, D = _CASES[case][:5]
    ks = jax.random.split(jax.random.PRNGKey(43), 4)
    q = jax.random.normal(ks[0], (B, Sq, H, D), jnp.float32)
    k, v = (jax.random.normal(kk, (B, Sk, H, D), jnp.float32)
            for kk in ks[1:3])
    return q, k, v, jax.random.normal(ks[3], (B, Sq, H, D), jnp.float32)


@functools.lru_cache(maxsize=None)
def _dense_of(case):
    """``(output, (dq, dk, dv))`` of the dense reference for a case's
    operands, in ONE jitted program, once a process: the comparator of both
    forms and both backward forms of a case (op by op it was two thirds of
    a case's time)."""
    kw = _CASES[case][6]
    q, k, v, w = _operands(case)

    def dense(q, k, v):
        if "window" in kw:
            return _dense_window(q, k, v, kw["window"])
        return mha_reference(q, k, v, **{
            n: x for n, x in kw.items() if n != "window"})

    def both(q, k, v):
        out, pull = jax.vjp(dense, q, k, v)
        return out, pull(w)
    out, grads = jax.jit(both)(q, k, v)
    return np.asarray(out), [np.asarray(g) for g in grads]


@pytest.mark.parametrize("two_kernel", [False, True],
                         ids=["fused-bwd", "two-kernel-bwd"])
@pytest.mark.parametrize("case,form", _CALLS)
def test_flash_token_major_matches_reference(monkeypatch, case, form,
                                             two_kernel):
    """Output and every gradient == the dense reference, for both backward
    forms (the fused sweep's dq accumulator over 1 and 2 K blocks)."""
    B, Sq, Sk, H, D, block, kw, tiles = _CASES[case]
    assert fa.token_major(H, D) == tiles
    if two_kernel:
        monkeypatch.setattr(fa, "MAX_FUSED_BWD_NK", 0)
    q, k, v, w = _operands(case)
    blocks = dict(block_q=block, block_k=block)
    dense_out, want = _dense_of(case)

    if form == "packed":
        def kernel(q, k, v):
            qkv = jnp.stack([q, k, v], axis=2).reshape(B, Sq, 3 * H * D)
            return flash_attention_packed(qkv, H, **kw, **blocks).reshape(
                B, Sq, H, D)
    elif case == "traced-window":
        traced = jax.jit(lambda q, k, v, win: flash_attention(
            q, k, v, causal=True, window=win, **blocks))
        kernel = lambda q, k, v: traced(q, k, v, jnp.asarray(kw["window"]))
    else:
        kernel = lambda q, k, v: flash_attention(q, k, v, **kw, **blocks)

    with fa.tally_causal_tiles() as tally:
        out = kernel(q, k, v)
    assert tally[2:] == [1, int(form == "packed" and tiles)]
    np.testing.assert_allclose(np.asarray(out), dense_out, atol=2e-5,
                               rtol=2e-5)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(kernel(*a) * w),
                           argnums=(0, 1, 2)))(q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


@pytest.mark.parametrize("nk", [2, 4])
def test_fused_backward_writes_dq_once_in_the_operands_type(nk):
    """The fused sweep returns three arrays of the operands' shape and type
    for any K extent it takes: no float32 partial of ``[., nk, S, D]``, no
    sum outside the kernel."""
    B, S, H, D, block = 1, 128 * nk, 2, 64, 128
    assert nk <= fa.MAX_FUSED_BWD_NK
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.bfloat16)
               for kk in jax.random.split(jax.random.PRNGKey(5), 3))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block)
            .astype(jnp.float32)), argnums=(0, 1, 2)))(q, k, v)
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in calls] == ["flash_fwd", "flash_bwd"]
    assert [(v.aval.shape, v.aval.dtype) for v in calls[1].outvars] == \
        [((B, S, H * D), jnp.bfloat16)] * 3
    # outside the kernels: reshapes and the loss's own sum of o
    assert sum(e.primitive.name == "reduce_sum" for e in jaxpr.eqns) == 1
    g = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block)
        .astype(jnp.float32)), argnums=(0, 1, 2))(q, k, v)
    r = jax.grad(lambda q, k, v: jnp.sum(mha_reference(
        q, k, v, causal=True).astype(jnp.float32)), argnums=(0, 1, 2))(
            q, k, v)
    for a, b in zip(g, r):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=0.08,
                                   rtol=0.08)


@pytest.mark.parametrize("form", ["packed", "three"])
def test_flash_token_major_on_dp2_tp2_mesh(form):
    """Under a mesh the call sits in a ``shard_map``: rows over the data
    axes; the three-array call's heads over the model axis, two to a shard,
    which pair; the packed product's columns stay whole."""
    from deepspeed_tpu.parallel.mesh import (DP_GROUP, MODEL_AXIS,
                                             ParallelDims, initialize_mesh)
    mm = initialize_mesh(ParallelDims(dp=2, tp=2), devices=jax.devices()[:4])
    B, S, H, D = 4, 128, 4, 64
    rng = np.random.default_rng(0)
    q, k, v = (jax.device_put(
        jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32),
        mm.sharding(DP_GROUP, None, MODEL_AXIS, None)) for _ in range(3))
    w = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    if form == "packed":
        def kernel(q, k, v):
            qkv = jnp.stack([q, k, v], axis=2).reshape(B, S, 3 * H * D)
            return flash_attention_packed(
                qkv, H, block_q=128, block_k=128).reshape(B, S, H, D)
    else:
        kernel = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                                 block_q=128, block_k=128)
    dense = lambda q, k, v: mha_reference(q, k, v, causal=True)
    assert "shard_map" in str(jax.make_jaxpr(kernel)(q, k, v))
    np.testing.assert_allclose(np.asarray(jax.jit(kernel)(q, k, v)),
                               np.asarray(dense(q, k, v)), atol=2e-5,
                               rtol=2e-5)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(kernel(*a) * w),
                           argnums=(0, 1, 2)))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    for g, r, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=5e-5,
                                   rtol=5e-5, err_msg=f"d{name}")


def test_gpt_block_hands_the_kernels_the_packed_product():
    """A GPT-2-like block (learned positions, no window) reaches the kernels
    through the packed call and matches the three-array path a rotary model
    takes; a rotary block, a windowed one and one whose heads a mesh axis
    splits keep three arrays."""
    import dataclasses
    from deepspeed_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=128, max_seq_len=1024, n_layer=1, n_head=2,
                        d_model=128, dtype=jnp.float32, vocab_round_to=128)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    p = jax.tree_util.tree_map(lambda x: x[0], params["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 1024, 128), jnp.float32)

    def tally_of(config, **kw):
        with fa.tally_causal_tiles() as tally:
            out = gpt._attn_residual(x, p, config, **kw)
        return out, tally[2:]

    out, calls = tally_of(cfg)
    assert calls == [1, 1]
    q, k, v = gpt.qkv_proj(x, p, cfg)
    want = gpt.attn_out_residual(x, mha_reference(q, k, v, causal=True), p,
                                 cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-4,
                               rtol=2e-4)
    _, calls = tally_of(dataclasses.replace(cfg, pos_embed="rotary"))
    assert calls == [1, 0]
    _, calls = tally_of(cfg, window=jnp.asarray(2048, jnp.int32))
    assert calls == [1, 0]
