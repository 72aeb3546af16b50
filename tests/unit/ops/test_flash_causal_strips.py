"""The flash kernels' causal row strips (PR 37): a DIAGONAL block walked in
strips that leave out the sub-tiles above the diagonal, forward and backward,
against the dense reference in interpret mode; the callers the strips must
leave on the whole-block body; and ``causal_tile_plan`` against what an
instrumented run visits.  Fast tier (``test_pallas_kernels.py`` is the slow
one): these guard the train cells' kernels on every PR.
"""

import importlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture()
def pallas_interpret(monkeypatch):
    """Route kernels through Pallas interpret mode so the kernel bodies run."""
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    yield


# block, S -> the strip heights the rule hands the forward and the backward:
# a head of one block (static mask) and of two, each height it can pick
_STRIP_SHAPES = {(512, 512): (0, 256), (512, 1024): (0, 256),
                 (1024, 1024): (512, 256), (1024, 2048): (512, 256)}


@pytest.mark.parametrize("two_kernel", [False, True],
                         ids=["fused-bwd", "two-kernel-bwd"])
@pytest.mark.parametrize("block,seq", list(_STRIP_SHAPES))
def test_flash_causal_strips_match_reference(pallas_interpret, monkeypatch,
                                             block, seq, two_kernel):
    """A diagonal block walked in causal row strips (forward top to bottom,
    backward bottom to top, both backward forms) == the dense reference:
    output and all three gradients."""
    from deepspeed_tpu.ops.pallas import flash_attention, mha_reference
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    if two_kernel:
        monkeypatch.setattr(fa, "MAX_FUSED_BWD_NK", 0)
    tiles = tuple(fa._causal_tile(seq, seq, block, block, True, backward=b)
                  for b in (False, True))
    assert tiles == _STRIP_SHAPES[block, seq]
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q, k, v = (jax.random.normal(kk, (1, seq, 2, 64), jnp.float32)
               for kk in ks)

    def loss(fn):
        return lambda a, b, c: jnp.sum(jnp.sin(fn(a, b, c)))

    kernel = lambda a, b, c: flash_attention(a, b, c, causal=True,
                                             block_q=block, block_k=block)
    dense = lambda a, b, c: mha_reference(a, b, c, causal=True)
    np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                               np.asarray(dense(q, k, v)), atol=2e-5,
                               rtol=2e-5)
    g_k = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(g_k, g_r, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=1e-4,
                                   rtol=1e-4, err_msg=f"d{name}")


def _not_engaging(kind):
    """``(Sq, Sk, block_q, block_k, flash_attention kwargs, plan flags)`` of
    a caller the causal strips must leave on the whole-block body; lengths
    and windows that mask nothing, so the run visits what the plan counts."""
    if kind == "kv_lens":
        return 256, 256, 128, 128, dict(
            causal=False, kv_lens=jnp.asarray([256], jnp.int32)), (True, False)
    if kind == "static-window":
        return 512, 512, 256, 256, dict(causal=True, window=512), (False, True)
    if kind == "traced-window":
        return 512, 512, 256, 256, dict(
            causal=True, window=jnp.asarray(512, jnp.int32)), (False, True)
    if kind == "cross-length":
        return 256, 512, 256, 256, dict(causal=True), (False, False)
    if kind == "non-causal":
        return 512, 512, 256, 256, dict(causal=False), (False, False)
    assert kind == "blocks-differ"
    return 512, 512, 512, 256, dict(causal=True), (False, False)


_NOT_ENGAGING = ["kv_lens", "static-window", "traced-window", "cross-length",
                 "non-causal", "blocks-differ"]


@pytest.mark.parametrize("kind", _NOT_ENGAGING)
def test_flash_causal_strips_leave_other_callers_alone(kind):
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    Sq, Sk, bq, bk, kw, (lens, window) = _not_engaging(kind)
    for backward in (False, True):
        assert fa._causal_tile(Sq, Sk, bq, bk, kw["causal"], lens, window,
                               backward=backward) == 0
    visited, square = fa.causal_tile_plan(Sq, Sk, bq, bk, kw["causal"], lens,
                                          window)
    assert visited == square > 0


@pytest.mark.parametrize("kind", ["gpt2m-train-s1024",
                                  "opt1b3-train-zero3-4chip"] + _NOT_ENGAGING)
def test_causal_tile_plan_counts_what_the_kernels_visit(pallas_interpret,
                                                        monkeypatch, kind):
    """``causal_tile_plan`` against an instrumented run: every score product
    the kernels execute reports its area, forward and (fused) backward.  The
    plan counts one head; the two heads of a 128-lane block each make their
    own products."""
    fa = importlib.import_module("deepspeed_tpu.ops.pallas.flash_attention")
    if kind in _NOT_ENGAGING:
        Sq, Sk, bq, bk, kw, (lens, window) = _not_engaging(kind)
        ratio = 1.0
    else:   # the two train cells: a head of one 1024-block, and of 2 x 2
        Sq = Sk = {"gpt2m-train-s1024": 1024}.get(kind, 2048)
        bq, bk = fa.resolve_env_blocks()
        kw, lens, window = dict(causal=True), False, False
        ratio = {1024: 88 / 128, 2048: 304 / 384}[Sq]
    area, real = [0], fa._scores

    def counted(q, ks, sm_scale):
        jax.debug.callback(lambda: area.__setitem__(
            0, area[0] + q.shape[0] * ks.shape[0]))
        return real(q, ks, sm_scale)

    monkeypatch.setattr(fa, "_scores", counted)
    ks = jax.random.split(jax.random.PRNGKey(12), 3)
    heads = 2
    q, k, v = (jax.random.normal(kk, (1, s, heads, 64), jnp.float32)
               for kk, s in zip(ks, (Sq, Sk, Sk)))
    with fa.tally_causal_tiles() as tally:
        grads = jax.grad(lambda a, b, c: jnp.sum(fa.flash_attention(
            a, b, c, block_q=bq, block_k=bk, **kw)), argnums=(0, 1, 2))(
                q, k, v)
    jax.block_until_ready(grads)
    jax.effects_barrier()
    visited, square = fa.causal_tile_plan(Sq, Sk, bq, bk, kw["causal"], lens,
                                          window)
    assert area[0] == heads * visited * fa.SUB_TILE ** 2
    assert visited / square == ratio
    assert tally == [visited, square, 1, 0]     # one call, three arrays
