"""The loss head of ``models/gpt.py``: the head and the cross-entropy over
chunks of the sequence under their own backward rule (``_chunked_nll``)
against the plain ``logsumexp`` form, what engages it, and what it traces
to."""

from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.models import gpt

V = 384     # no other extent of the test models equals it


def _config(**kw):
    kw = {"vocab_size": V, "max_seq_len": 32, "n_layer": 1, "n_head": 2,
          "d_model": 16, "dtype": jnp.float32, "vocab_round_to": 128, **kw}
    return gpt.GPTConfig(**kw)


def _batch(B, S, masked=()):
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0, V)
    labels = tokens[:, 1:]
    for rows, cols in masked:
        labels = labels.at[rows, cols].set(-100)
    return {"input_ids": tokens[:, :-1], "labels": labels}


def _plain_loss(params, batch, cfg):
    """The loss as it was before the head ran in chunks: whole logits,
    ``logsumexp`` and ``take_along_axis`` over ``[B, S, V]``."""
    logits = gpt.apply(params, batch["input_ids"], cfg)
    tot, cnt = gpt._token_nll(logits, batch["labels"])
    return tot / jnp.maximum(cnt, 1.0)


def _params(cfg):
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    if "lm_head_bias" in params:    # initialised to zeros: make it count
        params["lm_head_bias"] = 0.5 * jax.random.normal(
            jax.random.PRNGKey(2), params["lm_head_bias"].shape)
    return params


def _walk(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub)


CASES = {
    # name: (config, B, S, masked label slices, byte budget or None)
    "several-chunks": (dict(loss_chunk=4), 2, 16, (), None),
    # 22 = 2 x 11: the largest divisor under a cap of 8 is 2
    "awkward-divisor": (dict(loss_chunk=8), 3, 22, (), None),
    # chunk 1 of 4 (positions 4-7) is wholly masked, and a ragged tail
    "masked-chunk": (dict(loss_chunk=4), 2, 16,
                     ((slice(None), slice(4, 8)), (1, slice(13, None))),
                     None),
    "untied-head": (dict(loss_chunk=4, tie_word_embeddings=False), 2, 16,
                    (), None),
    "biased-head": (dict(loss_chunk=8, tie_word_embeddings=False,
                         lm_head_bias=True), 2, 16, ((0, slice(0, 3)),),
                    None),
    # no cap: the chunk follows from the bytes of the logits alone
    "byte-budget": ({}, 2, 16, (), 2 * 4 * V * 4),
    "byte-budget-under-cap": (dict(loss_chunk=8), 2, 16, (), 2 * 2 * V * 4),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_loss_and_gradients_match_the_plain_form(case, monkeypatch):
    kw, B, S, masked, budget = CASES[case]
    if budget is not None:
        monkeypatch.setattr(gpt, "_LOGITS_CHUNK_BYTES", budget)
    cfg = _config(**kw)
    params, batch = _params(cfg), _batch(B, S, masked)
    with gpt.tally_head() as tally:
        loss, grads = jax.value_and_grad(gpt.loss_fn)(params, batch, cfg)
    assert tally[0] == 3 and tally[1] > 1, tally    # it did run in chunks
    want, want_grads = jax.value_and_grad(_plain_loss)(params, batch, cfg)
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for got, ref in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("case,chunks", [
    ("several-chunks", 4), ("awkward-divisor", 11), ("byte-budget", 4),
    ("byte-budget-under-cap", 8)])
def test_chunk_follows_the_cap_and_the_bytes(case, chunks, monkeypatch):
    kw, B, S, masked, budget = CASES[case]
    if budget is not None:
        monkeypatch.setattr(gpt, "_LOGITS_CHUNK_BYTES", budget)
    cfg = _config(**kw)
    with gpt.tally_head() as tally:
        jax.eval_shape(partial(gpt.loss_fn, config=cfg), _params(cfg),
                       _batch(B, S))
    assert tally == [3, chunks]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.float16, 4e-3)],
                         ids=["float32", "float16"])
def test_a_loss_scale_reaches_the_gradients(dtype, tol):
    """A cotangent other than 1: the engine's dynamic loss scale, 65,536,
    seeds the backward rule, which multiplies gradients its forward rule
    made for a unit cotangent.  In float16 those are the softmax's gradient
    cast BEFORE the scale is in it: it must still match the plain form,
    whose cast comes after."""
    scale = 65536.0
    cfg = _config(loss_chunk=4, dtype=dtype)
    params, batch = _params(cfg), _batch(2, 16, ((0, slice(0, 5)),))

    def scaled(loss):
        return lambda p: scale * loss(p, batch, cfg)

    grads = jax.grad(scaled(gpt.loss_fn))(params)
    want = jax.grad(scaled(_plain_loss))(params)
    for got, ref in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(want)):
        assert np.isfinite(np.asarray(got)).all()
        np.testing.assert_allclose(
            got, ref, rtol=tol, atol=tol * float(jnp.abs(ref).max()))


@pytest.mark.parametrize("kw", [
    {}, dict(tie_word_embeddings=False, lm_head_bias=True),
    dict(pos_embed="rotary", parallel_residual=True),
    dict(pos_embed="alibi", embed_layernorm=True)],
    ids=["gpt2", "gptj-head", "neox", "bloom"])
def test_small_logits_trace_to_the_plain_jaxpr(kw):
    """Under the byte floor ``loss_fn`` is the single pass it was: text for
    text the jaxpr of whole logits into ``_token_nll``, forward and
    backward, and one chunk on the tally."""
    cfg = _config(**kw)
    params, batch = _params(cfg), _batch(2, 16)
    with gpt.tally_head() as tally:
        got = jax.make_jaxpr(jax.value_and_grad(
            partial(gpt.loss_fn, config=cfg)))(params, batch)
    assert tally == [3, 1]
    want = jax.make_jaxpr(jax.value_and_grad(
        partial(_plain_loss, cfg=cfg)))(params, batch)
    assert str(got) == str(want)


def test_an_int8_head_keeps_the_plain_path():
    """A head that is not a float matrix goes through ``_wdot`` as before,
    whatever the cap says: one chunk, and the int8 product's loss."""
    from deepspeed_tpu.ops.int8 import (Int8ComputeParam,
                                        quantize_for_int8_compute)
    cfg = _config(loss_chunk=4)
    params, batch = _params(cfg), _batch(2, 16)
    params["wte"] = quantize_for_int8_compute(params["wte"], (1,))
    assert isinstance(params["wte"], Int8ComputeParam)
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 16, cfg.d_model))
    with gpt.tally_head() as tally:
        tot, cnt = gpt._head_nll(params, h, batch["labels"], cfg)
    assert tally == [3, 1]
    logits = gpt._head_logits(params, h, cfg)
    ref, _ = gpt._token_nll(logits, batch["labels"])
    np.testing.assert_allclose(tot, ref, rtol=1e-6)
    assert float(cnt) == 32


@pytest.mark.parametrize("kw", [dict(loss_chunk=4),
                                dict(loss_chunk=8, lm_head_bias=True,
                                     tie_word_embeddings=False)],
                         ids=["tied", "biased"])
def test_chunked_jaxpr_holds_no_whole_logits_and_three_products(kw):
    """Above the floor no value of the traced step is as large as
    ``[B, S, V]``, and exactly ``head.logit_products`` products carry the
    vocabulary: the logits and the two gradients, each a chunk's."""
    B, S = 2, 16
    cfg = _config(**kw)
    params, batch = _params(cfg), _batch(B, S)
    with gpt.tally_head() as tally:
        jaxpr = jax.make_jaxpr(jax.value_and_grad(
            partial(gpt.loss_fn, config=cfg)))(params, batch)
    products = 0
    for eqn in _walk(jaxpr.jaxpr):
        shapes = [v.aval.shape for v in list(eqn.invars) + list(eqn.outvars)
                  if hasattr(v.aval, "shape")]
        for shape in shapes:
            assert int(np.prod(shape)) < B * S * V, (eqn.primitive, shape)
        if eqn.primitive.name == "dot_general" and any(
                V in shape for shape in shapes):
            products += 1
    assert products == tally[0] == 3


def test_the_plain_jaxpr_holds_three_products_over_whole_logits():
    """What the tally reports for one chunk: the jaxpr of the plain path
    holds three vocabulary-sized products (the compiler may re-make
    them) over ``[B, S, V]``."""
    cfg = _config()
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        partial(gpt.loss_fn, config=cfg)))(_params(cfg), _batch(2, 16))
    products = [eqn for eqn in _walk(jaxpr.jaxpr)
                if eqn.primitive.name == "dot_general" and any(
                    V in v.aval.shape
                    for v in list(eqn.invars) + list(eqn.outvars))]
    assert len(products) == 3


def test_loss_chunk_of_the_whole_sequence_is_the_plain_path():
    cfg = _config(loss_chunk=64)
    with gpt.tally_head() as tally:
        jax.eval_shape(partial(gpt.loss_fn, config=cfg), _params(cfg),
                       _batch(2, 16))
    assert tally == [3, 1]


@pytest.mark.parametrize("dims", [dict(dp=4), dict(dp=2, tp=2)],
                         ids=["dp4", "dp2-tp2"])
def test_chunked_loss_on_a_mesh_matches_the_plain_form(dims):
    """ZeRO-3 shards the head over data and tensor parallelism shards its
    vocabulary over model: each data shard sums the head gradient of its
    own rows (``shards`` of ``_loss_layout``) and the logsumexp's running
    pair reduces across the vocabulary's shards."""
    from deepspeed_tpu.models.partitioning import FSDP_RULES, tree_shardings
    from deepspeed_tpu.parallel.mesh import (DP_GROUP, ParallelDims,
                                             initialize_mesh)
    cfg = _config(loss_chunk=4, lm_head_bias=True, tie_word_embeddings=False)
    params, batch = _params(cfg), _batch(4, 16, ((2, slice(0, 6)),))
    want, want_grads = jax.value_and_grad(_plain_loss)(params, batch, cfg)

    mm = initialize_mesh(ParallelDims(**dims), devices=jax.devices()[:4])
    assert gpt._loss_layout(4, 16, V, 4)[:2] == (4, mm.dp_world_size)
    params = jax.device_put(params, tree_shardings(
        gpt.logical_axes(cfg), mm.mesh, FSDP_RULES))
    batch = jax.device_put(batch, mm.sharding(DP_GROUP, None))
    with gpt.tally_head() as tally:
        loss, grads = jax.jit(jax.value_and_grad(
            partial(gpt.loss_fn, config=cfg)))(params, batch)
    assert tally == [3, 4]
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    for got, ref in zip(jax.tree_util.tree_leaves(grads),
                        jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)
