"""Grouped heads at a head of 64 (32 query heads on 8 key-value heads: a
512-wide row of whole lane rows whose pieces are half a lane row): the
single-token sweep and the chunk kernel under the interpreter against the
dense reference, ragged frontiers, dead rows; and ``sweep_plan`` names the
grouped sweep for that row from the shapes alone."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import decode_attention as da
from deepspeed_tpu.ops.pallas.decode_attention import (
    cached_attention, cached_attention_reference, chunk_block_k,
    chunk_block_q, decode_block_k, grouped_sweep_serves, sweep_plan)

H, HKV, D = 32, 8, 64
G, W = H // HKV, HKV * D
SMAX = 3072             # the served slot: six blocks of 512


@pytest.fixture
def pallas_interpret(monkeypatch):
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")


@pytest.mark.parametrize("D,Hkv,serves", [
    (128, 2, True), (128, 8, True), (64, 8, True), (64, 2, True),
    (64, 1, False),     # a 64-wide row: half a lane row
    (64, 3, False),     # 192: a row and a half
    (32, 8, False), (96, 4, False)])
def test_the_rule_is_of_the_shapes_alone(D, Hkv, serves):
    """A lane-aligned head, or a head of 64 in a row of whole lane rows."""
    assert grouped_sweep_serves(D, Hkv) == serves
    plan = sweep_plan((Hkv * D,) * 2, 1024, 4 * Hkv, kv_heads=Hkv)
    assert plan.kernel == (da.GROUPED_SWEEP if serves else None)
    assert plan.copy_rows is None and plan.block_k is not None


def test_the_plan_of_the_served_row():
    plan = sweep_plan((W, W), SMAX, H, kv_heads=HKV)
    assert plan.kernel == da.GROUPED_SWEEP == "gqa_decode_attention"
    assert plan.block_k == decode_block_k(SMAX, W) == 512
    assert chunk_block_k(SMAX) == 1024
    # a chunk of any swept size is one query tile of 256 positions x 4 heads
    assert [chunk_block_q(c, G, 1024) for c in (256, 512, 1024)] == [256] * 3


def _heads(x):
    """[B, S, Hkv * D] -> [B, S, H, D]: the reference reads a key-value
    head per query head."""
    return jnp.repeat(x.reshape(x.shape[:2] + (HKV, D)), G, axis=2)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-3)],
                         ids=["float32", "bfloat16"])
def test_the_sweep_at_a_head_of_64(pallas_interpret, dtype, tol):
    """``cached_attention`` of one token a row takes the grouped sweep (no
    key is repeated out to the query heads): frontiers on both sides of a
    block's edge, at a slot's start and end, dead rows among them, layer 1
    of a pool of two."""
    pos = jnp.asarray([0, 511, 512, 1400, 2047, 5, SMAX - 1, 700], jnp.int32)
    active = jnp.asarray([True, True, True, True, False, True, True, False])
    B = len(pos)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(64), 3)
    q = jax.random.normal(kq, (B, 1, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (2, B, SMAX, W), jnp.float32).astype(dtype)
    v = jax.random.normal(kv, k.shape, jnp.float32).astype(dtype)
    traced = jax.make_jaxpr(lambda q, k, v: cached_attention(
        q, k, v, pos, layer=1, active=active, kv_heads=HKV))(q, k, v)
    assert "gqa_decode_attention" in str(traced)
    got = jax.jit(lambda q, k, v: cached_attention(
        q, k, v, pos, layer=1, active=active, kv_heads=HKV))(q, k, v)
    want = jax.jit(lambda q, k, v: cached_attention_reference(
        q.astype(jnp.float32), _heads(k[1]).astype(jnp.float32),
        _heads(v[1]).astype(jnp.float32), pos))(q, k, v)
    got, want = np.asarray(got, np.float32), np.asarray(want)
    live = np.asarray(active)
    # bf16: the result is rounded once more (a row at frontier 0 returns
    # its one value, up to 3, to 8 bits), the probabilities to the values'
    # type
    np.testing.assert_allclose(got[live], want[live], atol=tol,
                               rtol=0 if dtype == jnp.float32 else 2 ** -7)
    assert not got[~live].any()         # a dead row costs nothing: zeros


@pytest.mark.parametrize("ragged", [False, True], ids=["shared", "ragged"])
def test_the_chunk_kernel_at_a_group_of_4_heads_of_64(pallas_interpret,
                                                      ragged):
    """A chunk of 256 positions at a prefix (per row where ragged), a step a
    key-value head's four query heads against a key block."""
    B, Sq, smax = 2, 256, 2048
    pos = jnp.asarray([1100, 300], jnp.int32) if ragged \
        else jnp.asarray(768, jnp.int32)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(kq, (B, Sq, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, smax, HKV, D), jnp.float32)
    v = jax.random.normal(kv, k.shape, jnp.float32)
    traced = jax.make_jaxpr(lambda q, k, v: cached_attention(
        q, k, v, pos, kv_heads=HKV))(q, k, v)
    assert "chunk_attention" in str(traced)
    got = jax.jit(lambda q, k, v: cached_attention(
        q, k, v, pos, kv_heads=HKV))(q, k, v)
    want = jax.jit(lambda q, k, v: cached_attention_reference(
        q, jnp.repeat(k, G, axis=2), jnp.repeat(v, G, axis=2), pos))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=0)
