"""The gated delta-rule kernels (``ops/pallas/delta_rule.py``) against the
recurrence they stand for, token by token in float64: the chunked (WY) form
for sub-chunks of 16 and 64, a ragged ``valid``, a state carried over three
calls, decays near 0 and near 1; the decode step; a freed slot.  Each both
ways: the XLA twin, and the Pallas kernel under the interpreter at a head of
128 x 128 (the shape that tiles).

The tolerance: both sides are float32 / float64 of the same sums in another
order; over 192 tokens on a state of order 1 that reads 1e-6 to 1e-5 here
(the inverse of ``I + A`` by squarings adds nothing visible), and 2e-4 is
held as the state-space kernels' tests hold theirs."""

import numpy as np
import pytest

import jax.numpy as jnp

from deepspeed_tpu.ops.pallas import delta_rule

TOL = dict(atol=2e-4, rtol=2e-4)


def _draw(rng, B, S, H, K, decay):
    """``decay``: the largest ``-g`` a token draws (uniform from 1e-3)."""
    n = lambda *shape: rng.normal(size=shape)
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)
    return (unit(n(B, S, H, K)) * K ** -0.5, unit(n(B, S, H, K)),
            n(B, S, H, K), -rng.uniform(1e-3, decay, (B, S, H, K)),
            rng.uniform(0.0, 1.0, (B, S, H)))


def _recurrence(state, q, k, v, g, beta, valid=None):
    """Float64, a token and a head at a time, from ``state`` [B, K, H*V]:
    ``(o [B, S, H*V], state)``; a row's tokens past ``valid`` do nothing."""
    B, S, H, K = q.shape
    V = v.shape[-1]
    s = np.array(state, np.float64).reshape(B, K, H, V)
    o = np.zeros((B, S, H, V))
    for b in range(B):
        for t in range(S if valid is None else int(valid[b])):
            for h in range(H):
                sp = np.exp(g[b, t, h])[:, None] * s[b, :, h]
                u = beta[b, t, h] * (v[b, t, h] - sp.T @ k[b, t, h])
                s[b, :, h] = sp + np.outer(k[b, t, h], u)
                o[b, t, h] = s[b, :, h].T @ q[b, t, h]
    return o.reshape(B, S, H * V), s.reshape(B, K, H * V)


def _f32(*arrays):
    return tuple(jnp.asarray(a, jnp.float32) for a in arrays)


def _head(monkeypatch, path):
    """``(H, K)`` of the path: the twin at a small head, the kernel under
    the interpreter at the head that tiles."""
    if path == "kernel":
        monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
        assert delta_rule._tiles(128, 128)
        return 2, 128
    assert not delta_rule._tiles(16, 16)
    return 3, 16


@pytest.fixture(params=["xla", "kernel"])
def head(request, monkeypatch):
    return _head(monkeypatch, request.param)


@pytest.mark.parametrize("path,chunk,decay", [
    ("xla", 16, 1.6), ("xla", 64, 1.6), ("xla", 64, 1e-2), ("xla", 64, 40.0),
    # the kernel's sub-chunk is a multiple of 64
    ("kernel", 64, 1.6), ("kernel", 128, 1.6), ("kernel", 64, 1e-2),
    ("kernel", 64, 40.0)],
    ids=["xla-16", "xla-64", "xla-decay-near-1", "xla-decay-near-0",
         "kernel-64", "kernel-128", "kernel-decay-near-1",
         "kernel-decay-near-0"])
def test_the_chunk_form_equals_the_recurrence(monkeypatch, path, chunk,
                                              decay):
    """A ragged ``valid`` (a full row, a row that ends inside a sub-chunk)
    from a state that is not zero; a decay of e^-40 a token overflows any
    ``exp(-G_s)`` after three tokens."""
    H, K = _head(monkeypatch, path)
    rng = np.random.default_rng(0)
    B, S, L = 2, 128, 3
    q, k, v, g, beta = _draw(rng, B, S, H, K, decay)
    stack = rng.normal(size=(L, B, K, H * K)) * 0.3
    valid = np.array([S, 77])
    o, out = delta_rule.kda_chunk_scan(
        jnp.asarray(stack, jnp.float32), 1, *_f32(q, k, v, g, beta),
        valid=jnp.asarray(valid), chunk=chunk)
    want_o, want_s = _recurrence(stack[1], q, k, v, g, beta, valid)
    for b in range(B):
        np.testing.assert_allclose(np.asarray(o)[b, :valid[b]],
                                   want_o[b, :valid[b]], **TOL)
    np.testing.assert_allclose(np.asarray(out)[1], want_s, **TOL)
    assert (np.asarray(out)[[0, 2]] == np.float32(stack)[[0, 2]]).all()


def test_a_state_carried_over_three_chunks(head):
    """Three calls of 64, 64 and a padded 64 (40 real) are one prompt of
    168: the state a call leaves is the next one's start, and a padded tail
    leaves it where the last real token did."""
    H, K = head
    rng = np.random.default_rng(1)
    q, k, v, g, beta = _draw(rng, 1, 192, H, K, 1.6)
    state = jnp.zeros((2, 1, K, H * K), jnp.float32)
    got = []
    for i, real in enumerate((64, 64, 40)):
        at = slice(64 * i, 64 * i + 64)
        o, state = delta_rule.kda_chunk_scan(
            state, 0, *_f32(q[:, at], k[:, at], v[:, at], g[:, at],
                            beta[:, at]), valid=jnp.asarray([real]),
            chunk=64)
        got.append(np.asarray(o)[:, :real])
    want_o, want_s = _recurrence(np.zeros((1, K, H * K)), q, k, v, g, beta,
                                 [168])
    np.testing.assert_allclose(np.concatenate(got, 1), want_o[:, :168],
                               **TOL)
    np.testing.assert_allclose(np.asarray(state)[0], want_s, **TOL)


def test_the_decode_step_is_one_step_and_a_freed_slot_is_untouched(head):
    H, K = head
    rng = np.random.default_rng(2)
    B, L = 3, 2
    q, k, v, g, beta = _draw(rng, B, 1, H, K, 1.6)
    stack = rng.normal(size=(L, B, K, H * K))
    active = jnp.asarray([True, False, True])
    o, out = delta_rule.kda_decode_step(
        jnp.asarray(stack, jnp.float32), 1,
        *_f32(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]), active=active)
    want_o, want_s = _recurrence(stack[1], q, k, v, g, beta)
    live = [0, 2]
    assert o.shape == (B, 1, H * K)
    np.testing.assert_allclose(np.asarray(o)[live], want_o[live], **TOL)
    np.testing.assert_allclose(np.asarray(out)[1, live], want_s[live], **TOL)
    assert not np.asarray(o)[1].any()
    assert (np.asarray(out)[1, 1] == np.float32(stack)[1, 1]).all()
    assert (np.asarray(out)[0] == np.float32(stack)[0]).all()


def test_the_inverse_of_a_unit_lower_triangular_matrix():
    rng = np.random.default_rng(3)
    a = np.tril(rng.normal(size=(2, 64, 64)), -1) * 0.3
    inv = np.asarray(delta_rule._inverse_unit_lower(
        jnp.asarray(a, jnp.float32)))
    np.testing.assert_allclose(inv @ (np.eye(64) + a),
                               np.broadcast_to(np.eye(64), a.shape),
                               atol=1e-4)
