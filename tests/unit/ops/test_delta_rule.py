"""The gated delta-rule kernels (``ops/pallas/delta_rule.py``) against the
recurrence they stand for, token by token in float64: the chunked (WY) form
for sub-chunks of 16 and 64, a ragged ``valid``, a state carried over three
calls, decays near 0 and near 1; the decode step; a freed slot.  Each both
ways: the XLA twin, and the Pallas kernel under the interpreter at a head of
128 x 128 (the shape that tiles).  The kernel builds its scores in two
levels (products through a sub-block's first row, columns only inside a
sub-block) where the twin states the sums plainly, so the twin is also the
kernel's independent check: decays that differ by channel inside a head,
a ``valid`` inside a sub-block and on its edge, both to 1e-5.

The tolerance: both sides are float32 / float64 of the same sums in another
order; over 192 tokens on a state of order 1 that reads 1e-6 to 1e-5 here
(the inverse of ``I + A`` by halves adds nothing visible), and 2e-4 is
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


def _twin(monkeypatch, *args, **kwargs):
    """``kda_chunk_scan`` of the same call down its XLA twin."""
    with monkeypatch.context() as m:
        m.setattr(delta_rule, "_tiles", lambda K, V: False)
        return delta_rule.kda_chunk_scan(*args, **kwargs)


#: a decay a KEY CHANNEL inside every head: e^-40 a token beside e^-0.001
BY_CHANNEL = "by-channel"


@pytest.mark.parametrize("path,chunk,decay,valid,against", [
    ("xla", 16, 1.6, 77, "recurrence"), ("xla", 64, 1.6, 77, "recurrence"),
    ("xla", 64, 1e-2, 77, "recurrence"), ("xla", 64, 40.0, 77, "recurrence"),
    # the kernel's sub-chunk is a multiple of 64
    ("kernel", 64, 1.6, 77, "recurrence"),
    ("kernel", 128, 1.6, 77, "recurrence"),
    ("kernel", 64, 1e-2, 77, "recurrence"),
    ("kernel", 64, 40.0, 77, "recurrence"),
    ("xla", 64, BY_CHANNEL, 77, "recurrence"),
    ("kernel", 64, BY_CHANNEL, 77, "recurrence"),
    ("xla", 64, 1.6, 80, "recurrence"),
    ("kernel", 64, 1.6, 80, "recurrence"),
    ("kernel", 64, 1.6, 77, "twin"), ("kernel", 128, 1.6, 80, "twin"),
    ("kernel", 64, BY_CHANNEL, 80, "twin")],
    ids=["xla-16", "xla-64", "xla-decay-near-1", "xla-decay-near-0",
         "kernel-64", "kernel-128", "kernel-decay-near-1",
         "kernel-decay-near-0", "xla-decay-by-channel",
         "kernel-decay-by-channel", "xla-valid-on-a-sub-block-edge",
         "kernel-valid-on-a-sub-block-edge", "kernel-64-is-the-twin",
         "kernel-128-is-the-twin", "kernel-by-channel-is-the-twin"])
def test_the_chunk_form_equals_the_recurrence(monkeypatch, path, chunk,
                                              decay, valid, against):
    """A ragged ``valid`` (a full row, a row that ends inside a sub-chunk:
    at 77 inside a sub-block of the kernel's scores, whether of 8 rows or
    of 16, at 80 on a sub-block's edge) from a state that is not zero; a
    decay of e^-40 a token overflows any ``exp(-G_s)`` after three tokens,
    and ``by channel`` puts such channels beside channels that keep
    e^-0.001 in ONE head, so that the factor through a sub-block's first
    row underflows in some channels of a score and not in others.
    ``against`` the twin: the two-level kernel and the plain statement of
    the same sums sum in another order, and agree to 1e-5."""
    H, K = _head(monkeypatch, path)
    rng = np.random.default_rng(0)
    B, S, L = 2, 128, 3
    q, k, v, g, beta = _draw(rng, B, S, H, K,
                             1.6 if decay == BY_CHANNEL else decay)
    if decay == BY_CHANNEL:
        g = np.where(rng.uniform(size=(H, K)) < 0.5, -40.0, -1e-3) \
            * rng.uniform(0.9, 1.1, g.shape)
    stack = rng.normal(size=(L, B, K, H * K)) * 0.3
    valid = np.array([S, valid])
    call = (jnp.asarray(stack, jnp.float32), 1, *_f32(q, k, v, g, beta))
    sizes = dict(valid=jnp.asarray(valid), chunk=chunk)
    o, out = delta_rule.kda_chunk_scan(*call, **sizes)
    if against == "twin":
        want_o, want_s = _twin(monkeypatch, *call, **sizes)
        want_s, tol = np.asarray(want_s)[1], dict(atol=1e-5, rtol=1e-5)
    else:
        want_o, want_s = _recurrence(stack[1], q, k, v, g, beta, valid)
        tol = TOL
    for b in range(B):
        np.testing.assert_allclose(np.asarray(o)[b, :valid[b]],
                                   np.asarray(want_o)[b, :valid[b]], **tol)
    np.testing.assert_allclose(np.asarray(out)[1], want_s, **tol)
    assert (np.asarray(out)[[0, 2]] == np.float32(stack)[[0, 2]]).all()


def test_the_sub_block_is_any_number_of_whole_sublane_rows(monkeypatch):
    """``SUB_BLOCK`` is a constant of the kernel, chosen on the chip (8
    over 16); the two levels hold for any size that divides the
    sub-chunk."""
    H, K = _head(monkeypatch, "kernel")
    monkeypatch.setattr(delta_rule, "SUB_BLOCK", 16)
    rng = np.random.default_rng(4)
    q, k, v, g, beta = _draw(rng, 1, 128, H, K, 1.6)
    stack = rng.normal(size=(1, 1, K, H * K)) * 0.3
    call = (jnp.asarray(stack, jnp.float32), 0, *_f32(q, k, v, g, beta))
    sizes = dict(valid=jnp.asarray([100]), chunk=64)
    o, out = delta_rule.kda_chunk_scan(*call, **sizes)
    want_o, want_s = _twin(monkeypatch, *call, **sizes)
    np.testing.assert_allclose(np.asarray(o)[:, :100],
                               np.asarray(want_o)[:, :100], atol=1e-5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want_s),
                               atol=1e-5)


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


# ---- 64 heads, beta in (1, 2): the negative-eigenvalue delta rule

def _draw_64(rng, B, S, K, keys):
    """64 heads with ``beta`` in (1, 2): ``I - beta k k^T`` REFLECTS what the
    state reads for ``k``.  ``keys`` ``"random"``: independent unit keys and
    decays up to e^-1.6 a token; ``"parallel"``: every key of a head within
    0.05 of one direction under a decay of at most e^-0.001 a token, so that
    ``beta |k_t . k_s| D[t, s]`` stays near 2 across a whole sub-chunk (what
    an inverse of ``I + A`` by powers of ``A`` cannot survive)."""
    H = 64
    q, k, v, g, _ = _draw(rng, B, S, H, K, 1.6)
    if keys == "parallel":
        k = rng.normal(size=(B, 1, H, K)) + 0.05 * rng.normal(
            size=(B, S, H, K))
        k = k / np.linalg.norm(k, axis=-1, keepdims=True)
        g = -rng.uniform(1e-4, 1e-3, g.shape)
    return q, k, v, g, rng.uniform(1.0, 2.0, (B, S, H))


@pytest.mark.parametrize("keys", ["random", "parallel"])
@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_the_chunk_form_at_64_heads_and_beta_past_one(monkeypatch, path,
                                                      keys):
    """The published shape of the family with negative eigenvalues (64
    heads: 8 column blocks of 8 heads a row in the kernel) against the
    recurrence token by token in float64, ``beta`` drawn in (1, 2), from a
    state that is not zero, a ragged ``valid``.  The tolerance is the file's
    (the same sums in another order): with ``beta`` under 2 the transition
    never stretches, so neither side amplifies its rounding, and the system
    ``(I + A) U = rhs`` is solved by halves, whose intermediates are blocks
    of the inverse itself (order 1): this reads 1e-6 to 2e-5 on states of
    order 2, on near-parallel keys as on random ones, where the product form
    ``(I - A)(I + A^2)(I + A^4)...`` read 1e33."""
    _, K = _head(monkeypatch, path)
    rng = np.random.default_rng(7)
    B, S = 1, 128
    q, k, v, g, beta = _draw_64(rng, B, S, K, keys)
    stack = rng.normal(size=(2, B, K, 64 * K)) * 0.3
    valid = np.array([101])
    o, out = delta_rule.kda_chunk_scan(
        jnp.asarray(stack, jnp.float32), 1, *_f32(q, k, v, g, beta),
        valid=jnp.asarray(valid), chunk=64)
    want_o, want_s = _recurrence(stack[1], q, k, v, g, beta, valid)
    assert np.isfinite(np.asarray(o)[0, :101]).all()
    np.testing.assert_allclose(np.asarray(o)[0, :101], want_o[0, :101], **TOL)
    np.testing.assert_allclose(np.asarray(out)[1], want_s, **TOL)
    assert (np.asarray(out)[0] == np.float32(stack)[0]).all()


@pytest.mark.parametrize("keys", ["random", "parallel"])
@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_the_decode_step_at_64_heads_and_beta_past_one(monkeypatch, path,
                                                       keys):
    """8 tokens one at a time through the step (64 heads: two column
    blocks of 32 heads a slot in the kernel) with ``beta`` in (1, 2), a
    freed slot among three, against the recurrence in float64: the step IS
    the recurrence, a token's sums in float32, so the file's tolerance
    holds with room (1e-6 here)."""
    _, K = _head(monkeypatch, path)
    rng = np.random.default_rng(8)
    B, T = 3, 8
    q, k, v, g, beta = _draw_64(rng, B, T, K, keys)
    stack = rng.normal(size=(2, B, K, 64 * K)) * 0.3
    live = np.array([True, False, True])
    state = jnp.asarray(stack, jnp.float32)
    outs = []
    for t in range(T):
        o, state = delta_rule.kda_decode_step(
            state, 0, *_f32(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t]),
            active=jnp.asarray(live))
        outs.append(np.asarray(o)[:, 0])
    want_o, want_s = _recurrence(stack[0], q, k, v, g, beta)
    got = np.stack(outs, 1)
    for b in np.flatnonzero(live):
        np.testing.assert_allclose(got[b], want_o[b], **TOL)
        np.testing.assert_allclose(np.asarray(state)[0, b], want_s[b], **TOL)
    assert (got[1] == 0).all()
    assert (np.asarray(state)[0, 1] == np.float32(stack)[0, 1]).all()
    assert (np.asarray(state)[1] == np.float32(stack)[1]).all()


def test_the_inverse_by_halves_is_the_inverse_at_any_size():
    """``_inverse_unit_lower`` on the delta rule's own ``A`` at its worst
    (one direction's keys, ``beta`` in (1, 2), no decay: entries near 2
    everywhere under the diagonal, an inverse of order 1), at sizes that are
    and are not powers of two, against float64's inverse: float32's rounding
    of sums of order 1."""
    rng = np.random.default_rng(9)
    for C in (2, 8, 24, 64, 128):
        beta = rng.uniform(1.0, 2.0, (C, 1))
        k = rng.normal(size=(1, 16)) + 0.05 * rng.normal(size=(C, 16))
        k /= np.linalg.norm(k, axis=-1, keepdims=True)
        a = np.tril(beta * (k @ k.T), -1)
        inv = np.asarray(delta_rule._inverse_unit_lower(
            jnp.asarray(a, jnp.float32)))
        want = np.linalg.inv(np.eye(C) + a)
        assert np.abs(want).max() < 4
        np.testing.assert_allclose(inv, want, atol=2e-5)


def test_near_parallel_keys_under_one_too():
    """``beta`` in (0.5, 1), the range of the family without the factor 2, on
    keys within 0.05 of one direction under a slow decay: the product of
    squarings read 1e12 to 1e21 against the recurrence here as well (powers of
    ``A`` near ``C(t - s, n)``), the halves the file's tolerance."""
    rng = np.random.default_rng(11)
    B, S, H, K = 1, 128, 3, 16
    q, _, v, g, _ = _draw(rng, B, S, H, K, 1.6)
    k = rng.normal(size=(B, 1, H, K)) + 0.05 * rng.normal(size=(B, S, H, K))
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    g = -rng.uniform(1e-4, 1e-3, g.shape)
    beta = rng.uniform(0.5, 1.0, (B, S, H))
    stack = rng.normal(size=(1, B, K, H * K)) * 0.3
    o, out = delta_rule.kda_chunk_scan(
        jnp.asarray(stack, jnp.float32), 0, *_f32(q, k, v, g, beta), chunk=64)
    want_o, want_s = _recurrence(stack[0], q, k, v, g, beta)
    np.testing.assert_allclose(np.asarray(o), want_o, **TOL)
    np.testing.assert_allclose(np.asarray(out)[0], want_s, **TOL)
