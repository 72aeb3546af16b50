"""``ssm.causal_conv`` against a plain loop: with and without its activation
and its bias (the state-space and delta-rule families' SiLU form, and the
gated short convolution's bare sum), the tail carried between calls, taken
inside a padded call and given back bit for bit by a call with no real
token."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.pallas.ssm import causal_conv

B, S, C = 3, 11, 8


def _loop(u, tail, w, b, activation):
    """One position at a time over the sequence with its tail in front."""
    K = w.shape[0]
    full = np.concatenate([tail, u], axis=1).astype(np.float64)
    out = np.zeros((B, u.shape[1], C))
    for t in range(u.shape[1]):
        for j in range(K):
            out[:, t] += w[j] * full[:, t + j]
        if b is not None:
            out[:, t] += b
    if activation:
        out = out / (1.0 + np.exp(-out))
    return out


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("activation", [False, True], ids=["bare", "silu"])
def test_the_sum_its_bias_and_its_activation(activation, bias, taps):
    rng = np.random.default_rng(taps)
    u = rng.standard_normal((B, S, C)).astype(np.float32)
    tail = rng.standard_normal((B, taps - 1, C)).astype(np.float32)
    w = rng.standard_normal((taps, C)).astype(np.float32)
    b = rng.standard_normal(C).astype(np.float32) if bias else None
    kw = {} if activation else {"activation": None}
    out, new_tail = causal_conv(jnp.asarray(u), jnp.asarray(tail),
                                jnp.asarray(w),
                                None if b is None else jnp.asarray(b), **kw)
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out),
                               _loop(u, tail, w, b, activation),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(new_tail), u[:, S - taps + 1:])


@pytest.mark.parametrize("activation", [None, jax.nn.silu],
                         ids=["bare", "silu"])
def test_a_carried_a_padded_and_a_freed_tail(activation):
    """Two calls with the tail between them are one call; a padded call's
    tail is that of its last REAL position, per row; ``valid`` 0 gives the
    tail back bit for bit."""
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((B, S, C)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((3, C)), jnp.float32)
    zero = jnp.zeros((B, 2, C), jnp.bfloat16)
    conv = lambda u, tail, valid=None: causal_conv(
        u, tail, w, None, valid, activation=activation)
    whole, end = conv(u, zero)
    first, mid = conv(u[:, :4], zero)
    second, end_2 = conv(u[:, 4:], mid)
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate([first, second], 1)), np.asarray(whole))
    np.testing.assert_array_equal(np.asarray(end_2), np.asarray(end))
    valid = jnp.asarray([S, 5, 0], jnp.int32)
    _, padded = conv(u, mid, valid)
    np.testing.assert_array_equal(np.asarray(padded[0]), np.asarray(u[0, -2:]))
    np.testing.assert_array_equal(np.asarray(padded[1]), np.asarray(u[1, 3:5]))
    np.testing.assert_array_equal(np.asarray(padded[2]), np.asarray(mid[2]))
