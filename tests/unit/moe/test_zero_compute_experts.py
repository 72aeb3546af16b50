"""The softmax-over-all gate and the experts that hold no matrices
(``moe/held_experts.py``): the gate against plain numpy, the held share
reckoned against the router's whole width, the identity part added in full
whatever is held, droplessness when every token lands on held experts, and
the counter of pairs on zero-compute experts (0 for the other gates)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.moe.held_experts import (Routing, held_experts_ffn,
                                            local_slots, n_pair_counts,
                                            pair_counts, pairs_cap,
                                            read_pair_counts, route,
                                            route_softmax,
                                            route_softmax_all, zero_weight)

T, D, F = 96, 32, 16
N_EXPERTS, N_ZERO, K = 12, 8, 4
WIDTH = N_EXPERTS + N_ZERO
HELD = (0, 1, 2, 3)


def _softmax(z):
    e = np.exp(z - z.max(-1, keepdims=True))
    return e / e.sum(-1, keepdims=True)


def _inputs(seed=0):
    rng = np.random.default_rng(seed)
    h = rng.standard_normal((T, D)).astype(np.float32)
    w = (rng.standard_normal((D, WIDTH)) * 0.3).astype(np.float32)
    return rng, h, w


def test_the_gate_is_a_softmax_over_all_outputs_scaled_and_not_renormalised():
    rng, h, w = _inputs()
    bias = np.zeros((WIDTH,), np.float32)
    got = route_softmax_all(jnp.asarray(h), jnp.asarray(w), jnp.asarray(bias),
                            K, 6.0)
    p = _softmax(h.astype(np.float64) @ w.astype(np.float64))
    want = np.argsort(-p, axis=-1, kind="stable")[:, :K]
    assert got.experts.dtype == jnp.int32 and got.weights.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got.experts), want)
    np.testing.assert_allclose(np.asarray(got.weights),
                               6.0 * np.take_along_axis(p, want, -1),
                               rtol=1e-5)
    # not renormalised: the chosen weights sum to 6 times their share of
    # the softmax over ALL outputs, another share for every token
    sums = np.asarray(got.weights).sum(-1)
    assert (sums < 6.0).all() and sums.std() > 0.1
    # zero-compute outputs are chosen like any other
    assert (np.asarray(got.experts) >= N_EXPERTS).any()


def test_the_bias_moves_the_choice_and_never_the_weight():
    _, h, w = _inputs(1)
    zero = jnp.zeros((WIDTH,), jnp.float32)
    plain = route_softmax_all(jnp.asarray(h), jnp.asarray(w), zero, K, 2.0)
    bias = zero.at[WIDTH - 1].set(10.0)      # the last output wins everywhere
    moved = route_softmax_all(jnp.asarray(h), jnp.asarray(w), bias, K, 2.0)
    assert (np.asarray(moved.experts) == WIDTH - 1).any(axis=1).all()
    assert not (np.asarray(plain.experts) == WIDTH - 1).any(axis=1).all()
    p = _softmax(h.astype(np.float64) @ w.astype(np.float64))
    np.testing.assert_allclose(
        np.asarray(moved.weights),
        2.0 * np.take_along_axis(p, np.asarray(moved.experts), -1), rtol=1e-5)


def test_bf16_inputs_route_in_float32():
    h = jnp.ones((1, 4), jnp.float32)
    w = jnp.zeros((4, 6), jnp.float32).at[0, 3].set(1e-6)
    got = route_softmax_all(h, w, jnp.zeros((6,)), 1, 1.0)
    assert int(got.experts[0, 0]) == 3       # far under bf16's step
    tied = route_softmax_all(jnp.ones((2, 4), jnp.bfloat16),
                             jnp.zeros((4, 6), jnp.bfloat16),
                             jnp.zeros((6,)), 2, 3.0)
    assert np.asarray(tied.experts).tolist() == [[0, 1]] * 2
    np.testing.assert_allclose(np.asarray(tied.weights), 3.0 / 6)


def test_the_held_share_is_reckoned_against_the_routers_whole_width():
    slots = local_slots(HELD, WIDTH)
    assert slots.shape == (WIDTH,)
    assert list(slots[:4]) == [0, 1, 2, 3] and (slots[4:] == len(HELD)).all()
    n_pairs = 4096 * 12
    # 16 of 768 outputs: twice 1,024 pairs, on the row tile
    assert pairs_cap(n_pairs, 16, 768) == 2048
    # against the matrices alone it would be half as large again
    assert pairs_cap(n_pairs, 16, 512) == 3072


def _stack(rng, dtype=jnp.float32):
    shapes = {"w_gu": (D, 2 * F), "w_down": (F, D)}
    return {k: jnp.asarray(rng.standard_normal((len(HELD),) + s)
                           / np.sqrt(s[0]), dtype) for k, s in shapes.items()}


def _reference(h, routing, stack):
    """Float64: the held experts' weighted SwiGLUs plus the zero-compute
    pairs' weight times the input; pairs per held expert; zero pairs."""
    h = np.asarray(h, np.float64)
    out = np.zeros_like(h)
    experts = np.asarray(routing.experts)
    weights = np.asarray(routing.weights, np.float64)
    per_expert = [0] * len(HELD)
    for t in range(h.shape[0]):
        for e, w in zip(experts[t], weights[t]):
            if e >= N_EXPERTS:
                out[t] += w * h[t]
            elif e in HELD:
                i = HELD.index(e)
                gu = h[t] @ np.asarray(stack["w_gu"][i], np.float64)
                g, u = gu[:F], gu[F:]
                out[t] += w * ((g / (1 + np.exp(-g)) * u)
                               @ np.asarray(stack["w_down"][i], np.float64))
                per_expert[i] += 1
    return out, per_expert, int((experts >= N_EXPERTS).sum())


def _routed(rng, which):
    """A routing of ``T`` tokens: the gate's own, every pair on a held
    expert, every pair on a zero-compute one, or none on either."""
    if which == "gate":
        _, h, w = _inputs(2)
        bias = rng.standard_normal((WIDTH,)).astype(np.float32) * 1e-3
        return route_softmax_all(jnp.asarray(h), jnp.asarray(w),
                                 jnp.asarray(bias), K, 6.0)
    low, high = {"all_held": (0, len(HELD)), "all_zero": (N_EXPERTS, WIDTH),
                 "elsewhere": (len(HELD), N_EXPERTS)}[which]
    experts = np.stack([rng.permutation(np.arange(low, high))[:K]
                        for _ in range(T)]).astype(np.int32)
    return Routing(jnp.asarray(experts), jnp.asarray(
        rng.uniform(0.01, 0.1, (T, K)).astype(np.float32)))


@pytest.mark.parametrize("which", ["gate", "all_held", "all_zero",
                                   "elsewhere"])
def test_held_and_identity_parts_against_the_reference(which):
    rng = np.random.default_rng(5)
    h = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    stack = _stack(rng)
    routing = _routed(rng, which)
    out, counts = jax.jit(lambda h, r, s: held_experts_ffn(
        h, r, s, HELD, N_EXPERTS, n_zero=N_ZERO))(h, routing, stack)
    want, per_expert, zero = _reference(h, routing, stack)
    np.testing.assert_allclose(np.asarray(out, np.float64), want, rtol=2e-5,
                               atol=2e-5 * max(np.abs(want).max(), 1e-3))
    here = sum(per_expert)
    cap = pairs_cap(T * K, len(HELD), WIDTH)
    pages_over = max(-(-here // cap) - 1, 0)
    assert list(np.asarray(counts)) == per_expert + [pages_over, zero]
    vector = np.asarray(pair_counts(counts, T * K))
    assert vector.shape == (n_pair_counts(len(HELD)),)
    assert list(vector) == [here, T * K, sum(c > 0 for c in per_expert)
                            ] + per_expert + [pages_over, zero]
    # the one reader of the vector's order: the names the span uses
    assert read_pair_counts(vector) == {
        "held": here, "routed": T * K,
        "visits": sum(c > 0 for c in per_expert), "per_expert": per_expert,
        "pages_over_cap": pages_over, "zero": zero}
    if which == "all_held":
        # dropless: every pair of the call lands here, pages beyond the cap
        assert here == T * K and cap < T * K and pages_over > 0 == zero
    if which == "all_zero":
        assert here == 0 and zero == T * K
        w, n = zero_weight(routing, N_EXPERTS)
        assert int(n) == T * K
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(w)[:, None] * np.asarray(h),
            rtol=1e-6)
    if which == "elsewhere":
        assert here == zero == 0 and not np.asarray(out).any()


def test_the_identity_part_is_the_same_whatever_share_is_held():
    """Every chip adds the identity part in full: a share's result less its
    held part alone (the same call with the zero-compute pairs weighing
    nothing) is the zero-compute pairs' weight times the input, for any
    share."""
    rng = np.random.default_rng(7)
    h = jnp.asarray(rng.standard_normal((T, D)), jnp.float32)
    routing = _routed(rng, "gate")
    stack = _stack(rng)
    w, n = zero_weight(routing, N_EXPERTS)
    assert int(n) > 0
    identity = np.asarray(w)[:, None] * np.asarray(h)
    weightless = Routing(routing.experts, jnp.where(
        routing.experts >= N_EXPERTS, 0.0, routing.weights))
    for held in (HELD, (8, 9, 10, 11)):
        full, counts = held_experts_ffn(h, routing, stack, held, N_EXPERTS,
                                        n_zero=N_ZERO)
        held_alone, _ = held_experts_ffn(h, weightless, stack, held,
                                         N_EXPERTS, n_zero=N_ZERO)
        assert np.abs(np.asarray(held_alone)).max() > 0
        np.testing.assert_allclose(np.asarray(full - held_alone), identity,
                                   atol=1e-5)
        assert int(counts[-1]) == int(n)


@pytest.mark.parametrize("gate", ["sigmoid", "softmax_of_top_k"])
def test_the_other_gates_count_no_zero_compute_pair(gate):
    rng, h, w = _inputs(3)
    w = w[:, :N_EXPERTS]
    routing = route(jnp.asarray(h), jnp.asarray(w),
                    jnp.zeros((N_EXPERTS,)), K, 2.5) if gate == "sigmoid" \
        else route_softmax(jnp.asarray(h), jnp.asarray(w), K)
    stack = _stack(rng)
    out, counts = held_experts_ffn(jnp.asarray(h), routing, stack, HELD,
                                   N_EXPERTS)
    assert counts.shape == (len(HELD) + 2,) and int(counts[-1]) == 0
    named = read_pair_counts(pair_counts(counts, T * K))
    assert named["zero"] == 0 and named["held"] == sum(named["per_expert"]) > 0
