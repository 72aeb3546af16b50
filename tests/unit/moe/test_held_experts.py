"""``held_experts_ffn`` against a plain float32 reference: a loop over the
held experts with dense products over every token.

The held pairs of a call are brought together in pages of ``pairs_cap`` rows
(twice a uniform router's share), so the counts that matter are those around
the cap: no pair held here, the mean, exactly one page, one pair more, and
every pair of the call (four pages), for both expert forms, for one layer's
matrices and for the whole stack read in place, and for both gates' own
routings."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.moe.held_experts import (RELU2, SWIGLU, Routing,
                                            held_experts_ffn, n_pair_counts,
                                            pair_counts, pairs_cap,
                                            read_pair_counts, route,
                                            route_softmax)

T, K, D, F = 128, 4, 32, 16
N_EXPERTS, HELD = 32, (5, 2, 30, 9)         # an eighth, in no order
N_PAIRS = T * K
CAP = pairs_cap(N_PAIRS, len(HELD), N_EXPERTS)
LAYERS, LAYER = 3, 1

#: routing -> pairs held here, or a gate's name
ROUTINGS = {"none_here": 0, "the_mean": N_PAIRS * len(HELD) // N_EXPERTS,
            "one_page": CAP, "a_pair_over": CAP + 1,
            "groups_across_pages": 2 * CAP + 44, "every_pair": N_PAIRS,
            "one_expert_takes_all": "one", "sigmoid_gate": "sigmoid",
            "softmax_gate": "softmax"}


def test_the_cap_is_twice_the_mean_on_the_row_tile():
    assert CAP == 128 < N_PAIRS
    assert pairs_cap(8192, 16, 64) == 4096          # an EP4 admission chunk
    assert pairs_cap(4096, 12, 384) == 256          # an EP32 one
    assert pairs_cap(384, 16, 64) == 256            # a tick of 48 rows
    assert pairs_cap(1024, 12, 384) == 128
    assert pairs_cap(512, 8, 8) == 512              # every expert held
    assert pairs_cap(40, 2, 8) == 40                # too small to round under


def _routing(kind, rng, h):
    """``Routing`` with exactly ``kind`` pairs on held experts (each token's
    choices distinct), or a gate's own over random router weights."""
    if kind in ("sigmoid", "softmax"):
        w_router = jnp.asarray(rng.standard_normal((D, N_EXPERTS)),
                               jnp.float32)
        if kind == "softmax":
            return route_softmax(h, w_router, K)
        bias = jnp.asarray(rng.standard_normal(N_EXPERTS) * 0.1, jnp.float32)
        return route(h, w_router, bias, K, 2.5)
    elsewhere = [e for e in range(N_EXPERTS) if e not in HELD]
    experts = np.stack([rng.permutation(elsewhere)[:K] for _ in range(T)])
    if kind == "one":
        experts[:, 2] = HELD[1]
    else:
        for t in range(T):
            here = kind // T + (t < kind % T)
            for j in range(here):
                experts[t, (t + j) % K] = HELD[(t + j) % len(HELD)]
    weights = rng.uniform(0.05, 1.0, (T, K))
    return Routing(jnp.asarray(experts, jnp.int32),
                   jnp.asarray(weights, jnp.float32))


def _reference(h, routing, w, form):
    """Every held expert over every token in float64, weighted by the
    token's weight for it (0 where it did not choose it)."""
    h = np.asarray(h, np.float64)
    experts, weights = np.asarray(routing.experts), np.asarray(
        routing.weights, np.float64)
    out = np.zeros((T, D))
    for i, e in enumerate(HELD):
        weight = (weights * (experts == e)).sum(axis=1)
        if form == RELU2:
            act = np.maximum(h @ w["w_up"][i], 0.0) ** 2
        else:
            gu = h @ w["w_gu"][i]
            act = gu[:, :F] / (1.0 + np.exp(-gu[:, :F])) * gu[:, F:]
        out += weight[:, None] * (act @ w["w_down"][i])
    per_expert = [(experts == e).sum() for e in HELD]
    return out, per_expert


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("stacked", [False, True], ids=["layer", "stack"])
@pytest.mark.parametrize("form", [SWIGLU, RELU2])
@pytest.mark.parametrize("name", list(ROUTINGS))
def test_the_held_pairs_in_pages_are_the_reference(name, form, stacked,
                                                   dtype):
    rng = np.random.default_rng(sorted(ROUTINGS).index(name))
    h = jnp.asarray(rng.standard_normal((T, D)), dtype)
    up = "w_up" if form == RELU2 else "w_gu"
    shapes = {up: (D, F if form == RELU2 else 2 * F), "w_down": (F, D)}
    stack = {k: jnp.asarray(rng.standard_normal(
        (LAYERS, len(HELD)) + s) / np.sqrt(s[0]), dtype)
        for k, s in shapes.items()}
    one = {k: np.asarray(v[LAYER], np.float64) for k, v in stack.items()}
    routing = _routing(ROUTINGS[name], rng, h.astype(jnp.float32))

    def call(h, routing, stack):
        p = stack if stacked else {k: v[LAYER] for k, v in stack.items()}
        return held_experts_ffn(h, routing, p, HELD, N_EXPERTS,
                                layer=LAYER if stacked else None, form=form)

    out, counts = jax.jit(call)(h, routing, stack)
    want, per_expert = _reference(h, routing, one, form)
    assert out.shape == (T, D) and out.dtype == dtype
    # float32: the sums' order alone differs; bfloat16: the program rounds a
    # pair's activation, its result and its weighted result, as it always has
    tol = 2e-5 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               rtol=tol, atol=tol * np.abs(want).max())
    here = sum(per_expert)
    if isinstance(ROUTINGS[name], int):
        assert here == ROUTINGS[name]
    pages_over = max(-(-here // CAP) - 1, 0)
    assert counts.dtype == jnp.int32
    assert list(np.asarray(counts)) == per_expert + [pages_over, 0]
    if name == "every_pair":
        assert pages_over == N_PAIRS // CAP - 1 > 0
    if name in ("none_here", "the_mean", "one_page", "one_expert_takes_all",
                "sigmoid_gate", "softmax_gate"):
        assert pages_over == 0
    vector = np.asarray(pair_counts(counts, N_PAIRS))
    assert vector.shape == (n_pair_counts(len(HELD)),)
    assert list(vector) == [here, N_PAIRS, sum(c > 0 for c in per_expert)
                            ] + per_expert + [pages_over, 0]
    named = read_pair_counts(vector)
    assert list(named) == ["held", "routed", "visits", "per_expert",
                           "pages_over_cap", "zero"]
    assert named["per_expert"] == per_expert and all(
        type(c) is int for c in named["per_expert"])
    assert [named[k] for k in named if k != "per_expert"] == [
        here, N_PAIRS, sum(c > 0 for c in per_expert), pages_over, 0]
