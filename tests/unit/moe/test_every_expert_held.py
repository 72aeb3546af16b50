"""``held_experts_ffn`` where EVERY expert is held (``held_experts=None`` of
a config: the one page is all ``T * k`` pairs): against plain numpy, for both
gates, one layer's matrices and the stack read in place, ``held`` in any
order; and the program of a call is the same whatever share it holds: one
sort, and the way back to tokens a 0/1 product ``[T, P]`` (PERF.md 6, PR 61:
on the chip that product is 3% of an admission's pass at 1,024 tokens x 4
choices, and a way back by the sort's inverse was measured no faster, so
there is one way)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.moe.held_experts import (SWIGLU, held_experts_ffn,
                                            pairs_cap, route, route_softmax)

T, K, D, F, E = 96, 4, 32, 16, 8
LAYERS, LAYER = 3, 2


def _call(held, stacked, dtype=jnp.float32, seed=0, gate="sigmoid"):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.standard_normal((T, D)), dtype)
    stack = {"w_gu": jnp.asarray(rng.standard_normal(
        (LAYERS, len(held), D, 2 * F)) / np.sqrt(D), dtype),
        "w_down": jnp.asarray(rng.standard_normal(
            (LAYERS, len(held), F, D)) / np.sqrt(F), dtype)}
    w_router = jnp.asarray(rng.standard_normal((D, E)), jnp.float32)
    h32 = h.astype(jnp.float32)
    routing = route_softmax(h32, w_router, K) if gate == "softmax" else \
        route(h32, w_router, jnp.asarray(rng.standard_normal(E) * 0.1,
                                         jnp.float32), K, 1.0)

    def run(h, routing, stack):
        p = stack if stacked else {k: v[LAYER] for k, v in stack.items()}
        return held_experts_ffn(h, routing, p, held, E,
                                layer=LAYER if stacked else None, form=SWIGLU)
    return run, (h, routing, stack)


def _numpy(h, routing, stack, held):
    h = np.asarray(h, np.float64)
    experts = np.asarray(routing.experts)
    weights = np.asarray(routing.weights, np.float64)
    out = np.zeros((T, D))
    for i, e in enumerate(held):
        gu = h @ np.asarray(stack["w_gu"][LAYER, i], np.float64)
        act = gu[:, :F] / (1.0 + np.exp(-gu[:, :F])) * gu[:, F:]
        out += (weights * (experts == e)).sum(1)[:, None] * (
            act @ np.asarray(stack["w_down"][LAYER, i], np.float64))
    return out


def test_one_page_holds_every_pair():
    assert pairs_cap(T * K, E, E) == T * K
    assert pairs_cap(1024 * 4, 32, 32) == 4096      # a chunk of the cell's
    assert pairs_cap(256 * 4, 32, 32) == 1024       # a tick of its 256 slots


@pytest.mark.parametrize("gate", ["sigmoid", "softmax"])
@pytest.mark.parametrize("stacked", [False, True], ids=["layer", "stack"])
@pytest.mark.parametrize("order", ["in_order", "permuted"])
def test_every_expert_held_is_plain_numpys(order, stacked, gate):
    """Every pair is multiplied here and none is dropped: the result is
    plain numpy's sum over all experts, the counts every routed pair."""
    held = tuple(range(E)) if order == "in_order" else (3, 0, 7, 1, 6, 2, 5, 4)
    run, args = _call(held, stacked, gate=gate)
    out, counts = jax.jit(run)(*args)
    want = _numpy(*args, held)
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               rtol=2e-5, atol=2e-5 * np.abs(want).max())
    per_expert = [int((np.asarray(args[1].experts) == e).sum()) for e in held]
    assert list(np.asarray(counts)) == per_expert + [0, 0]
    assert sum(per_expert) == T * K


def test_bf16_rounds_a_pairs_rows_and_sums_them_in_float32():
    run, args = _call(tuple(range(E)), True, dtype=jnp.bfloat16, seed=3)
    out, _ = jax.jit(run)(*args)
    want = _numpy(*args, tuple(range(E)))
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float64), want,
                               rtol=4e-2, atol=4e-2 * np.abs(want).max())


def _primitives(jaxpr, found=None):
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


def _way_back(held):
    """``(sorts, loops, 0/1 products [T, P] x [P, d])`` of a call's
    program."""
    run, args = _call(held, True)
    eqns = _primitives(jax.make_jaxpr(run)(*args).jaxpr)
    P = pairs_cap(T * K, len(held), E)
    products = [e for e in eqns if e.primitive.name == "dot_general"
                and e.invars[0].aval.shape == (T, P)
                and e.invars[1].aval.shape == (P, D)]
    return (sum(e.primitive.name == "sort" for e in eqns),
            sum(e.primitive.name == "while" for e in eqns), len(products))


@pytest.mark.parametrize("held,loops", [((5, 2), 1),
                                        (tuple(range(E)), 0)],
                         ids=["a_share", "every_expert"])
def test_a_call_is_one_sort_and_one_way_back(held, loops):
    """Whatever share a call holds it sorts its pairs once and goes back to
    tokens by the 0/1 product on its page; only the loop over further pages
    goes where one page holds every pair.  (A share's jaxpr was compared
    text for text with the parent commit's in PR 61: this file is what
    holds it since.)"""
    assert _way_back(held) == (1, loops, 1)
