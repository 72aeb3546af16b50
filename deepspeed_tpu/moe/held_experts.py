"""An expert layer that is told which experts it holds.

Expert parallelism gives each chip a slice of a layer's experts.  The router
keeps its published width (it scores every expert of the deployment, held
here or not) and its experts per token; this chip multiplies only the
(token, expert) pairs that land on the experts it holds and adds their part
of the result.  What the absent experts would add is another chip's to add:
on one chip the layer runs without its exchange, and nothing here stands in
for the chips that are not there.

Three gates return the same ``Routing``.  ``route`` is the published
``noaux_tc`` gate with one group: sigmoid scores over all experts **in
float32**, the ``k`` largest of ``score + bias`` (the bias moves the
selection and never the weight), the selected scores normalised to sum to
one and scaled.  ``route_softmax`` takes the ``k`` largest router logits and
weighs them by a softmax over those ``k`` alone.  ``route_softmax_all``
takes a softmax over ALL the router's outputs, chooses by ``p + bias`` and
weighs by ``scale * p`` with no renormalisation.

A router may be wider than there are matrices: its last ``n_zero`` outputs
are **zero-compute experts** (identity: ``E(h) = h``).  A pair that chose
one is held by no chip and multiplied by none; its weight goes to a scalar
a token that multiplies the layer's own input (``zero_weight``).  That part
is the token's chip's to add: computed here in full whatever the share
held, so across the shares of a deployment it counts once.

The held pairs are multiplied grouped and dropless.  Pairs are sorted by the
local index of their expert, held ones first, and the rows of each held
expert meet that expert's matrices in one grouped product: on a TPU the
Pallas grouped matmul that ships with JAX (``megablox.gmm``), whose grid is
the row tiles of the live groups, so an expert's matrices are streamed once;
elsewhere ``lax.ragged_dot``, the same product.  The pairs held elsewhere
cost nothing around the products either: the held ones are brought together
in a page of ``pairs_cap`` rows (twice a uniform router's share of the
call), and a call that holds more runs further pages, so nothing is dropped
whatever the routing.  No ``[tokens, experts, capacity]`` tensor and no
``[tokens x k, width]`` array exists, and the cost is linear in the pairs
held here.
"""

from __future__ import annotations

from typing import Dict, NamedTuple, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas.utils import interpret_mode, use_pallas

#: an expert's form: three matrices, ``W_d (silu(W_g h) * W_u h)`` with gate
#: beside up in ``w_gu``; or two, ``W_d relu(W_u h)^2`` with ``w_up``
SWIGLU, RELU2 = "swiglu", "relu2"

#: the grouped matmul's (rows, contraction, columns) tile on the chip at its
#: largest: 2 MB of an expert's matrix a step, streamed behind the product
#: before it
GMM_TILING = (128, 1024, 1024)


def gmm_tiling(k: int, n: int) -> Tuple[int, int, int]:
    """The grouped matmul's tile for matrices ``[k, n]``: ``GMM_TILING``
    where its sides divide them, else the largest whole number of lane rows
    that does (768 for a side of 768 or 1536): a tile that overhangs a
    matrix's edge is masked every step.  Where the column tile comes out
    narrower than ``GMM_TILING``'s, VMEM has room for the WHOLE contraction
    side (a block of at most 6 MiB, double-buffered): an expert whose rows
    straddle two row tiles then asks for the same block twice in a row and
    it is fetched once (measured at 4096 x 1536, PERF.md 6, PR 35: 0.57 ->
    0.44 ms with 71 rows an expert, 0.44 -> 0.38 with 18)."""
    def side(dim, cap):
        return next((t for t in range(cap, 0, -128) if dim % t == 0), cap)
    tm, tk, tn = GMM_TILING[0], side(k, GMM_TILING[1]), side(n, GMM_TILING[2])
    if tn < GMM_TILING[2] and k * tn * 2 <= 6 << 20:
        tk = k
    return tm, tk, tn


class Routing(NamedTuple):
    """``experts`` [T, k] global ids, ``weights`` [T, k] float32."""
    experts: jnp.ndarray
    weights: jnp.ndarray


def route(h, w_router, bias, k: int, scale: float,
          normalize: bool = True) -> Routing:
    """The sigmoid top-k gate.  ``h`` [T, d] in any dtype, ``w_router``
    [d, E], ``bias`` [E]: scores in float32 at full precision, selection by
    ``score + bias``, weights from the scores alone."""
    scores = jax.nn.sigmoid(jnp.dot(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST))
    _, experts = lax.top_k(scores + bias.astype(jnp.float32), k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if normalize:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-20)
    return Routing(experts.astype(jnp.int32), weights * scale)


def route_softmax(h, w_router, k: int) -> Routing:
    """The softmax-of-top-k gate: logits ``W_r h`` in float32 at full
    precision over all experts, the ``k`` largest chosen, the weights a
    softmax over the chosen logits alone (no bias, no scale)."""
    logits = jnp.dot(h.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    top, experts = lax.top_k(logits, k)
    return Routing(experts.astype(jnp.int32), jax.nn.softmax(top, axis=-1))


def route_softmax_all(h, w_router, bias, k: int, scale: float) -> Routing:
    """The softmax-over-all gate.  ``w_router`` [d, E] with ``E`` every
    output of the router, zero-compute experts included, ``bias`` [E]:
    logits and softmax in float32 at full precision, the ``k`` largest of
    ``p + bias`` chosen (the bias moves the choice and never the weight),
    weights ``scale * p`` of the chosen, not renormalised."""
    p = jax.nn.softmax(jnp.dot(
        h.astype(jnp.float32), w_router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST), axis=-1)
    _, experts = lax.top_k(p + bias.astype(jnp.float32), k)
    return Routing(experts.astype(jnp.int32),
                   jnp.take_along_axis(p, experts, axis=-1) * scale)


def zero_weight(routing: Routing, n_experts: int):
    """``([T] float32, [] int32)``: the summed weight of each token's pairs
    on zero-compute experts (ids ``>= n_experts``), and how many such pairs
    the call has."""
    zero = routing.experts >= n_experts
    return (jnp.sum(jnp.where(zero, routing.weights, 0.0), -1),
            jnp.sum(zero, dtype=jnp.int32))


def local_slots(held: Sequence[int], n_experts: int) -> np.ndarray:
    """``[n_experts]`` int32 (``n_experts``: the router's whole width): a
    held expert's index among the held, and ``len(held)`` for every expert
    held elsewhere or by no one."""
    table = np.full((n_experts,), len(held), np.int32)
    table[np.asarray(held, np.int64)] = np.arange(len(held), dtype=np.int32)
    return table


def _grouped(rows, w, group_sizes, layer=None):
    """``rows[group g] @ w[g]`` for rows sorted by group; rows past the last
    group come back as they may (the caller zeroes them).  With ``layer``
    (a scan's index) ``w`` is the whole stack ``[layers, groups, k, n]`` and
    the product reads layer ``layer`` of it WHERE IT LIES: the kernel sees
    ``layers * groups`` groups, all empty but that layer's, and an empty
    group costs no step.  (Handing a Pallas call one layer of a stack makes
    the compiler copy that layer out first: 1.06 GB a layer a tick at the
    published widths.)"""
    if use_pallas() and not interpret_mode():
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
        if layer is not None:
            n_groups = group_sizes.shape[0]
            group_sizes = lax.dynamic_update_slice(
                jnp.zeros((w.shape[0] * n_groups,), jnp.int32), group_sizes,
                (layer * n_groups,))
            w = w.reshape((-1,) + w.shape[2:])
        return gmm(rows, w, group_sizes, preferred_element_type=rows.dtype,
                   tiling=gmm_tiling(*w.shape[-2:]))
    if layer is not None:
        w = lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
    return lax.ragged_dot(rows, w.astype(rows.dtype), group_sizes)


def n_pair_counts(n_held: int) -> int:
    """The length of ``pair_counts``' vector for ``n_held`` held experts."""
    return 5 + n_held


def pair_counts(counts, routed: int):
    """An expert layer call's counters ``[n_pair_counts(n_held)]`` int32 from
    ``held_experts_ffn``'s ``counts``: pairs held here, pairs routed in all,
    held experts that took at least one pair (each streams its matrices
    once), pairs per held expert, the pages of pairs the call ran beyond its
    first (``pairs_cap``), and last the pairs that chose a zero-compute
    expert (among the routed; 0 for a router without such)."""
    per_expert = counts[:-2]
    return jnp.concatenate([
        jnp.sum(per_expert, keepdims=True),
        jnp.full((1,), routed, jnp.int32),
        jnp.sum(per_expert > 0, keepdims=True, dtype=jnp.int32),
        counts])


def read_pair_counts(vector) -> Dict[str, object]:
    """``pair_counts``' vector back on the host, each counter under the name
    the span ``serve.moe_pairs`` gives it: ``held``, ``routed``, ``visits``,
    ``per_expert`` (a list, one entry a held expert), ``pages_over_cap`` and
    ``zero``.  The one place that knows the vector's order."""
    held, routed, visits, *per_expert, pages_over_cap, zero = (
        int(c) for c in np.asarray(vector))
    return {"held": held, "routed": routed, "visits": visits,
            "per_expert": per_expert, "pages_over_cap": pages_over_cap,
            "zero": zero}


def pairs_cap(n_pairs: int, n_held: int, n_experts: int) -> int:
    """Rows of the buffer the held pairs of a call are brought together in:
    twice the pairs a uniform router sends to ``n_held`` of ``n_experts``
    (the router's whole width, zero-compute outputs included), on the
    grouped product's row tile, and never more than all
    ``n_pairs``.  A call that holds more runs further pages of as many."""
    mean = -(-n_pairs * n_held // n_experts)
    tile = GMM_TILING[0]
    return min(n_pairs, -(-2 * mean // tile) * tile)


def held_experts_ffn(h, routing: Routing, p: Dict[str, jnp.ndarray],
                     held: Sequence[int], n_experts: int, layer=None,
                     form: str = SWIGLU, n_zero: int = 0
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The held experts' part of the layer's result, and the pair counts.

    ``h`` [T, d]; ``p["w_gu"]`` [n_held, d, 2f] (gate beside up; ``form``
    ``RELU2``: ``p["w_up"]`` [n_held, d, f]) and ``p["w_down"]`` [n_held, f,
    d], the held experts' matrices in the order of ``held``; with ``layer``
    (a layer scan's index) both are the whole stacks ``[layers, n_held,
    ...]``, read in place.  Returns ``(out [T, d], counts [n_held + 2]
    int32)``: ``sum_{i in sel, i held} w_i E_i(h)``; the pairs each held
    expert took, the pages run beyond the first and, last, the pairs on
    zero-compute experts.  ``n_zero``: the router's last ``n_zero`` outputs
    (ids ``n_experts ..``) are zero-compute experts, and ``out`` gains
    ``zero_weight * h``, in full.

    Of the ``T * k`` pairs routed anywhere only those held here are
    multiplied, so only they are moved: sorted by expert they fill the
    first rows of a page of ``P = pairs_cap(...)`` rows, and the row gather,
    both grouped products, the activation, the weighting and the way back
    to tokens (a 0/1 matrix ``[T, P]`` times the weighted rows, accumulated
    in float32: exact, and its cost follows ``P`` where a gather's follows
    ``T * k``) all run on ``P`` rows; what is kept a pair of the whole call
    is its expert's index, its place and its weight.  Dropless for any
    routing: a call that holds more than ``P`` pairs runs ``ceil(held /
    P)`` pages through the one body, each with the group sizes clipped to
    its range and its part added to the result."""
    T, d = h.shape
    k = routing.experts.shape[1]
    n_held, n_pairs = len(held), T * k
    width = n_experts + n_zero
    P = pairs_cap(n_pairs, n_held, width)
    flat = jnp.asarray(local_slots(held, width))[routing.experts].reshape(-1)
    with jax.named_scope("moe_routed"):
        # held pairs first, by expert: a stable sort keeps tokens in order,
        # and a pair's weight rides with it (a gather of scalars costs more)
        _, order, weights = lax.sort(
            (flat, jnp.arange(n_pairs, dtype=jnp.int32),
             jnp.where(flat < n_held, routing.weights.reshape(-1), 0.0)),
            num_keys=1, is_stable=True)
        counts = jnp.sum(flat[:, None] == jnp.arange(n_held)[None, :],
                         axis=0, dtype=jnp.int32)
        ends = jnp.cumsum(counts)
        n_here = ends[-1]
        # whole pages to slice
        token = jnp.pad(order // k, (0, -n_pairs % P))
        weights = jnp.pad(weights, (0, -n_pairs % P))

        def page(at, out):
            """Rows ``[at, at + P)`` of the sorted pairs, added to ``out``
            [T, d] float32."""
            of = lax.dynamic_slice(token, (at,), (P,))
            w = lax.dynamic_slice(weights, (at,), (P,))
            sizes = jnp.clip(jnp.minimum(ends, at + P)
                             - jnp.maximum(ends - counts, at), 0)
            rows = h[of]                                         # [P, d]
            if form == RELU2:
                up = _grouped(rows, p["w_up"], sizes, layer)
                act = jnp.square(jax.nn.relu(up.astype(jnp.float32))
                                 ).astype(h.dtype)
            else:
                gu = _grouped(rows, p["w_gu"], sizes, layer)
                f = gu.shape[-1] // 2
                act = (jax.nn.silu(gu[:, :f].astype(jnp.float32))
                       * gu[:, f:].astype(jnp.float32)).astype(h.dtype)
            y = _grouped(act, p["w_down"], sizes, layer)         # [P, d]
            # a row past the held pairs weighs nothing, whatever came back
            y = jnp.where(w[:, None] != 0,
                          y.astype(jnp.float32) * w[:, None], 0.0
                          ).astype(h.dtype)
            # back to tokens: each token's rows summed in float32
            mine = of[None, :] == jnp.arange(T)[:, None]
            return out + jnp.dot(mine.astype(h.dtype), y,
                                 precision=lax.Precision.HIGHEST,
                                 preferred_element_type=jnp.float32)

        out = jnp.zeros((T, d), jnp.float32)
        if P == n_pairs:            # one page holds whatever lands here
            out = page(0, out)
        else:
            _, out = lax.while_loop(
                lambda c: c[0] < n_here,
                lambda c: (c[0] + P, page(c[0], c[1])), (jnp.int32(0), out))
    more = jnp.maximum(-(-n_here // P) - 1, 0)
    n_on_zero = jnp.zeros((), jnp.int32)
    if n_zero:
        with jax.named_scope("moe_zero"):
            w_zero, n_on_zero = zero_weight(routing, n_experts)
            out = out + w_zero[:, None] * h.astype(jnp.float32)
    return out.astype(h.dtype), jnp.concatenate(
        [counts, more[None], n_on_zero[None]])
