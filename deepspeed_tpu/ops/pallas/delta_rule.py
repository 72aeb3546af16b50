"""Gated delta-rule (Kimi Delta Attention, KDA) kernels for a cache that keeps
STATE PER SLOT.

A KDA mixer keeps, per layer and per conversation, one matrix a head, ``S``
``[d_k, d_v]`` float32, whatever the conversation's length.  Token ``t`` of
head ``h`` brings a query and a key ``q, k`` ``[d_k]`` (unit length, the
query over ``sqrt(d_k)``), a value ``v`` ``[d_v]``, a log-decay ``g`` ``[d_k]``
(<= 0: EVERY KEY CHANNEL decays by its own ``alpha = exp(g)``) and a scalar
``beta`` in (0, 2) (``sigmoid`` in ``linear_latent_moe``, twice that in
``linear_gqa_moe``: past 1 the transition REFLECTS what the state reads for
``k``, its eigenvalue along ``k`` ``1 - beta`` in (-1, 0)):

    S' = alpha[:, None] * S_{t-1}        what is left of the state
    u  = beta * (v - S'^T k)             the delta: what k should read, less
                                         what it reads now
    S_t = S' + k u^T                     a rank-one correction
    o  = S_t^T q

(``S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T``.)  It is not the
state-space update of ``ops/pallas/ssm.py``: the decay is a vector a head, the
write needs a reduction over the state first (``S'^T k``), and every head has
its own ``q`` and ``k``.  What it shares with that file is the pool: ``[L, B,
d_k, heads * d_v]`` float32, a layer's state of a slot one matrix with the
key channels on the sublanes and the ``(head, value channel)`` pairs on the
lanes, so head ``h`` is the ``[d_k, d_v]`` tile at lanes ``h d_v ..``, and the
aliased stack indexed by layer, ``live_rows`` and ``valid``.

Two kernels, each with an XLA twin of the same signature (the CPU, and
shapes that do not tile):

``kda_decode_step``: one token for every LIVE slot.  The grid is the live
slots' column blocks (``ssm.live_rows``); a step reads a slot's state from
layer ``layer`` of the stack where it lies, writes it back in place and
returns ``o``.  ``q``, ``k`` and ``alpha`` come to the kernel as COLUMNS
(``[d_k, heads]``: a head's vector down the sublanes, broadcast over its
tile's lanes); ``v``, ``beta`` and ``o`` are rows of the tile's lanes.  A
freed slot costs no step and no byte, and its state is not touched.

``kda_chunk_scan``: a chunk of a prompt from the state a slot holds to the
state after the chunk's last REAL token, in the chunked (WY / UT) form.
Inside a sub-chunk of ``C`` tokens starting from ``S_0``, with ``G_t =
sum_{s<=t} g_s`` (inclusive, per channel) and ``D[t, s] = exp(G_t - G_s)``
for ``s <= t`` (every exponent <= 0; ``exp(-G_s)`` alone overflows float32
after a few dozen fast-decaying tokens and is never formed): unrolling the
recurrence, ``S_t = Diag(exp(G_t)) S_0 + sum_{s<=t} (k_s o D[t, s]) u_s^T``,
and putting that into ``u_t = beta_t (v_t - S'_t^T k_t)`` with ``S'_t =
Diag(alpha_t) S_{t-1}``:

    u_t = beta_t (v_t - S_0^T (k_t o exp(G_t))) - sum_{s<t} A[t, s] u_s
    A[t, s] = beta_t sum_c k_t[c] k_s[c] D[t, s][c]            (s < t)

a unit lower-triangular system ``(I + A) U = beta o (V - (K o exp(G)) S_0)``;
then

    o_t = S_0^T (q_t o exp(G_t)) + sum_{s<=t} (sum_c q_t[c] k_s[c] D[t,s][c]) u_s
    S_C = exp(G_C)[:, None] * S_0 + sum_s (k_s o D[C, s]) u_s^T

and the next sub-chunk starts from ``S_C``.  The per-channel decay sits
inside every score, so ``A`` and the query scores are no plain products of
``k`` and ``q``.  They are built in TWO LEVELS, over sub-blocks of
``SUB_BLOCK`` rows.  For a row ``t`` of a sub-block whose first row is
``r``, and any earlier column ``s < r``, ``G`` does not rise in any channel
(``g <= 0``), so

    D[t, s] = exp(G_t - G_r) exp(G_r - G_s)        (s < r <= t)

with BOTH exponents <= 0: nothing overflows, and where a factor underflows
``D[t, s]`` itself is below float32's smallest.  Through that reference row
the sum over channels is a plain product, the matrix unit's: the sub-block's
rows ``k_t o exp(G_t - G_r)`` and ``q_t o exp(G_t - G_r)`` against the keys
before it, ``k_s o exp(G_r - G_s)``, one product a sub-block (float32,
``HIGHEST``, like every product here).  Only the pairs INSIDE a sub-block
(the diagonal blocks) keep the direct form, a column ``s`` at a time:
``k_s o D[:, s]`` against the sub-block's own rows, a ``SUB_BLOCK``-th of
what a column over the whole sub-chunk costs the vector unit.
``(I + A)^-1`` is built by halves (``_inverse_unit_lower``): inside a
diagonal block of 2 it is ``I - A``, and a block of ``2b`` follows from its
two blocks of ``b`` by two products, ``log2 C - 1`` levels: products, where
forward substitution would be ``C`` dependent steps, and every intermediate
a block of the inverse itself.  Positions at or past ``valid``
take ``g = 0`` and ``beta = 0``: ``D`` is 1 across them, their ``u`` is 0,
and a padded tail leaves the state exactly where the last real token left
it.  Everything inside is float32.

The convolutions before the kernels are ``ssm.causal_conv`` (one call over
``q | k | v``).

What is served and what was checked.  Heads: any number whose tiles are
whole lane rows by whole sublane rows (``d_k``, ``d_v`` multiples of 128);
32 heads (one step block of 4,096 lanes a slot, 4 scan blocks of 8 heads)
and 64 (two step blocks, 8 scan blocks) run in the benchmark, and both
compile for the chip in ``tests/unit/ops/test_tpu_aot_compile.py``.
``beta``: (0, 2).  Against the recurrence token by token in float64
(``tests/unit/ops/test_delta_rule.py``): ``beta`` in (0, 1) on random keys
under decays from e^-0.001 to e^-40 a token, and ``beta`` in (1, 2) at 64
heads on random keys and on keys within 0.05 of one direction under a decay
of at most e^-0.001 a token, where ``beta |k_t . k_s| D[t, s]`` stays near 2
over a whole sub-chunk: both read 1e-6 to 2e-5 on states of order 2.  The
product form ``(I + N)(I + N^2)(I + N^4)...`` (``N = -A``), which this file
used until PR 64, costs the same ten products a sub-chunk and read 1e33
there: its powers of ``N`` grow like ``2^n C(t - s, n)`` before they cancel.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ssm import live_rows
from .utils import interpret_mode, use_pallas

LANES = 128
#: lanes of a slot's state one decode step reads and writes: 128 x 4096
#: float32 is 2 MB in and 2 MB out, double-buffered 8 MB of VMEM
DECODE_BLOCK = 4096
#: lanes one scan step carries: 8 heads of 128, a 512 KB state in scratch
SCAN_BLOCK = 1024
_HI = lax.Precision.HIGHEST
#: rows of a sub-block of a sub-chunk's scores: pairs inside one are built a
#: column at a time, pairs across two are products through a reference row
#: (a whole number of sublane rows of 8; on the chip a 1,024-token chunk a
#: layer takes 1.70 ms at 8 and 1.79 at 16: PERF.md 6, PR 56)
SUB_BLOCK = 8


def _tiles(K: int, V: int) -> bool:
    """Whether the kernels take the shape: a head's tile is whole lane rows
    by whole sublane rows."""
    return use_pallas() and V % LANES == 0 and K % LANES == 0


def _head_cols(t, nc: int):
    """``[B, heads, K]`` -> ``[B, nc, K, heads / nc]``: each column block's
    heads as columns, a head's vector down the sublanes."""
    B, H, K = t.shape
    return t.reshape(B, nc, H // nc, K).transpose(0, 1, 3, 2)


# ------------------------------------------------------------- decode step

def _decode_kernel(rows_ref, n_ref, layer_ref, cols_ref, v_ref, beta_ref,
                   h_ref, y_ref, ho_ref, *, nc: int, V: int):
    """One grid step: block ``s % nc`` of the columns of live slot ``rows[s
    // nc]``.  ``h_ref`` / ``ho_ref`` (K, cb) are the same block of the
    aliased stack, ``cb / V`` heads' tiles side by side; ``cols_ref`` (K, 3
    hb) holds the block's heads' ``q``, ``k`` and ``alpha`` as columns, in
    that order; ``v_ref`` and ``beta_ref`` (``beta`` repeated over a head's
    lanes) are (1, cb) rows.  Steps past the live ones (a static grid's
    tail) stay on the last live block and do nothing; with no live slot at
    all the one block the grid visits is copied through."""
    hb = h_ref.shape[1] // V

    @pl.when(n_ref[0] == 0)
    def _nothing_live():
        ho_ref[...] = h_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(pl.program_id(0) < n_ref[0] * nc)
    def _step():
        for i in range(hb):
            sl = slice(i * V, (i + 1) * V)
            qc = cols_ref[:, i:i + 1]
            kc = cols_ref[:, hb + i:hb + i + 1]
            ac = cols_ref[:, 2 * hb + i:2 * hb + i + 1]
            s = ac * h_ref[:, sl]
            u = beta_ref[:, sl] * (
                v_ref[:, sl] - jnp.sum(kc * s, axis=0, keepdims=True))
            s = s + kc * u
            ho_ref[:, sl] = s
            y_ref[:, sl] = jnp.sum(qc * s, axis=0, keepdims=True)


def _decode_pallas(state, layer, q, k, v, alpha, beta, work):
    L, B, K, HV = state.shape
    H = q.shape[1]
    V = HV // H
    cb = next(c for c in (DECODE_BLOCK, 2048, 1024, 512, 256, LANES)
              if HV % c == 0 and c % V == 0)
    nc = HV // cb
    hb = cb // V
    rows, n = work
    interpret = interpret_mode()
    cols = jnp.concatenate([_head_cols(t, nc) for t in (q, k, alpha)], -1)

    def at(s, n_ref):
        return jnp.minimum(s, jnp.maximum(n_ref[0] * nc - 1, 0))

    def row_idx(s, rows_ref, n_ref, layer_ref):
        s = at(s, n_ref)
        return (rows_ref[s // nc], 0, s % nc)

    def cols_idx(s, rows_ref, n_ref, layer_ref):
        s = at(s, n_ref)
        return (rows_ref[s // nc], s % nc, 0, 0)

    def state_idx(s, rows_ref, n_ref, layer_ref):
        s = at(s, n_ref)
        return (layer_ref[0], rows_ref[s // nc], 0, s % nc)

    row_spec = pl.BlockSpec((None, 1, cb), row_idx)
    state_spec = pl.BlockSpec((None, None, K, cb), state_idx)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        # on the chip the bound is the live slots' blocks (a dynamic bound);
        # the interpreter runs the static grid and skips the tail
        grid=(B * nc if interpret else jnp.maximum(n[0], 1) * nc,),
        in_specs=[pl.BlockSpec((None, None, K, 3 * hb), cols_idx),
                  row_spec, row_spec, state_spec],
        out_specs=[row_spec, state_spec])
    y, state = pl.pallas_call(
        functools.partial(_decode_kernel, nc=nc, V=V),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, 1, HV), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 6 (after the three prefetched scalars) is the stack
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret, name="kda_decode_step")(
            rows, n, jnp.asarray(layer, jnp.int32).reshape(1), cols,
            v.reshape(B, 1, HV), jnp.repeat(beta, V, axis=-1)[:, None],
            state)
    return y, state


def _decode_xla(state, layer, q, k, v, alpha, beta, active):
    L, B, K, HV = state.shape
    H = q.shape[1]
    s0 = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    s = alpha.transpose(0, 2, 1)[..., None] * s0.reshape(B, K, H, HV // H)
    u = beta[..., None] * (v - jnp.einsum("bkhv,bhk->bhv", s, k,
                                          precision=_HI))
    s = s + jnp.einsum("bhk,bhv->bkhv", k, u, precision=_HI)
    y = jnp.einsum("bkhv,bhk->bhv", s, q, precision=_HI).reshape(B, 1, HV)
    s = s.reshape(B, K, HV)
    if active is not None:
        s = jnp.where(active[:, None, None], s, s0)
    return y, lax.dynamic_update_slice(state, s[None], (layer, 0, 0, 0))


def kda_decode_step(state, layer, q, k, v, g, beta, active=None, work=None
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token a live slot through layer ``layer`` of the state stack.

    ``state`` [L, B, d_k, H * d_v] float32 (donate it: the result aliases
    it); ``q``, ``k``, ``g`` [B, H, d_k] and ``v`` [B, H, d_v] float32 (``g``
    the log-decay, <= 0); ``beta`` [B, H].  ``active`` [B] bool (default:
    every slot); ``work`` is ``ssm.live_rows`` of it, built once by a caller
    that steps many layers.  Returns ``(o [B, 1, H * d_v] float32, state)``;
    a dead slot's ``o`` is zero and its state untouched."""
    L, B, K, HV = state.shape
    f32 = lambda t: t.astype(jnp.float32)
    q, k, v, beta, alpha = f32(q), f32(k), f32(v), f32(beta), jnp.exp(f32(g))
    if _tiles(K, HV // q.shape[1]):
        if work is None:
            work = live_rows(active, B)
        y, state = _decode_pallas(state, layer, q, k, v, alpha, beta, work)
    else:
        y, state = _decode_xla(state, layer, q, k, v, alpha, beta, active)
    if active is not None:      # the kernel never writes a dead slot's row
        y = jnp.where(active[:, None, None], y, 0.0)
    return y, state


# -------------------------------------------------------------- chunk scan

def _inverse_unit_lower(a):
    """``(I + a)^-1`` for ``a`` [..., C, C] strictly lower triangular, by
    HALVES: inside a diagonal block of 2 the inverse is ``I - a`` exactly,
    and a block of ``2b`` follows from its two blocks of ``b``, ``[[P, 0],
    [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]``: two products a level,
    ``log2 C - 1`` levels.  Every intermediate is a block of the inverse
    itself, which the delta rule keeps of order 1 for ``beta`` in (0, 2)
    (``I - beta k k^T`` never stretches); the product form ``(I + N)(I +
    N^2)(I + N^4)...`` costs the same products and forms powers of ``N = -a``
    that pass 1e30 where ``beta |k_t . k_s|`` stays near 2 over a sub-chunk
    (near-parallel keys under a slow decay) before they cancel."""
    C = a.shape[-1]
    t_of = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s_of = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    dot = functools.partial(jnp.matmul, precision=_HI)
    inv = jnp.eye(C, dtype=a.dtype) \
        - jnp.where(t_of >> 1 == s_of >> 1, a, 0.0)
    for level in range(1, max(C - 1, 1).bit_length()):
        # ``a`` between the two blocks of ``2^level`` of a block twice that
        between = (t_of >> level + 1 == s_of >> level + 1) \
            & (t_of >> level != s_of >> level)
        inv = inv - dot(dot(inv, jnp.where(between, a, 0.0)), inv)
    return inv


def _scan_kernel(layer_ref, q_ref, k_ref, v_ref, g_ref, kt_ref, gt_ref,
                 b_ref, h_ref, y_ref, ho_ref, hs_ref, *, K: int, V: int):
    """One grid step: sub-chunk ``c`` (the innermost, sequential axis) of
    column block ``j`` of row ``b``.  ``q_ref``, ``k_ref`` (C, hb K) and
    ``v_ref`` (C, hb V) the block's heads side by side; ``g_ref`` (C, hb K)
    the INCLUSIVE cumulative log-decay ``G`` of this sub-chunk; ``kt_ref``,
    ``gt_ref`` (hb K, C) the keys and ``G`` transposed; ``b_ref`` (C, hb)
    ``beta`` a head a column.  ``hs_ref`` (K, hb V) carries the state from
    sub-chunk to sub-chunk; it is read from the stack on the first and
    written back on the last.  The scores in two levels (the module's
    notes): the diagonal blocks of all the heads first, in one loop over
    columns, then a head at a time the products across sub-blocks and
    everything that follows from the scores."""
    c = pl.program_id(2)
    C, SB = q_ref.shape[0], SUB_BLOCK
    hb = b_ref.shape[1]
    dot = functools.partial(jnp.dot, precision=_HI,
                            preferred_element_type=jnp.float32)

    @pl.when(c == 0)
    def _load():
        hs_ref[...] = h_ref[...]

    t_of = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    s_of = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    s_sb = lax.broadcasted_iota(jnp.int32, (SB, C), 1)

    def columns(s8, carry):
        """Columns ``8 s8 .. 8 s8 + 7`` of the DIAGONAL blocks of every
        head's two score matrices (a dynamic load takes whole sublane rows
        of 8): ``k_s o D[:, s]`` against the keys and queries of the
        column's own sub-block.  Row ``i`` of a head's carry is row ``i`` of
        that sub-block: every column lies in one.  One loop for all the
        heads: their columns are independent, and side by side in one body
        each hides the others' lane reductions."""
        at = pl.ds(pl.multiple_of(s8 * 8, 8), 8)
        own = pl.ds(pl.multiple_of(s8 * 8 // SB * SB, 8), SB)
        out = []
        for i, (a, qk) in enumerate(carry):
            ks = slice(i * K, (i + 1) * K)
            k8, g8 = k_ref[at, ks], g_ref[at, ks]
            kb, qb, Gb = k_ref[own, ks], q_ref[own, ks], g_ref[own, ks]
            for r in range(8):
                x = k8[r:r + 1] * jnp.exp(
                    jnp.minimum(Gb - g8[r:r + 1], 0.0))
                here = s_sb == s8 * 8 + r
                a = jnp.where(here, jnp.sum(kb * x, 1, keepdims=True), a)
                qk = jnp.where(here, jnp.sum(qb * x, 1, keepdims=True), qk)
            out.append((a, qk))
        return tuple(out)

    zero = jnp.zeros((SB, C), jnp.float32)
    diagonals = lax.fori_loop(0, C // 8, columns, ((zero, zero),) * hb)
    for i, diagonal in enumerate(diagonals):
        ks, vs = slice(i * K, (i + 1) * K), slice(i * V, (i + 1) * V)
        q, k, G = q_ref[:, ks], k_ref[:, ks], g_ref[:, ks]
        beta = b_ref[:, i:i + 1]
        # a sub-block's rows against every EARLIER column, through the
        # block's first row r: D[t, s] = exp(G_t - G_r) exp(G_r - G_s)
        blocks = [diagonal]
        for r0 in range(SB, C, SB):
            own, first = slice(r0, r0 + SB), slice(r0, r0 + 1)
            e = jnp.exp(jnp.minimum(G[own] - G[first], 0.0))
            keys = k[:r0] * jnp.exp(jnp.minimum(G[first] - G[:r0], 0.0))
            across = lax.dot_general(
                jnp.concatenate([k[own] * e, q[own] * e], 0),
                jnp.concatenate(
                    [keys, jnp.zeros((C - r0, K), jnp.float32)], 0),
                (((1,), (1,)), ((), ())), precision=_HI,
                preferred_element_type=jnp.float32)
            blocks.append(tuple(
                jnp.where(s_sb < r0, across[j * SB:(j + 1) * SB], diagonal[j])
                for j in range(2)))
        a, qk = (jnp.concatenate(rows, 0) for rows in zip(*blocks))
        a = jnp.where(t_of > s_of, beta * a, 0.0)
        qk = jnp.where(t_of >= s_of, qk, 0.0)
        s0 = hs_ref[:, vs]
        eg = jnp.exp(G)
        u = dot(_inverse_unit_lower(a),
                beta * (v_ref[:, vs] - dot(k * eg, s0)))
        y_ref[:, vs] = dot(q * eg, s0) + dot(qk, u)
        last = gt_ref[ks, C - 1:C]                       # G_C, a column
        hs_ref[:, vs] = jnp.exp(last) * s0 + dot(
            kt_ref[ks, :] * jnp.exp(jnp.minimum(last - gt_ref[ks, :], 0.0)),
            u)

    @pl.when(c == pl.num_programs(2) - 1)
    def _store():
        ho_ref[...] = hs_ref[...]


def _scan_pallas(state, layer, q, k, v, G, beta, C: int):
    L, B, K, HV = state.shape
    S, H = q.shape[1:3]
    V = HV // H
    nq = S // C
    hb = next(c for c in (SCAN_BLOCK, 512, 256, LANES)
              if HV % c == 0 and c % V == 0) // V
    nc = H // hb
    wide = lambda t: t.reshape(B, S, -1)                    # heads on lanes
    # the sub-chunk's keys and G the other way up: [B, nq, H K, C]
    tr = lambda t: wide(t).reshape(B, nq, C, H * K).transpose(0, 1, 3, 2)

    def seq_idx(b, j, c, *_):
        return (b, c, j)

    def tr_idx(b, j, c, *_):
        return (b, c, j, 0)

    def state_idx(b, j, c, layer_ref):
        return (layer_ref[0], b, 0, j)

    key_spec = pl.BlockSpec((None, C, hb * K), seq_idx)
    val_spec = pl.BlockSpec((None, C, hb * V), seq_idx)
    tr_spec = pl.BlockSpec((None, None, hb * K, C), tr_idx)
    state_spec = pl.BlockSpec((None, None, K, hb * V), state_idx)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, nc, nq),
        in_specs=[key_spec, key_spec, val_spec, key_spec, tr_spec, tr_spec,
                  pl.BlockSpec((None, None, None, C, hb),
                               lambda b, j, c, *_: (b, c, j, 0, 0)),
                  state_spec],
        out_specs=[val_spec, state_spec],
        scratch_shapes=[pltpu.VMEM((K, hb * V), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_scan_kernel, K=K, V=V), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, S, HV), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 8 (after the prefetched layer) is the stack
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret_mode(), name="kda_chunk_scan")(
            jnp.asarray(layer, jnp.int32).reshape(1), wide(q), wide(k),
            wide(v), wide(G), tr(k), tr(G),
            beta.reshape(B, nq, C, nc, hb).transpose(0, 1, 3, 2, 4), state)


def _scan_xla(state, layer, q, k, v, G, beta, C: int):
    """The same sub-chunks in ``jax.numpy``: a ``lax.scan`` over them."""
    L, B, K, HV = state.shape
    S, H = q.shape[1:3]
    nq = S // C
    s0 = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    s0 = s0.reshape(B, K, H, HV // H).transpose(0, 2, 1, 3)     # [B,H,K,V]
    lower = jnp.tril(jnp.ones((C, C), bool))

    def sub(s, xs):
        q, k, v, G, beta = xs                   # [B,C,H,*], beta [B,C,H]
        d = jnp.exp(jnp.minimum(G[:, :, None] - G[:, None], 0.0))
        a = jnp.einsum("bthc,bshc,btshc->bhts", k, k, d, precision=_HI)
        qk = jnp.einsum("bthc,bshc,btshc->bhts", q, k, d, precision=_HI)
        bt = beta.transpose(0, 2, 1)[..., None]                 # [B,H,C,1]
        a = jnp.where(lower & ~jnp.eye(C, dtype=bool), bt * a, 0.0)
        qk = jnp.where(lower, qk, 0.0)
        eg = jnp.exp(G)
        rhs = bt * (v.transpose(0, 2, 1, 3) - jnp.einsum(
            "bthc,bhcv->bhtv", k * eg, s, precision=_HI))
        u = jnp.matmul(_inverse_unit_lower(a), rhs, precision=_HI)
        y = jnp.einsum("bthc,bhcv->bthv", q * eg, s, precision=_HI) \
            + jnp.einsum("bhts,bhsv->bthv", qk, u, precision=_HI)
        last = G[:, -1]                                         # [B,H,K]
        s = jnp.exp(last)[..., None] * s + jnp.einsum(
            "bshc,bhsv->bhcv", k * jnp.exp(jnp.minimum(last[:, None] - G,
                                                       0.0)), u,
            precision=_HI)
        return s, y

    split = lambda t: jnp.moveaxis(
        t.reshape((B, nq, C) + t.shape[2:]), 1, 0)
    s, ys = lax.scan(sub, s0, tuple(map(split, (q, k, v, G, beta))))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, HV)
    s = s.transpose(0, 2, 1, 3).reshape(B, K, HV)
    return y, lax.dynamic_update_slice(state, s[None], (layer, 0, 0, 0))


def kda_chunk_scan(state, layer, q, k, v, g, beta, valid=None,
                   chunk: int = 64) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A chunk of every row's prompt through layer ``layer`` of the stack.

    ``state`` [L, B, d_k, H * d_v] float32: each row starts from what it
    holds there (zeros at the start of a sequence) and is left with the
    state after its last real token.  ``q``, ``k``, ``g`` [B, S, H, d_k]
    and ``v`` [B, S, H, d_v] float32 (``g`` the log-decay, <= 0); ``beta``
    [B, S, H].  ``valid`` [B]: the real tokens of each row's ``S`` (default
    all): positions past them take ``g = 0`` and ``beta = 0``.  ``chunk``:
    the sub-chunk of the chunked form.  Returns ``(o [B, S, H * d_v]
    float32 (junk past ``valid``), state)``."""
    L, B, K, HV = state.shape
    S, H = q.shape[1:3]
    f32 = lambda t: t.astype(jnp.float32)
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    if valid is not None:
        real = jnp.arange(S)[None, :] < jnp.asarray(valid,
                                                    jnp.int32)[:, None]
        g = jnp.where(real[..., None, None], g, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
    C = min(chunk, S)
    pad = -S % C
    if pad:     # whole sub-chunks: padding takes g = 0, beta = 0 like a tail
        padded = lambda t: jnp.pad(t, ((0, 0), (0, pad))
                                   + ((0, 0),) * (t.ndim - 2))
        q, k, v, g, beta = map(padded, (q, k, v, g, beta))
    Sp = S + pad
    # the inclusive cumulative log-decay inside each sub-chunk
    G = jnp.cumsum(g.reshape(B, Sp // C, C, H, K), axis=2).reshape(g.shape)
    # the kernel's (C, C) matrices are whole sublane rows of half a lane row
    tiles = _tiles(K, HV // H) and C % 64 == 0
    y, state = (_scan_pallas if tiles else _scan_xla)(
        state, layer, q, k, v, G, beta, C)
    return y[:, :S], state
