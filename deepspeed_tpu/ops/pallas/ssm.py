"""State-space (Mamba-2 / SSD) kernels for a cache that keeps STATE PER SLOT.

A state-space mixer keeps, per layer and per conversation, one running sum
``H`` (``heads x head_dim x d_state``, float32) whatever the conversation's
length:

    H_t = exp(dt_t a) H_{t-1} + dt_t v_t B_t^T        y_t = H_t C_t

``a`` and ``dt`` are per head; ``B`` and ``C`` are shared by the heads of a
GROUP (``groups`` of them, consecutive heads each: head ``h`` of ``H`` reads
group ``h // (H / groups)``; one group: shared by all).  The pool of a serving batch is ``[L_ssm, B, d_state, heads *
head_dim]``: a layer's state of a slot is one ``[N, H*P]`` matrix with the
``(head, channel)`` pairs on the lanes (``H*P`` is the width of a token's
activation row, so ``y``, ``v`` and a head's decay, repeated over its
channels, are plain rows of it) and ``d_state`` on the sublanes (``B_t`` and
``C_t`` are one column a group, shared by the group's ``H*P / groups``
lanes; they come as ``[.., groups * d_state]``, group by group).  The mathematics' ``[heads,
head_dim, d_state]`` is that matrix transposed; stored that way a head's
64-wide channel axis would be half a lane row and ``v_t`` would have to be
re-laid as a column for every head.

Two kernels, each with an XLA path of the same mathematics (the CPU, and
shapes that do not tile):

``ssm_decode_step``: one token for every LIVE slot.  The grid is the live
slots' column blocks and nothing else (a work list like ``decode_sweep``'s:
``live_rows``); a step reads a block of the slot's state from layer
``layer`` of the stack WHERE IT LIES, writes it back in place (the stack is
aliased to the result) and returns ``y``.  A freed slot costs no step and no
byte, and its state is not touched.

``ssd_chunk_scan``: a chunk of a prompt from the state a slot holds to the
state after the chunk's last REAL token, in the chunked (SSD) form: inside a
sub-chunk of ``chunk`` tokens ``y = (L o C B^T)(dt v)`` with ``L_ts =
exp(sum_{r=s+1..t} dt_r a)``, between sub-chunks the recurrence.  Positions
at or past ``valid`` take ``dt = 0``: their decay is ``exp(0) = 1`` and they
add nothing, so a padded tail leaves the state exactly where the last real
token left it.

``causal_conv`` is the depthwise convolution with the tail of pre-activation
inputs a slot keeps (before the scans here and in ``delta_rule.py``, with
SiLU; the whole of a gated short convolution's mixing, with no activation
and no bias: ``models/conv_moe.py``); plain XLA.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .utils import interpret_mode, use_pallas

LANES = 128
#: lanes of a slot's state one decode step reads and writes: 128 x 4096
#: float32 is 2 MB in and 2 MB out, double-buffered 8 MB of VMEM
DECODE_BLOCK = 4096
#: lanes one scan step carries: 16 heads of 64, a 512 KB state in scratch
SCAN_BLOCK = 1024


def causal_conv(u, tail, w, b, valid=None, activation=jax.nn.silu):
    """Depthwise causal convolution with a carried tail, then ``activation``
    (SiLU; None: the sum as it is).

    ``u`` [B, S, C] pre-activation inputs, ``tail`` [B, K-1, C] the last
    ``K-1`` of them before this call (zeros before a sequence), ``w`` [K, C]
    (``w[K-1]`` weighs the current position), ``b`` [C] or None for a
    convolution without a bias.  ``valid`` [B]: how many of the ``S``
    positions are real (default all).  Returns ``(activation(b + sum_j w_j
    u_{t-K+1+j}) [B, S, C] float32, the tail after the last real position
    [B, K-1, C])``: with ``valid`` 0 the tail comes back bit for bit."""
    B, S, C = u.shape
    K = w.shape[0]
    full = jnp.concatenate([tail.astype(u.dtype), u], axis=1)  # [B,S+K-1,C]
    w32 = w.astype(jnp.float32)
    out = sum(w32[j] * full[:, j:j + S].astype(jnp.float32)
              for j in range(K))
    if b is not None:
        out = b.astype(jnp.float32) + out
    if valid is None:
        new_tail = full[:, S:]
    else:
        new_tail = jax.vmap(lambda f, n: lax.dynamic_slice_in_dim(
            f, n, K - 1, axis=0))(full, jnp.asarray(valid, jnp.int32))
    if activation is not None:
        out = activation(out)
    return out, new_tail.astype(tail.dtype)


def live_rows(active, B: int):
    """The decode step's work list: ``(rows [B] int32, n [1] int32)``, the
    live slots first, in order, and how many they are."""
    if active is None:
        return jnp.arange(B, dtype=jnp.int32), jnp.full((1,), B, jnp.int32)
    rows = jnp.argsort(jnp.logical_not(active), stable=True)
    return rows.astype(jnp.int32), \
        jnp.sum(active, dtype=jnp.int32).reshape(1)


def _tiles(N: int, HP: int, groups: int = 1) -> bool:
    """Whether the kernels take the shape: whole lane rows, and with more
    than one group whole lane rows a group, of state and of ``B`` / ``C``."""
    return use_pallas() and HP % LANES == 0 and N % 8 == 0 and (
        groups == 1 or (HP % (groups * LANES) == 0 and N % LANES == 0))


def _group_blocks(HP: int, cb: int, groups: int):
    """How a column block of ``cb`` lanes meets the groups' ``HP / groups``
    lanes: ``(lanes a group, groups a block, block index of ``B`` / ``C``
    for column block c)``.  A block holds whole groups or lies inside one."""
    gl = HP // groups
    per = max(1, cb // gl)
    assert cb % gl == 0 or gl % cb == 0, (HP, cb, groups)
    return gl, per, lambda c: (c * cb) // (gl * per)


def _column(row, N: int):
    """A ``(1, N)`` row as a lane-broadcast ``(N, LANES)`` column."""
    return jnp.transpose(jnp.broadcast_to(row, (LANES, N)))


# ------------------------------------------------------------- decode step

def _decode_kernel(rows_ref, n_ref, layer_ref, x_ref, decay_ref, b_ref,
                   c_ref, h_ref, y_ref, ho_ref, *, nc: int, gl: int):
    """One grid step: block ``s % nc`` of the columns of live slot ``rows[s
    // nc]``.  ``h_ref`` / ``ho_ref`` (N, cb) are the same block of the
    aliased stack; ``x_ref`` (``dt v``) and ``decay_ref`` (``exp(dt a)``)
    are (1, cb) rows, ``b_ref`` / ``c_ref`` (1, groups in the block * N);
    ``gl`` the lanes of a group.  Steps past the live
    ones (a static grid's tail) stay on the last live block and do nothing;
    with no live slot at all the one block the grid visits is copied
    through."""
    N, cb = h_ref.shape

    @pl.when(n_ref[0] == 0)
    def _nothing_live():
        ho_ref[...] = h_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(pl.program_id(0) < n_ref[0] * nc)
    def _step():
        for g in range(b_ref.shape[1] // N):
            of = slice(g * N, (g + 1) * N)
            bb = _column(b_ref[:, of].astype(jnp.float32), N)
            cc = _column(c_ref[:, of].astype(jnp.float32), N)
            for j in range(g * gl // LANES, min(cb, (g + 1) * gl) // LANES):
                sl = slice(j * LANES, (j + 1) * LANES)
                h = decay_ref[:, sl] * h_ref[:, sl] + bb * x_ref[:, sl]
                ho_ref[:, sl] = h
                y_ref[:, sl] = jnp.sum(h * cc, axis=0, keepdims=True)


def _decode_pallas(state, layer, x, decay, Bm, Cm, work, groups):
    L, B, N, HP = state.shape
    cb = next(c for c in (DECODE_BLOCK, 2048, 1024, 512, 256, LANES)
              if HP % c == 0)
    nc = HP // cb
    gl, per, group_block = _group_blocks(HP, cb, groups)
    rows, n = work
    interpret = interpret_mode()

    def at(s, n_ref):
        return jnp.minimum(s, jnp.maximum(n_ref[0] * nc - 1, 0))

    def row_idx(s, rows_ref, n_ref, layer_ref):
        s = at(s, n_ref)
        return (rows_ref[s // nc], 0, s % nc)

    def vec_idx(s, rows_ref, n_ref, layer_ref):
        s = at(s, n_ref)
        return (rows_ref[s // nc], 0, group_block(s % nc))

    def state_idx(s, rows_ref, n_ref, layer_ref):
        s = at(s, n_ref)
        return (layer_ref[0], rows_ref[s // nc], 0, s % nc)

    row_spec = pl.BlockSpec((None, 1, cb), row_idx)
    vec_spec = pl.BlockSpec((None, 1, per * N), vec_idx)
    state_spec = pl.BlockSpec((None, None, N, cb), state_idx)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        # on the chip the bound is the live slots' blocks (a dynamic bound);
        # the interpreter runs the static grid and skips the tail
        grid=(B * nc if interpret else jnp.maximum(n[0], 1) * nc,),
        in_specs=[row_spec, row_spec, vec_spec, vec_spec, state_spec],
        out_specs=[row_spec, state_spec])
    y, state = pl.pallas_call(
        functools.partial(_decode_kernel, nc=nc, gl=gl),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, 1, HP), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 7 (after the three prefetched scalars) is the stack
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret, name="ssm_decode_step")(
            rows, n, jnp.asarray(layer, jnp.int32).reshape(1),
            x.reshape(B, 1, HP), decay.reshape(B, 1, HP),
            Bm.reshape(B, 1, groups * N), Cm.reshape(B, 1, groups * N),
            state)
    return y[:, 0], state


def ssm_decode_step(state, layer, x, decay, Bm, Cm, active=None, work=None,
                    groups: int = 1):
    """One token a live slot through layer ``layer`` of the state stack.

    ``state`` [L, B, N, H*P] float32 (donate it: the result aliases it);
    ``x`` [B, H*P] float32, ``dt v``; ``decay`` [B, H*P] float32, ``exp(dt
    a)`` with a head's value on each of its channels; ``Bm``, ``Cm`` [B,
    groups * N], group by group.  ``active`` [B] bool (default: every slot); ``work`` is ``live_rows`` of
    it, built once by a caller that steps many layers.  Returns ``(y [B,
    H*P] float32, state)``; a dead slot's ``y`` is zero and its state
    untouched."""
    L, B, N, HP = state.shape
    x = x.astype(jnp.float32)
    decay = decay.astype(jnp.float32)
    if _tiles(N, HP, groups):
        if work is None:
            work = live_rows(active, B)
        y, state = _decode_pallas(state, layer, x, decay, Bm, Cm, work,
                                  groups)
    else:
        h0 = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
        # [B, N, groups, lanes a group]: a group's column on its lanes
        bm = jnp.swapaxes(Bm.astype(jnp.float32).reshape(B, groups, N), 1, 2)
        cm = jnp.swapaxes(Cm.astype(jnp.float32).reshape(B, groups, N), 1, 2)
        grouped = lambda t: t.reshape(t.shape[:-1] + (groups, HP // groups))
        h = decay[:, None, :] * h0 + (
            bm[..., None] * grouped(x)[:, None]).reshape(B, N, HP)
        y = jnp.einsum("bngc,bng->bgc", grouped(h), cm,
                       precision=lax.Precision.HIGHEST).reshape(B, HP)
        if active is not None:
            h = jnp.where(active[:, None, None], h, h0)
        state = lax.dynamic_update_slice(state, h[None], (layer, 0, 0, 0))
    if active is not None:      # the kernel never writes a dead slot's row
        y = jnp.where(active[:, None], y, 0.0)
    return y, state


# -------------------------------------------------------------- chunk scan

def _scan_kernel(layer_ref, x_ref, cs_ref, cst_ref, b_ref, bt_ref, c_ref,
                 h_ref, y_ref, ho_ref, hs_ref, *, P: int, gl: int):
    """One grid step: sub-chunk ``q`` (the innermost, sequential axis) of
    column block ``c`` of row ``b``.  ``x_ref`` (Q, cb) float32 ``dt v``;
    ``cs_ref`` (Q, hb) and ``cst_ref`` (hb, Q) the inclusive cumulative
    ``dt a`` of this sub-chunk for the block's ``hb`` heads, both ways up;
    ``b_ref`` / ``c_ref`` (Q, groups in the block * N), ``bt_ref`` the
    former transposed; ``gl`` the lanes of a group.  ``hs_ref`` (N, cb)
    carries the state from sub-chunk to sub-chunk; it is read from the
    stack on the first and written back on the last."""
    q = pl.program_id(2)
    Q, cb = x_ref.shape
    N = hs_ref.shape[0]
    per = LANES // P                     # heads in one 128-lane slab

    @pl.when(q == 0)
    def _load():
        hs_ref[...] = h_ref[...]

    causal = lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= \
        lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    head_of = lax.broadcasted_iota(jnp.int32, (Q, LANES), 1) // P
    for j in range(cb // LANES):
        if j * LANES % gl == 0:          # the slab opens a group: its C B^T
            of = slice(j * LANES // gl * N, (j * LANES // gl + 1) * N)
            cm = c_ref[:, of]
            g = jnp.dot(cm, bt_ref[of, :],
                        preferred_element_type=jnp.float32)
            c32 = cm.astype(jnp.float32)
            bt32 = bt_ref[of, :].astype(jnp.float32)
        sl = slice(j * LANES, (j + 1) * LANES)
        x = x_ref[:, sl]
        y = jnp.zeros((Q, LANES), jnp.float32)
        cw = jnp.zeros((Q, LANES), jnp.float32)
        for i in range(per):
            h = j * per + i
            col, row = cs_ref[:, h:h + 1], cst_ref[h:h + 1, :]
            mine = head_of == i
            # decay from s to t, s <= t: exp of a number <= 0
            m = jnp.where(causal, g * jnp.exp(jnp.minimum(col - row, 0.0)),
                          0.0)
            y += jnp.dot(m.astype(cm.dtype),
                         jnp.where(mine, x, 0.0).astype(cm.dtype),
                         preferred_element_type=jnp.float32)
            cw = jnp.where(mine, col, cw)
        hs = hs_ref[:, sl]
        y += jnp.exp(cw) * jnp.dot(c32, hs, precision=lax.Precision.HIGHEST,
                                   preferred_element_type=jnp.float32)
        y_ref[:, sl] = y
        last = cw[Q - 1:Q]
        hs_ref[:, sl] = jnp.exp(last) * hs + jnp.dot(
            bt32, x * jnp.exp(last - cw), precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    @pl.when(q == pl.num_programs(2) - 1)
    def _store():
        ho_ref[...] = hs_ref[...]


def _scan_pallas(state, layer, x, cs, Bm, Cm, Q: int, P: int, groups: int):
    L, B, N, HP = state.shape
    S = x.shape[1]
    nq = S // Q
    cb = next(c for c in (SCAN_BLOCK, 512, 256, LANES) if HP % c == 0)
    hb = cb // P
    nc = HP // cb
    gl, per, group_block = _group_blocks(HP, cb, groups)
    # the block's heads' cumulative decays, both ways up: [B, nq, nc, Q, hb]
    cs5 = cs.reshape(B, nq, Q, nc, hb).transpose(0, 1, 3, 2, 4)
    bt = Bm.reshape(B, nq, Q, groups * N).transpose(0, 1, 3, 2)

    def seq_idx(b, c, q, *_):
        return (b, q, c)

    def head_idx(b, c, q, *_):
        return (b, q, c, 0, 0)

    def shared_idx(b, c, q, *_):
        return (b, q, group_block(c))

    def state_idx(b, c, q, layer_ref):
        return (layer_ref[0], b, 0, c)

    state_spec = pl.BlockSpec((None, None, N, cb), state_idx)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B, nc, nq),
        in_specs=[
            pl.BlockSpec((None, Q, cb), seq_idx),
            pl.BlockSpec((None, None, None, Q, hb), head_idx),
            pl.BlockSpec((None, None, None, hb, Q), head_idx),
            pl.BlockSpec((None, Q, per * N), shared_idx),
            pl.BlockSpec((None, None, per * N, Q),
                         lambda b, c, q, *_: (b, q, group_block(c), 0)),
            pl.BlockSpec((None, Q, per * N), shared_idx),
            state_spec],
        out_specs=[pl.BlockSpec((None, Q, cb), seq_idx), state_spec],
        scratch_shapes=[pltpu.VMEM((N, cb), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_scan_kernel, P=P, gl=gl), grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, S, HP), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret_mode(), name="ssd_chunk_scan")(
            jnp.asarray(layer, jnp.int32).reshape(1), x, cs5,
            cs5.transpose(0, 1, 2, 4, 3), Bm, bt, Cm, state)


def _scan_xla(state, layer, x, cs, Bm, Cm, Q: int, P: int, groups: int):
    """The same sub-chunks in ``jax.numpy``: a ``lax.scan`` over them."""
    L, B, N, HP = state.shape
    S = x.shape[1]
    H, nq, G = HP // P, S // Q, groups
    hi = lax.Precision.HIGHEST
    h0 = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    causal = jnp.tril(jnp.ones((Q, Q), bool))

    def sub(h, xs):
        x, cs, bm, cm = xs          # [B,Q,HP] [B,Q,H] [B,Q,G*N] [B,Q,G*N]
        bm = bm.astype(jnp.float32).reshape(B, Q, G, N)
        cm = cm.astype(jnp.float32).reshape(B, Q, G, N)
        g = jnp.einsum("btgn,bsgn->btsg", cm, bm, precision=hi)
        lts = jnp.exp(jnp.minimum(
            cs[:, :, None, :] - cs[:, None, :, :], 0.0))     # [B,t,s,H]
        m = jnp.where(causal[None, :, :, None],
                      jnp.repeat(g, H // G, axis=-1) * lts, 0.0)
        xh = x.reshape(B, Q, H, P)
        y = jnp.einsum("btsh,bshp->bthp", m, xh, precision=hi)
        wide = lambda t: jnp.repeat(t, P, axis=-1)           # [.., H]->HP
        # a group's lanes apart: [.., G, HP / G]
        grouped = lambda t: t.reshape(t.shape[:-1] + (G, HP // G))
        y = y.reshape(B, Q, HP) + wide(jnp.exp(cs)) * jnp.einsum(
            "btgn,bngc->btgc", cm, grouped(h),
            precision=hi).reshape(B, Q, HP)
        last = cs[:, -1:]
        h = wide(jnp.exp(last)) * h + jnp.einsum(
            "bsgn,bsgc->bngc", bm, grouped(x * wide(jnp.exp(last - cs))),
            precision=hi).reshape(B, N, HP)
        return h, y

    split = lambda t: jnp.moveaxis(
        t.reshape((B, nq, Q) + t.shape[2:]), 1, 0)
    h, ys = lax.scan(sub, h0, (split(x), split(cs), split(Bm), split(Cm)))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, S, HP)
    return y, lax.dynamic_update_slice(state, h[None], (layer, 0, 0, 0))


def ssd_chunk_scan(state, layer, v, dt, a, Bm, Cm, valid=None,
                   chunk: int = 256, groups: int = 1
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """A chunk of every row's prompt through layer ``layer`` of the stack.

    ``state`` [L, B, N, H*P] float32: each row starts from what it holds
    there (zeros at the start of a sequence) and is left with the state
    after its last real token.  ``v`` [B, S, H, P]; ``dt`` [B, S, H]
    float32, positive (softplus applied); ``a`` [H] float32, negative;
    ``Bm``, ``Cm`` [B, S, groups * N], group by group.  ``valid`` [B]: the real tokens of each row's
    ``S`` (default all): positions past them take ``dt = 0``.  ``chunk``:
    the sub-chunk of the SSD form.  Returns ``(y [B, S, H*P] float32, ``H_t
    C_t`` at every position (junk past ``valid``), state)``."""
    L, B, N, HP = state.shape
    S, H, P = v.shape[1:]
    dt = dt.astype(jnp.float32)
    if valid is not None:
        dt = jnp.where(jnp.arange(S)[None, :, None]
                       < jnp.asarray(valid, jnp.int32)[:, None, None],
                       dt, 0.0)
    Q = min(chunk, S)
    pad = -S % Q
    if pad:     # whole sub-chunks: padding takes dt = 0 like any tail
        padded = lambda t: jnp.pad(t, ((0, 0), (0, pad))
                                   + ((0, 0),) * (t.ndim - 2))
        v, dt, Bm, Cm = map(padded, (v, dt, Bm, Cm))
    Sp = S + pad
    x = (dt[..., None] * v.astype(jnp.float32)).reshape(B, Sp, HP)
    # the inclusive cumulative dt a inside each sub-chunk
    cs = jnp.cumsum((dt * a.astype(jnp.float32)).reshape(B, Sp // Q, Q, H),
                    axis=2).reshape(B, Sp, H)
    tiles = _tiles(N, HP, groups) and LANES % P == 0 and Q % 8 == 0 \
        and (Q % LANES == 0 or Sp == Q)
    y, state = (_scan_pallas if tiles else _scan_xla)(
        state, layer, x, cs, Bm, Cm, Q, P, groups)
    return y[:, :S], state
