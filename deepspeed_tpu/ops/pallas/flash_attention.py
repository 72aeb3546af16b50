"""Flash attention (causal / full) as a Pallas TPU kernel, fwd + bwd.

TPU-native counterpart of the reference's fused attention CUDA kernels
(``csrc/transformer/softmax_kernels.cu`` + strided-batch-gemm attention in
``csrc/includes/strided_batch_gemm.h``, and the inference
``softmax_context`` path of ``csrc/transformer/inference/csrc/pt_binding.cpp``).
Rather than separate gemm/softmax launches stitched on streams, one Pallas
kernel streams (block_k, W) K/V tiles through VMEM against a resident Q
block with the online-softmax recurrence, so the S×S score matrix never
exists in HBM and VMEM stays O(block · W) regardless of sequence length.

Operands and results are token-major rows ``[B, S, H*D]``: a block is
``(1, block, W)`` lanes, two heads of 64 to a 128-lane tile or one head of
a multiple of 128 (:func:`token_major`, :func:`_head`), so q, k, v, o and
their gradients stay where the projections around the call leave and want
them; the packed qkv product ``[B, S, 3*H*D]`` is read in place through
three index maps.  Grid layout is (batch, head_blocks, q_blocks, k_blocks)
with the k dimension innermost: Pallas revisits the same output block
across the k sweep and pipelines the K/V tile DMAs, while the softmax
running state (acc, m, l) lives in VMEM scratch that persists across grid
steps on the same core.

Causal masking is end-aligned (a query attends to the last ``Sq`` positions
of ``Sk``), matching :func:`mha_reference` for cross-length decode shapes.
Blocks wholly above the diagonal are skipped; a block ON the diagonal is
walked in causal row strips that leave out the sub-tiles above it
(:func:`_causal_tile`, :func:`causal_tile_plan`), forward and backward.

Backward off saved (O, logsumexp): one fused K sweep whose dq accumulates
in float32 in VMEM and leaves in the operands' type, or, past
``MAX_FUSED_BWD_NK`` K blocks, the standard two-kernel flash backward (dq
sweep and dk/dv sweep).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .utils import interpret_mode, use_pallas

NEG_INF = float("-inf")

#: the remat name of an attention output (``remat_policy="attn_out"``)
ATTN_OUT_NAME = "ds_attn_out"


# ------------------------------------------------------------------ reference

def mha_reference(q, k, v, causal: bool = True, sm_scale: Optional[float] = None,
                  kv_lens=None):
    """Dense softmax attention; ground truth for the kernel. [B,S,H,D].
    ``kv_lens`` [B]: keys at position ≥ kv_lens[b] are masked (right-padded
    batches)."""
    D = q.shape[-1]
    B, Sq = q.shape[:2]
    Sk = k.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((Sq, Sk), dtype=bool), k=Sk - Sq)
        mask = jnp.broadcast_to(mask[None], (B, Sq, Sk))
    else:
        mask = jnp.ones((B, Sq, Sk), dtype=bool)
    if kv_lens is not None:
        mask = jnp.logical_and(
            mask, (jnp.arange(Sk)[None, :] < kv_lens[:, None])[:, None])
    if not causal and kv_lens is None:
        p = jax.nn.softmax(s, axis=-1)
    else:
        # rows with zero visible keys (Sq > Sk causal heads, or kv_len 0)
        # get zero output instead of softmax-over-(-inf) NaNs
        s = jnp.where(mask[:, None], s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0))
        e = jnp.where(mask[:, None], e, 0.0)
        denom = jnp.sum(e, axis=-1, keepdims=True)
        p = e / jnp.maximum(denom, 1e-30)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)


def _causal_mask(s, qi, ki, block_q, block_k, offset):
    """End-aligned causal mask on a (block_q, block_k) score tile."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    return jnp.where(k_pos <= q_pos + offset, s, NEG_INF)


def _lens_mask(s, ki, block_k, kv_len):
    """Mask key columns at global position ≥ kv_len (right-padded rows)."""
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(k_pos < kv_len, s, NEG_INF)


def _band_lower_mask(s, qi, ki, block_q, block_k, offset, window):
    """Mask keys below the banded-causal window: keep k_pos such that
    q_pos + offset - k_pos < window (GPT-Neo local attention; ``window``
    is a traced scalar, >= Sk degenerates to no-op pure causal)."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos + offset - k_pos < window, s, NEG_INF)


def _block_visible(qi, ki, block_q, block_k, offset):
    """Whether any (q, k) pair in this tile survives the causal mask."""
    return ki * block_k <= qi * block_q + block_q - 1 + offset


def _block_crosses_mask(qi, ki, block_q, block_k, offset, causal, use_lens,
                        kv_len, use_window=False, window=0):
    """Whether this tile needs masking at all.  Interior tiles (fully below
    the diagonal AND fully inside every row's live prefix AND inside the
    band) skip the iota/compare/select VPU work — on short-head-dim shapes
    the kernels are VPU-bound (exp + mask ops), not MXU-bound, so this is
    the fast path."""
    crosses = False
    if causal:
        # last key column of the tile vs first query row of the tile
        crosses = (ki + 1) * block_k - 1 > qi * block_q + offset
    if use_lens:
        crosses = jnp.logical_or(crosses, (ki + 1) * block_k > kv_len)
    if use_window:
        # some (q, k) pair falls below the band's lower edge: the tile's
        # max distance (last q row vs first k column) reaches the window
        max_dist = (qi + 1) * block_q - 1 + offset - ki * block_k
        crosses = jnp.logical_or(crosses, max_dist >= window)
    return crosses


def _band_block_visible(qi, ki, block_q, block_k, offset, window):
    """Whether any pair in this tile is inside the band's lower edge (the
    min distance — first q row vs last k column — must be < window)."""
    return qi * block_q + offset - ((ki + 1) * block_k - 1) < window


def _scores(q, ks, sm_scale):
    """Scaled float32 scores of query rows against key rows.  MXU operands
    stay in the input dtype (bf16 in production) with f32 accumulation — an
    fp32 cast before the dot would run the systolic array at a fraction of
    its bf16 rate."""
    return jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32) * sm_scale


def _no_mask(s):
    return s


# ------------------------------------------------------ causal sub-tile plan

#: side of the sub-tile :func:`causal_tile_plan` counts in: the lane width,
#: the smallest strip height :func:`_causal_tile` hands out
SUB_TILE = 128


def _causal_tile(Sq, Sk, block_q, block_k, causal, use_lens=False,
                 use_window=False, backward=False) -> int:
    """Height of the causal row strips a kernel walks a DIAGONAL block in, or
    0 where it runs the whole-block body: the one place that decides whether
    the path engages (the kernels and :func:`causal_tile_plan` both ask it).

    It engages on what is static at the call: pure causal attention over
    equal lengths (so ``offset == 0``), square blocks, no ``kv_lens``, no
    ``window``.  There the only visible blocks that cross the mask are the
    ``qi == ki`` ones and the block's own offsets cancel, so every slice is
    static: strip ``i`` is query rows ``[i T, (i+1) T)`` against keys
    ``[0, (i+1) T)`` of the resident block, ``n (n+1) / 2`` of the ``n^2``
    sub-tiles, ``n = block / T``.

    ``T`` is derived from the block, as ``decode_attention.decode_block_k``
    derives its block from the row; no knob.  Chosen from a sweep on a TPU
    v5e (PR 37; a kernel's device time in a profiler trace, ms; bf16, D 64,
    block 1024; whole-block body / T 128 / 256 / 512), at the train cells'
    per-chip shapes, a head of one block and of 2 x 2:

    ========  =============================  =============================
    kernel    [24 x 16 heads, 1024, 64]      [8 x 32 heads, 2048, 64]
    ========  =============================  =============================
    forward   1.662 / 1.454 / 1.341 / 1.234  3.881 / 4.052 / 3.676 / 3.468
    backward  2.963 / 2.184 / 2.155 / 2.408  6.493 / 5.374 / 5.340 / 5.655
    ========  =============================  =============================

    The backward is paced by the matrix unit (96% of its issue slots in the
    whole-block body), so it gains what the strips leave out, less what
    short strips lose in filling it: 256 at both shapes, the strips taken
    bottom to top.  The forward is paced by vector stores and latency and
    a strip's fixed costs show, so tall strips: 512 at both, top to bottom.
    (Where a head is ONE block its grid indices are constants, the
    compiler folds the causal mask and the whole-block body already skips
    the vector work above the diagonal; what the strips save there is the
    products and, since they finish their rows, the round trip of the
    softmax state through scratch, without which 512 reads 1.862 and only
    128 gains, 1.606.)  Other orders and shapes of the pieces were slower:
    ``PERF.md`` 6, PR 37.
    """
    if not causal or use_lens or use_window or Sq != Sk \
            or block_q != block_k:
        return 0
    tile = 256 if backward else 512
    if block_q % tile or block_q // tile < 2:
        return 0
    return tile


def _causal_strips(block, tile):
    """The lower triangle of a diagonal block as ``(rows, cols)`` strips, top
    to bottom: query rows ``[i tile, (i+1) tile)`` meet keys
    ``[0, (i+1) tile)``."""
    return [(slice(i * tile, (i + 1) * tile), slice(0, (i + 1) * tile))
            for i in range(block // tile)]


def _strip_mask(first_row):
    """Causal mask of a strip of :func:`_causal_strips` whose first query
    row is ``first_row`` of the block: rows and keys count from the block's
    own start, which cancels on the diagonal, so local positions decide."""
    def mask(s):
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        return jnp.where(col <= row + first_row, s, NEG_INF)
    return mask


def causal_tile_plan(Sq, Sk, block_q, block_k, causal, use_lens=False,
                     use_window=False) -> tuple:
    """``(visited, square)`` for one head: the ``SUB_TILE``-square sub-tiles
    of the score matrix whose products the forward and the backward sweep
    compute, and those of the whole blocks they computed before a diagonal
    block was walked in causal strips.  Host arithmetic on the static facts
    the kernels branch on (:func:`_causal_tile`).  Where the path does not
    engage ``visited == square``: the blocks the static causal skip leaves
    (``kv_lens`` and ``window`` skip more at run time, which no static count
    sees)."""
    nq, nk = Sq // block_q, Sk // block_k
    offset = Sk - Sq
    blocks = sum(1 for qi in range(nq) for ki in range(nk)
                 if not causal or _block_visible(qi, ki, block_q, block_k,
                                                 offset))
    per_block = pl.cdiv(block_q, SUB_TILE) * pl.cdiv(block_k, SUB_TILE)
    square = 2 * blocks * per_block
    visited = square
    for backward in (False, True):
        tile = _causal_tile(Sq, Sk, block_q, block_k, causal, use_lens,
                            use_window, backward)
        if tile:
            # engaged: Sq == Sk in square blocks, so nq diagonal blocks
            n, sub = block_q // tile, (tile // SUB_TILE) ** 2
            visited -= nq * (n * (n - 1) // 2) * sub
    return visited, square


#: the [visited, square, calls, packed calls] sums opened by
#: :func:`tally_causal_tiles`, innermost last
_tallies = []


@contextlib.contextmanager
def tally_causal_tiles():
    """Sums over the kernel calls traced inside the block (the dense
    fallbacks count nothing) into the ``[visited, square, calls, packed]`` it
    yields: :func:`causal_tile_plan` of one head of each call site, the call
    sites, and those of them that hand the kernels the packed qkv product
    in token-major rows (nothing re-laid around the call).  The train engine
    opens it around the trace of its loss and records the four as counters."""
    tally = [0, 0, 0, 0]
    _tallies.append(tally)
    try:
        yield tally
    finally:
        _tallies.pop()


# ------------------------------------------------------------ operand layout

#: lanes of a vector register: the width of a block's minor dimension
LANES = 128


def token_major(H: int, D: int) -> bool:
    """Whether ``H`` heads of ``D`` tile the ``H * D`` columns of a
    token-major row into lane-aligned blocks: whole heads of a multiple of
    128, or ``128 / D`` heads to a 128-lane block (two of 64).  Where they do
    not (an odd head count at 64, a head of 80 or 96) the entry makes each
    head a row of its own, ``[B*H, S, D]``, whose block spans the whole minor
    dimension: the same kernels, paid for with a transpose in and out."""
    return D % LANES == 0 or (LANES % D == 0 and (H * D) % LANES == 0)


def _head_block(C: int, D: int) -> int:
    """Lanes of one block of a ``[rows, S, C]`` operand whose heads are
    ``D`` wide: whole heads where they are lane-aligned or the row holds one
    (:func:`token_major`'s other case), else the heads that fill 128."""
    return D if D % LANES == 0 or C == D else LANES


def _in_head(shape, h: int, D: int):
    """Which lanes of a ``[n, W]`` tile are head ``h``'s: two compares of a
    lane index against constants (a floor division of the index costs more
    to lower and to run than the select it feeds)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    if h == 0:
        return lane < D
    if (h + 1) * D == shape[-1]:
        return lane >= h * D
    return (lane >= h * D) & (lane < (h + 1) * D)


def _head(x, h: int, D: int):
    """``x`` (``[n, W]``, a block of ``W / D`` heads) with every lane outside
    head ``h`` zeroed.  A product that contracts the lanes of ``_head(a, h)``
    with those of ``b`` is head ``h``'s alone, and one that keeps the lanes of
    ``_head(b, h)`` is zero outside them, so the heads of a block are summed,
    not selected or shifted: at ``D`` 64 the matrix unit is handed 128 deep
    or 128 wide what it was handed half empty."""
    if x.shape[-1] == D:
        return x
    return jnp.where(_in_head(x.shape, h, D), x, jnp.zeros_like(x))


def _spread(cols, D: int, W: int):
    """``[n, W]`` whose head ``h``'s lanes hold the column ``cols[h]``
    (``[n, 1]``): a per-head row statistic against a block of heads."""
    n = cols[0].shape[0]
    out = jnp.broadcast_to(cols[-1], (n, W))
    for h, col in enumerate(cols[:-1]):
        out = jnp.where(_in_head((n, W), h, D), col, out)
    return out


def _geometry(ops, D):
    """``(R, Sq, Sk, C, W)`` of a call's operands, ``(q, k, v)`` each
    ``[R, S, C]`` or ``(qkv,)`` ``[R, S, 3 C]``: rows, lengths, a head every
    ``D`` of the ``C`` columns, ``W`` lanes a block."""
    R, Sq, C = ops[0].shape
    if len(ops) == 1:
        C //= 3
    return R, Sq, ops[-1].shape[1], C, _head_block(C, D)


def _operand_specs(ops, blocks, W, order):
    """``(arrays, specs)`` of q, k, v for a grid ``(row, head block, ...)``:
    three arrays ``[R, S, C]``, or ONE ``[R, S, 3 C]`` (the qkv product as it
    was written) read through three index maps, a third of its columns each.
    ``blocks``: each operand's rows a block; ``order``: ``(qi, ki)`` from the
    grid's last two indices."""
    packed = len(ops) == 1
    thirds = ops[0].shape[-1] // 3 // W if packed else 0

    def spec(i, block, which):
        return pl.BlockSpec(
            (1, block, W),
            lambda r, c, a, b: (r, order(a, b)[which], c + i * thirds))

    arrays = ops * 3 if packed else ops
    return list(arrays), [spec(0, blocks[0], 0), spec(1, blocks[1], 1),
                          spec(2, blocks[1], 1)]


# ------------------------------------------------------------------- forward

def _fwd_kernel(lens_ref, win_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                *scratch, sm_scale, causal, block_q, block_k, offset,
                use_lens, use_window, len_rows, D, tile, carried):
    """One block of ``W / D`` heads (:func:`_head`).  ``tile``: height of a
    diagonal block's causal strips, 0 for the whole-block body
    (:func:`_causal_tile`).  ``carried``: whether the softmax state lives in
    scratch across grid steps; not where the strips engage and a head is one
    block, whose strips finish their rows."""
    r = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    W = q_ref.shape[-1]
    G = W // D
    kv_len = lens_ref[r // len_rows] if use_lens else 0
    window = win_ref[0] if use_window else 0

    if carried:
        acc_ref, m_ref, l_ref = scratch

        @pl.when(ki == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    run = _block_visible(qi, ki, block_q, block_k, offset) if causal else True
    if use_lens:
        run = jnp.logical_and(run, ki * block_k < kv_len)
    if use_window:
        run = jnp.logical_and(run, _band_block_visible(
            qi, ki, block_q, block_k, offset, window))

    def _update(masked: bool):
        q = q_ref[0]                                       # (BQ, W)
        ks = k_ref[0]                                      # (BK, W)
        vs = v_ref[0]
        alphas, pv = [], None
        for h in range(G):
            s = _scores(_head(q, h, D), ks, sm_scale)
            if masked and causal:
                s = _causal_mask(s, qi, ki, block_q, block_k, offset)
            if masked and use_lens:
                s = _lens_mask(s, ki, block_k, kv_len)
            if masked and use_window:
                s = _band_lower_mask(s, qi, ki, block_q, block_k, offset,
                                     window)
            m_prev = m_ref[h]
            l_prev = l_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            # banded/lens tiles can fully mask a row (m_new still -inf):
            # guard the subtraction so exp(-inf - -inf) never produces NaN —
            # the row's p and alpha correctly come out 0
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe)
            alpha = jnp.exp(m_prev - m_safe)
            m_ref[h] = m_new
            l_ref[h] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            alphas.append(alpha)
            term = jnp.dot(p.astype(vs.dtype), _head(vs, h, D),
                           preferred_element_type=jnp.float32)
            pv = term if pv is None else pv + term
        acc_ref[...] = acc_ref[...] * _spread(alphas, D, W) + pv

    def _update_diagonal():
        # the qi == ki block in causal row strips.  All of the block's keys
        # are resident, so a strip takes its rows' max once: one softmax
        # update a strip, carried in from the earlier ki blocks, and no
        # rescale between strips.  A row's own key is always visible, so
        # m_new is finite and l >= 1.
        for rows, cols in _causal_strips(block_q, tile):
            q, ks, vs = q_ref[0, rows, :], k_ref[0, cols, :], v_ref[0, cols, :]
            alphas, out = [], None
            for h in range(G):
                s = _strip_mask(rows.start)(
                    _scores(_head(q, h, D), ks, sm_scale))
                m_new = jnp.max(s, axis=1, keepdims=True)
                if carried:
                    m_prev = m_ref[h, rows, :]
                    m_new = jnp.maximum(m_prev, m_new)
                    alpha = jnp.exp(m_prev - m_new)
                    alphas.append(alpha)
                    m_ref[h, rows, :] = m_new
                p = jnp.exp(s - m_new)
                l = jnp.sum(p, axis=1, keepdims=True)
                term = jnp.dot(p.astype(vs.dtype), _head(vs, h, D),
                               preferred_element_type=jnp.float32)
                if carried:
                    l_ref[h, rows, :] = l_ref[h, rows, :] * alpha + l
                else:
                    # the head's one block: a strip finishes its rows, and
                    # the output leaves from values, past the scratch state
                    term = term / l
                    lse_ref[0, h, 0, rows] = (m_new + jnp.log(l))[:, 0]
                out = term if out is None else out + term
            if carried:
                acc_ref[rows, :] = acc_ref[rows, :] * _spread(
                    alphas, D, W) + out
            else:
                o_ref[0, rows, :] = out.astype(o_ref.dtype)

    if not carried:
        _update_diagonal()      # the grid's one (qi, ki) is the diagonal's
    elif causal or use_lens or use_window:
        crosses = _block_crosses_mask(qi, ki, block_q, block_k, offset,
                                      causal, use_lens, kv_len,
                                      use_window, window)
        # where the strips engage (_causal_tile) the one visible block that
        # crosses the mask is the diagonal one
        pl.when(jnp.logical_and(run, crosses))(
            _update_diagonal if tile else lambda: _update(True))
        pl.when(jnp.logical_and(run, jnp.logical_not(crosses)))(
            lambda: _update(False))
    else:
        pl.when(run)(lambda: _update(False))

    if carried:
        @pl.when(ki == nk - 1)
        def _finalize():
            ls = [jnp.maximum(l_ref[h], 1e-30) for h in range(G)]
            o_ref[0] = (acc_ref[...] / _spread(ls, D, W)).astype(o_ref.dtype)
            for h in range(G):
                lse_ref[0, h, 0, :] = (m_ref[h] + jnp.log(ls[h]))[:, 0]


def _compiler_params(G, rows, block_k, live, sweep="parallel"):
    """The kernels' grid semantics (``sweep``: the outer of the two sweeps;
    the inner one carries state in scratch) and, where a block of ``G``
    heads needs it, their scoped VMEM.  The 16 MiB default holds the operand
    tiles and one head's float32 score temporaries of a 1024-square block
    (the plans in ``tests/unit/ops/test_tpu_aot_compile.py``); the heads of
    a block are unrolled (as a loop they ran 11-23% slower on a v5e:
    ``PERF.md`` 6, PR 43), so ``live`` such ``[rows, block_k]`` temporaries
    a head are planned at once.  ``rows``: the tallest piece a body walks."""
    need = live * G * rows * block_k * 4 + (6 << 20)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", sweep, "arbitrary"),
        vmem_limit_bytes=need if need > 16 << 20 else None)


def _tallest(tile, block_q, nq, nk):
    """Rows of the tallest piece a kernel's bodies walk: a causal strip
    where a head is its one diagonal block, else a whole block."""
    return tile if tile and nq == nk == 1 else block_q


def _smem_args(lens, win):
    return (jnp.asarray(lens if lens is not None else [0], jnp.int32),
            jnp.asarray([win] if win is not None else [0],
                        jnp.int32).reshape(1))


def _fwd(ops, lens, win, causal, sm_scale, block_q, block_k, D, len_rows):
    """``ops``: ``(q, k, v)``, each ``[R, S, C]`` with a head every ``D``
    columns, or ``(qkv,)``, ``[R, S, 3 C]``.  Returns ``o [R, Sq, C]`` and
    ``lse [R, C / D, 1, Sq]`` float32."""
    R, Sq, Sk, C, W = _geometry(ops, D)
    G = W // D
    offset = Sk - Sq
    tile = _causal_tile(Sq, Sk, block_q, block_k, causal, lens is not None,
                        win is not None)
    carried = not tile or Sk > block_k
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=block_q, block_k=block_k, offset=offset,
                               use_lens=lens is not None,
                               use_window=win is not None, len_rows=len_rows,
                               D=D, tile=tile, carried=carried)
    arrays, specs = _operand_specs(ops, (block_q, block_k), W,
                                   lambda qi, ki: (qi, ki))
    o, lse = pl.pallas_call(
        kernel,
        grid=(R, C // W, Sq // block_q, Sk // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            *specs,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, W), lambda r, c, qi, ki: (r, qi, c)),
            pl.BlockSpec((1, G, 1, block_q),
                         lambda r, c, qi, ki: (r, c, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, Sq, C), ops[0].dtype),
            jax.ShapeDtypeStruct((R, C // D, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, W), jnp.float32),
            pltpu.VMEM((G, block_q, 1), jnp.float32),
            pltpu.VMEM((G, block_q, 1), jnp.float32),
        ] if carried else [],
        interpret=interpret_mode(),
        compiler_params=_compiler_params(
            G, _tallest(tile, block_q, Sq // block_q, Sk // block_k),
            block_k, live=2),
        name="flash_fwd",
    )(*_smem_args(lens, win), *arrays)
    return o, lse


# ------------------------------------------------------------------ backward

def _bwd_products(refs, rows, cols, mask, *, sm_scale, D, want):
    """``(dq, dk, dv)`` contributions (float32, ``[n, W]``, or None where
    not in ``want``) of query ``rows`` against key ``cols`` of the resident
    blocks, summed over the block's heads.  MXU operands stay in the input
    dtype (see _scores); ``delta = rowsum(do * o)`` is taken here from the
    resident rows of ``do`` and ``o``, per head."""
    q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref = refs
    q, do = q_ref[0, rows, :], do_ref[0, rows, :]
    ks, vs = k_ref[0, cols, :], v_ref[0, cols, :]
    o = o_ref[0, rows, :].astype(jnp.float32)
    dq = dk = dv = None

    def add(acc, term):
        return term if acc is None else acc + term

    for h in range(q.shape[-1] // D):
        qh, doh = _head(q, h, D), _head(do, h, D)
        lse = lse_ref[0, h, 0, rows][:, None]
        delta = jnp.sum(doh.astype(jnp.float32) * o, axis=1, keepdims=True)
        p = jnp.exp(mask(_scores(qh, ks, sm_scale)) - lse)
        if "dv" in want:
            dv = add(dv, jax.lax.dot_general(
                p.astype(do.dtype), doh, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        dp = jax.lax.dot_general(doh, vs, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        if "dk" in want:
            dk = add(dk, jax.lax.dot_general(
                ds, qh, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        if "dq" in want:
            dq = add(dq, jnp.dot(ds, _head(ks, h, D),
                                 preferred_element_type=jnp.float32))
    return dq, dk, dv


def _block_mask(qi, ki, kv_len, window, *, causal, block_q, block_k, offset,
                use_lens, use_window):
    """The masks a whole crossing block takes, as one ``scores -> scores``."""
    def mask(s):
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, offset)
        if use_lens:
            s = _lens_mask(s, ki, block_k, kv_len)
        if use_window:
            s = _band_lower_mask(s, qi, ki, block_q, block_k, offset, window)
        return s
    return mask


def _bwd_sweep(qi, ki, kv_len, window, accumulate, idle=None, *, causal,
               block_q, block_k, offset, use_lens, use_window, tile, single):
    """Runs ``accumulate(rows, cols, mask)`` over what block ``(qi, ki)``
    holds under the masks: nothing (``idle()``, where given), the whole block
    bare or masked, or a diagonal block's causal strips.  The strips take p
    from the saved lse, so they are independent: bottom to top, so that the
    short strips' products fill the matrix unit behind the long ones
    (:func:`_causal_tile`'s sweep).  ``single``: the grid's one
    ``(qi, ki)`` is the diagonal's, so no other body is lowered."""
    def _update_diagonal():
        for rows, cols in reversed(_causal_strips(block_q, tile)):
            accumulate(rows, cols, _strip_mask(rows.start))

    if tile and single:
        return _update_diagonal()
    run = _block_visible(qi, ki, block_q, block_k, offset) if causal else True
    if use_lens:
        # the whole K block is beyond this row's live prefix
        run = jnp.logical_and(run, ki * block_k < kv_len)
    if use_window:
        run = jnp.logical_and(run, _band_block_visible(
            qi, ki, block_q, block_k, offset, window))

    def _update(masked: bool):
        accumulate(slice(0, block_q), slice(0, block_k), _block_mask(
            qi, ki, kv_len, window, causal=causal, block_q=block_q,
            block_k=block_k, offset=offset, use_lens=use_lens,
            use_window=use_window) if masked else _no_mask)

    if causal or use_lens or use_window:
        crosses = _block_crosses_mask(qi, ki, block_q, block_k, offset,
                                      causal, use_lens, kv_len,
                                      use_window, window)
        pl.when(jnp.logical_and(run, crosses))(
            _update_diagonal if tile else lambda: _update(True))
        pl.when(jnp.logical_and(run, jnp.logical_not(crosses)))(
            lambda: _update(False))
        if idle is not None:
            pl.when(jnp.logical_not(run))(idle)
    else:
        # run is the literal True here: every block executes _update
        pl.when(run)(lambda: _update(False))


def _bwd_dq_kernel(lens_ref, win_ref, q_ref, k_ref, v_ref, do_ref, o_ref,
                   lse_ref, dq_ref, dq_acc, *, sm_scale, len_rows, D,
                   **masks):
    r = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    kv_len = lens_ref[r // len_rows] if masks["use_lens"] else 0
    window = win_ref[0] if masks["use_window"] else 0
    refs = (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _accumulate(rows, cols, mask):
        dq, _, _ = _bwd_products(refs, rows, cols, mask, sm_scale=sm_scale,
                                 D=D, want=("dq",))
        dq_acc[rows, :] += dq

    _bwd_sweep(qi, ki, kv_len, window, _accumulate, **masks)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(lens_ref, win_ref, q_ref, k_ref, v_ref, do_ref, o_ref,
                    lse_ref, dk_ref, dv_ref, *rest, sm_scale, len_rows, D,
                    emit_dq, **masks):
    """K-sweep backward kernel, two forms selected by the static
    ``emit_dq``:

    - ``emit_dq=False``: the dk/dv half of the classic two-kernel backward
      (dq comes from ``_bwd_dq_kernel``'s separate sweep).
    - ``emit_dq=True``: the fused single-sweep backward — this K-block's dq
      contribution is taken in the same pass, removing the dq kernel's
      recomputation of s and dp and its extra pass over q/k/v/do: 7 → 5
      matmul-equivalents.  ``dq`` of the whole sequence accumulates in
      float32 in VMEM (``dq_acc``, ``[nq, block_q, W]``) over the K blocks
      and leaves once, in the operands' type, during the last of them; one K
      block writes it straight from values, strip by strip.
    """
    dq_ref = None
    if emit_dq:
        dq_ref, rest = rest[0], rest[1:]
    dk_acc, dv_acc = rest[:2]
    dq_acc = rest[2] if len(rest) > 2 else None     # several K blocks
    r = pl.program_id(0)
    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nk = pl.num_programs(2)
    nq = pl.num_programs(3)
    kv_len = lens_ref[r // len_rows] if masks["use_lens"] else 0
    window = win_ref[0] if masks["use_window"] else 0
    refs = (q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if dq_acc is not None:
        @pl.when(ki == 0)
        def _init_dq():
            dq_acc[qi] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

    def _accumulate(rows, cols, mask):
        dq, dk, dv = _bwd_products(
            refs, rows, cols, mask, sm_scale=sm_scale, D=D,
            want=("dq", "dk", "dv") if emit_dq else ("dk", "dv"))
        dv_acc[cols, :] += dv
        dk_acc[cols, :] += dk
        if dq_acc is not None:
            dq_acc[qi, rows, :] += dq
        elif emit_dq:
            # a strip holds all of its rows' keys in this, the one K block
            dq_ref[0, rows, :] = dq.astype(dq_ref.dtype)

    def _idle():
        # one K block: every dq block must be written (unwritten = garbage)
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    _bwd_sweep(qi, ki, kv_len, window, _accumulate,
               _idle if emit_dq and dq_acc is None else None, **masks)

    if dq_acc is not None:
        @pl.when(ki == nk - 1)
        def _emit_dq():
            dq_ref[0] = dq_acc[qi].astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


#: ki extent up to which the fused single-sweep backward runs: its float32
#: dq accumulator holds a whole sequence of one block of heads in VMEM
#: (0.5 MB a 1024 rows of 128 lanes), so the extent bounds it
MAX_FUSED_BWD_NK = 4


def _bwd(ops, o, lse, do, lens, win, causal, sm_scale, block_q, block_k, D,
         len_rows):
    """``(dq, dk, dv)``, each ``[R, S, C]`` in the operands' type: three
    arrays whether ``ops`` is three or the one packed product."""
    R, Sq, Sk, C, W = _geometry(ops, D)
    G = W // D
    dtype = ops[0].dtype
    nq, nk = Sq // block_q, Sk // block_k
    use_lens = lens is not None
    common = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, offset=Sk - Sq, use_lens=use_lens,
                  use_window=win is not None, len_rows=len_rows, D=D,
                  tile=_causal_tile(Sq, Sk, block_q, block_k, causal,
                                    use_lens, win is not None,
                                    backward=True),
                  single=nq == nk == 1)
    smem = [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
    tallest = _tallest(common["tile"], block_q, nq, nk)

    def call(kernel, order, out_specs, out_shape, scratch, name,
             sweep="parallel"):
        """One backward kernel over the grid ``(row, head block, a, b)``,
        ``order(a, b) -> (qi, ki)``."""
        outer, inner = (nq, nk) if order(0, 1) == (0, 1) else (nk, nq)
        arrays, specs = _operand_specs(ops, (block_q, block_k), W, order)

        def q_rows(r, c, a, b):
            return (r, order(a, b)[0], c)

        return pl.pallas_call(
            functools.partial(kernel, **common),
            grid=(R, C // W, outer, inner),
            in_specs=smem + specs + [
                pl.BlockSpec((1, block_q, W), q_rows),          # do
                pl.BlockSpec((1, block_q, W), q_rows),          # o
                pl.BlockSpec((1, G, 1, block_q),
                             lambda r, c, a, b: (r, c, 0, order(a, b)[0])),
            ],
            out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch,
            interpret=interpret_mode(), name=name,
            compiler_params=_compiler_params(G, tallest, block_k, 3, sweep),
        )(*_smem_args(lens, win), *arrays, do, o, lse)

    k_sweep = lambda ki, qi: (qi, ki)
    kv_specs = [pl.BlockSpec((1, block_k, W),
                             lambda r, c, ki, qi: (r, ki, c))] * 2
    kv_shape = [jax.ShapeDtypeStruct((R, Sk, C), dtype)] * 2
    kv_scratch = [pltpu.VMEM((block_k, W), jnp.float32)] * 2
    q_shape = jax.ShapeDtypeStruct((R, Sq, C), dtype)

    if nk <= MAX_FUSED_BWD_NK:
        # dq's block is written during the LAST K block's sweep; until then
        # its index stands still, so nothing is written back
        dq_spec = pl.BlockSpec(
            (1, block_q, W),
            lambda r, c, ki, qi: (r, jnp.where(ki == nk - 1, qi, 0), c))
        dk, dv, dq = call(
            functools.partial(_bwd_dkv_kernel, emit_dq=True), k_sweep,
            kv_specs + [dq_spec], kv_shape + [q_shape],
            kv_scratch + ([pltpu.VMEM((nq, block_q, W), jnp.float32)]
                          if nk > 1 else []), "flash_bwd",
            # dq accumulates across the K blocks: no core may take a part
            sweep="arbitrary")
        return dq, dk, dv

    dq = call(_bwd_dq_kernel, lambda qi, ki: (qi, ki),
              pl.BlockSpec((1, block_q, W), lambda r, c, qi, ki: (r, qi, c)),
              q_shape, [pltpu.VMEM((block_q, W), jnp.float32)],
              "flash_bwd_dq")
    dk, dv = call(functools.partial(_bwd_dkv_kernel, emit_dq=False), k_sweep,
                  kv_specs, kv_shape, kv_scratch, "flash_bwd_dkv")
    return dq, dk, dv


# ----------------------------------------------------------------- custom vjp

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(ops, lens, win, causal, sm_scale, block_q, block_k, D, len_rows):
    o, _ = _fwd(ops, lens, win, causal, sm_scale, block_q, block_k, D,
                len_rows)
    return o


def _flash_fwd(ops, lens, win, causal, sm_scale, block_q, block_k, D,
               len_rows):
    o, lse = _fwd(ops, lens, win, causal, sm_scale, block_q, block_k, D,
                  len_rows)
    # name-tag the backward's residuals so a remat policy can SAVE them:
    # without the lse tag, ``remat_policy="attn_out"`` (which saves the
    # "ds_attn_out"-tagged o) still re-runs this whole forward kernel in
    # the backward just to regenerate lse — tagging both makes the policy
    # actually eliminate the kernel re-run.  The kernel's result IS the
    # array the output product reads, so this is o's one tag.
    # checkpoint_name is a no-op outside jax.checkpoint, so the non-remat
    # path is unchanged.
    o = checkpoint_name(o, ATTN_OUT_NAME)
    lse = checkpoint_name(lse, "ds_attn_lse")
    return o, (ops, o, lse, lens, win)


def _flash_bwd(causal, sm_scale, block_q, block_k, D, len_rows, res, do):
    import numpy as np
    ops, o, lse, lens, win = res
    dq, dk, dv = _bwd(ops, o, lse, do, lens, win, causal, sm_scale, block_q,
                      block_k, D, len_rows)
    # the packed product's gradient is one array again: the kernels return
    # three (benchmarks/chip/flops.py tells a call's work from its results)
    d_ops = (jnp.concatenate([dq, dk, dv], axis=-1),) if len(ops) == 1 \
        else (dq, dk, dv)
    # int32 lens/window: float0 cotangents (non-differentiable inputs)
    lens_ct = None if lens is None else np.zeros(lens.shape, jax.dtypes.float0)
    win_ct = None if win is None else np.zeros(jnp.shape(win),
                                               jax.dtypes.float0)
    return d_ops, lens_ct, win_ct


_flash.defvjp(_flash_fwd, _flash_bwd)


def resolve_env_blocks() -> tuple:
    """The (block_q, block_k) the kernel will use when the caller passes
    none: FLASH_BLOCK_Q/FLASH_BLOCK_K env knobs (on-chip block sweeps) over
    the measured-best default.  Callers that pre-check tiling feasibility
    (models/gpt.py's windowed-flash guard) MUST resolve through this same
    helper so guard and kernel can never disagree."""
    import os
    return (int(os.environ.get("FLASH_BLOCK_Q", 1024)),
            int(os.environ.get("FLASH_BLOCK_K", 1024)))


def _pick_block(seq: int, want: int) -> Optional[int]:
    """A block size dividing ``seq`` that satisfies Mosaic tiling: each of
    the last two block dims must be divisible by (8, 128) or span the full
    array dim.  Blocks land in both sublane (q tiles) and lane (lse)
    position, so: multiple of 128, or the whole (8-aligned, small) sequence.
    """
    for b in (want, 256, 128):
        if b % 128 == 0 and b <= want and seq % b == 0:
            return b
    if seq % 8 == 0 and seq <= 2048:
        return seq  # single whole-sequence block
    return None


# -------------------------------------------------------------------- public

# Below this query length XLA's fused dense attention beats the streaming
# kernel on TPU (measured v5e: dense wins at S=128/512, kernel at S=1024);
# applies only when the caller left block sizes on auto AND the dense
# score tensor stays small enough that the quadratic-memory path cannot
# become the OOM cause (per-layer transient cap below).
FLASH_MIN_SEQ = 1024
DENSE_SCORES_BYTE_CAP = 1 << 30


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    kv_lens=None,
                    window=None):
    """Memory-linear attention. q,k,v: [B, S, H, D] → [B, S, H, D].

    The kernels read token-major rows ``[B, S, H*D]`` where the heads tile
    them (:func:`token_major`), so q, k, v reach them by reshape and the
    result is the ``[B, S, H*D]`` an output projection reads: nothing is
    re-laid before or after a call, forward or backward.  A caller that
    holds the qkv product whole hands it over as it is:
    :func:`flash_attention_packed`.  The result carries the remat name
    ``ds_attn_out`` once, on every path.

    ``kv_lens`` [B] masks keys at positions ≥ kv_lens[b] — right-padded
    batches (BERT MLM) keep the streaming kernel, and blocks entirely
    beyond a row's live prefix are skipped in fwd AND both backward sweeps.
    Lengths are clamped to ≥ 1 (a zero-length row has no defined
    attention output; callers mask its loss anyway).

    ``window`` (causal only; int or traced scalar) restricts visibility to
    the banded-causal ``0 <= dist < window`` (GPT-Neo local attention):
    tiles entirely below the band are skipped in fwd and both backward
    sweeps, so cost is O(S·window) FLOPs at O(block) memory.  A traced
    ``window >= Sk`` degenerates to pure causal, so one compiled program
    serves an alternating global/local layer stack.

    Falls back to the dense reference when the backend has no Pallas path,
    the sequence doesn't tile (tiny/odd test shapes, Sq > Sk causal), or —
    with auto block sizes — the sequence is short enough that dense wins
    (< FLASH_MIN_SEQ).
    """
    return _attend((q, k, v), q.shape[2], causal, sm_scale, block_q, block_k,
                   kv_lens, window)


def flash_attention_packed(qkv, n_head: int, causal: bool = True,
                           sm_scale: Optional[float] = None,
                           block_q: Optional[int] = None,
                           block_k: Optional[int] = None):
    """Self-attention on the qkv product as ONE projection wrote it:
    ``qkv [B, S, 3*H*D]`` (q's heads, then k's, then v's) →
    ``[B, S, H*D]``.  The kernels read their three operands out of the one
    array through three index maps (no slice, no transpose) and return the
    rows the output projection reads; the gradient comes back as one array
    of ``qkv``'s shape.  For a caller with nothing between the product and
    the kernel (no rotary embedding, lengths or window) whose heads no mesh
    axis splits (a third of the columns is not a shard of them: under a
    mesh only rows are mapped).  Falls back as :func:`flash_attention`
    does; heads that do not tile the row (:func:`token_major`) are sliced
    and re-laid as there."""
    return _attend((qkv,), n_head, causal, sm_scale, block_q, block_k, None,
                   None)


def _attend(ops, H, causal, sm_scale, block_q, block_k, kv_lens, window):
    """The one entry behind both public forms.  ``ops``: ``(q, k, v)``
    ``[B, S, H, D]`` or ``(qkv,)`` ``[B, S, 3*H*D]``; the result has the
    rank of its operands."""
    packed = len(ops) == 1
    auto_blocks = block_q is None and block_k is None
    if block_q is None or block_k is None:
        env_q, env_k = resolve_env_blocks()
        block_q = env_q if block_q is None else block_q
        block_k = env_k if block_k is None else block_k
    B, Sq = ops[0].shape[:2]
    Sk = ops[-1].shape[1]
    D = ops[0].shape[-1] // (3 * H) if packed else ops[0].shape[-1]
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    if kv_lens is not None:
        kv_lens = jnp.maximum(jnp.asarray(kv_lens, jnp.int32), 1)
    if window is not None:
        assert causal, "window masking is defined for causal attention"
        window = jnp.maximum(jnp.asarray(window, jnp.int32), 1)
    short_seq_dense = (auto_blocks and Sq < FLASH_MIN_SEQ
                       and B * H * Sq * Sk * 4 <= DENSE_SCORES_BYTE_CAP)
    if (not use_pallas() or bq is None or bk is None
            or (causal and Sq > Sk) or short_seq_dense):
        if window is not None:
            raise ValueError(
                "flash_attention(window=...) has no dense fallback here; "
                "route short/odd shapes through gpt._windowed_attention")
        o = mha_reference(*_unpacked(ops, H), causal=causal,
                          sm_scale=sm_scale, kv_lens=kv_lens)
        return checkpoint_name(o.reshape(B, Sq, H * D) if packed else o,
                               ATTN_OUT_NAME)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)

    def kernel(ops, kv_lens, window):
        b = ops[0].shape[0]     # this shard's rows and heads under a mesh
        h = H if packed else ops[0].shape[2]
        if token_major(h, D):
            rows = tuple(x.reshape(b, x.shape[1], -1) for x in ops)
            o = _flash(rows, kv_lens, window, causal, scale, bq, bk, D, 1)
            return o if packed else o.reshape(b, Sq, h, D)

        def to3(x):  # [B,S,H,D] → [B*H, S, D]: a head a row
            return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], D)

        o3 = _flash(tuple(to3(x) for x in _unpacked(ops, h)), kv_lens,
                    window, causal, scale, bq, bk, D, h)
        o = o3.reshape(b, h, Sq, D).transpose(0, 2, 1, 3)
        return o.reshape(b, Sq, h * D) if packed else o

    mesh = _partition_mesh()
    rows = heads = None
    if mesh is not None:
        # Mosaic kernels cannot be partitioned by the compiler: under a
        # multi-device mesh the call sits in a shard_map, rows over the
        # data-parallel axes and heads over the model axis (attention is
        # independent across both).  A dim its axes do not divide stays
        # whole and that work is repeated on each of their devices.
        from ...parallel.mesh import DP_GROUP, MODEL_AXIS
        rows = tuple(a for a in DP_GROUP if mesh.shape[a] > 1)
        if not rows or B % math.prod(mesh.shape[a] for a in rows):
            rows = None
        if not packed and H % mesh.shape[MODEL_AXIS] == 0:
            heads = MODEL_AXIS
    if _tallies:
        plan = causal_tile_plan(Sq, Sk, bq, bk, causal, kv_lens is not None,
                                window is not None)
        for tally in _tallies:
            tally[0] += plan[0]
            tally[1] += plan[1]
            tally[2] += 1
            tally[3] += int(packed and token_major(H, D))
    if mesh is None:
        return kernel(ops, kv_lens, window)
    from jax.sharding import PartitionSpec as P
    spec = P(rows, None, None) if packed else P(rows, None, heads, None)
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=((spec,) * len(ops), P(rows), P()),
        out_specs=spec, check_vma=False)(ops, kv_lens, window)


def _unpacked(ops, H):
    """``(q, k, v)`` ``[B, S, H, D]`` of a call's operands: themselves, or
    the packed product's three slices (the paths that re-lay their operands
    anyway)."""
    if len(ops) == 3:
        return ops
    B, S, C = ops[0].shape
    qkv = ops[0].reshape(B, S, 3, H, C // (3 * H))
    return tuple(qkv[:, :, i] for i in range(3))


def _partition_mesh():
    """The active multi-device mesh the kernel call must be mapped over,
    or None: one device, no mesh, or a caller that is already inside a
    manual region (ring/Ulysses attention, the pipeline stages, the
    per-worker gradient collapse), where the operands are per-shard."""
    from ...parallel.mesh import get_mesh_manager
    mm = get_mesh_manager(optional=True)
    if mm is None or mm.mesh.size == 1 \
            or jax.sharding.get_abstract_mesh().manual_axes:
        return None
    return mm.mesh
