"""Flash attention (causal / full) as a Pallas TPU kernel, fwd + bwd.

TPU-native counterpart of the reference's fused attention CUDA kernels
(``csrc/transformer/softmax_kernels.cu`` + strided-batch-gemm attention in
``csrc/includes/strided_batch_gemm.h``, and the inference
``softmax_context`` path of ``csrc/transformer/inference/csrc/pt_binding.cpp``).
Rather than separate gemm/softmax launches stitched on streams, one Pallas
kernel streams (block_k, D) K/V tiles through VMEM against a resident Q
block with the online-softmax recurrence, so the S×S score matrix never
exists in HBM and VMEM stays O(block · D) regardless of sequence length.

Grid layout is (batch·heads, q_blocks, k_blocks) with the k dimension
innermost: Pallas revisits the same output block across the k sweep and
pipelines the K/V tile DMAs, while the softmax running state (acc, m, l)
lives in VMEM scratch that persists across grid steps on the same core.

Causal masking is end-aligned (a query attends to the last ``Sq`` positions
of ``Sk``), matching :func:`mha_reference` for cross-length decode shapes.
Blocks wholly above the diagonal are skipped; a block ON the diagonal is
walked in causal row strips that leave out the sub-tiles above it
(:func:`_causal_tile`, :func:`causal_tile_plan`), forward and backward.

Layout: [B, S, H, D] (the model's native layout; [B*H, S, D] internally).
Backward is the standard two-kernel flash backward (dq sweep and dk/dv
sweep) off saved (O, logsumexp).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .utils import interpret_mode, use_pallas

NEG_INF = float("-inf")


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


#: the [visited, square] sums opened by :func:`tally_causal_tiles`,
#: innermost last
_tallies = []


@contextlib.contextmanager
def tally_causal_tiles():
    """Sums :func:`causal_tile_plan` over the kernel calls traced inside the
    block (one head of each call site; the dense fallbacks count nothing)
    into the ``[visited, square]`` it yields.  The train engine opens it
    around the trace of its loss and records the two as counters."""
    tally = [0, 0]
    _tallies.append(tally)
    try:
        yield tally
    finally:
        _tallies.pop()


# ------------------------------------------------------------------- forward

def _fwd_kernel(lens_ref, win_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *,
                sm_scale, causal, block_q, block_k, offset, use_lens,
                use_window, H, tile, carried):
    """``tile``: height of a diagonal block's causal strips, 0 for the
    whole-block body (:func:`_causal_tile`).  ``carried``: whether the
    softmax state lives in scratch across grid steps; not where the strips
    engage and a head is one block, whose strips finish their rows."""
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    kv_len = lens_ref[bh // H] if use_lens else 0
    window = win_ref[0] if use_window else 0

    if carried:
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
        q = q_ref[0]                                       # (BQ, D)
        ks = k_ref[0]                                      # (BK, D)
        vs = v_ref[0]
        s = _scores(q, ks, sm_scale)
        if masked and causal:
            s = _causal_mask(s, qi, ki, block_q, block_k, offset)
        if masked and use_lens:
            s = _lens_mask(s, ki, block_k, kv_len)
        if masked and use_window:
            s = _band_lower_mask(s, qi, ki, block_q, block_k, offset, window)
        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # banded/lens tiles can fully mask a row (m_new still -inf): guard
        # the subtraction so exp(-inf - -inf) never produces NaN — the
        # row's p and alpha correctly come out 0
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe)
        alpha = jnp.exp(m_prev - m_safe)
        m_ref[...] = m_new
        l_ref[...] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(vs.dtype), vs, preferred_element_type=jnp.float32)

    def _update_diagonal():
        # the qi == ki block in causal row strips.  All of the block's keys
        # are resident, so a strip takes its rows' max once: one softmax
        # update a strip, carried in from the earlier ki blocks, and no
        # rescale between strips.  A row's own key is always visible, so
        # m_new is finite and l >= 1.
        for rows, cols in _causal_strips(block_q, tile):
            s = _strip_mask(rows.start)(
                _scores(q_ref[0, rows, :], k_ref[0, cols, :], sm_scale))
            m_new = jnp.max(s, axis=1, keepdims=True)
            if not carried:
                # the head's one block: a strip finishes its rows, and the
                # output leaves from values, past the scratch state
                p = jnp.exp(s - m_new)
                l = jnp.sum(p, axis=1, keepdims=True)
                acc = jnp.dot(p.astype(v_ref.dtype), v_ref[0, cols, :],
                              preferred_element_type=jnp.float32)
                o_ref[0, rows, :] = (acc / l).astype(o_ref.dtype)
                lse_ref[0, 0, rows] = (m_new + jnp.log(l))[:, 0]
                continue
            m_prev = m_ref[rows, :]
            m_new = jnp.maximum(m_prev, m_new)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            m_ref[rows, :] = m_new
            l_ref[rows, :] = l_ref[rows, :] * alpha + jnp.sum(
                p, axis=1, keepdims=True)
            acc_ref[rows, :] = acc_ref[rows, :] * alpha + jnp.dot(
                p.astype(v_ref.dtype), v_ref[0, cols, :],
                preferred_element_type=jnp.float32)

    if causal or use_lens or use_window:
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
            l = jnp.maximum(l_ref[...], 1e-30)
            o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
            lse_ref[0, 0, :] = (m_ref[...] + jnp.log(l))[:, 0]


def _fwd(q3, k3, v3, lens, win, causal, sm_scale, block_q, block_k, H):
    BH, Sq, D = q3.shape
    Sk = k3.shape[1]
    offset = Sk - Sq
    tile = _causal_tile(Sq, Sk, block_q, block_k, causal, lens is not None,
                        win is not None)
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=block_q, block_k=block_k, offset=offset,
                               use_lens=lens is not None,
                               use_window=win is not None, H=H, tile=tile,
                               carried=not tile or Sk > block_k)
    lens_arr = jnp.asarray(lens if lens is not None else [0], jnp.int32)
    win_arr = jnp.asarray([win] if win is not None else [0],
                          jnp.int32).reshape(1)
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, Sq // block_q, Sk // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret_mode(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_fwd",
    )(lens_arr, win_arr, q3, k3, v3)
    return o, lse


# ------------------------------------------------------------------ backward

def _bwd_operands(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, rows,
                  cols):
    """Query ``rows`` and key ``cols`` of the resident blocks, as the
    backward's operands (input dtype: see _scores) and row statistics."""
    q = q_ref[0, rows, :]
    ks = k_ref[0, cols, :]
    vs = v_ref[0, cols, :]
    do = do_ref[0, rows, :]
    lse = lse_ref[0, 0, rows][:, None]
    delta = delta_ref[0, 0, rows][:, None]
    return q, ks, vs, do, lse, delta


def _bwd_ds(p, do, vs, delta, sm_scale, dtype):
    dp = jax.lax.dot_general(do, vs, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return (p * (dp - delta) * sm_scale).astype(dtype)


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


def _bwd_dq_kernel(lens_ref, win_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, dq_acc, *, sm_scale, causal, block_q,
                   block_k, offset, use_lens, use_window, H, tile):
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    kv_len = lens_ref[bh // H] if use_lens else 0
    window = win_ref[0] if use_window else 0

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = _block_visible(qi, ki, block_q, block_k, offset) if causal else True
    if use_lens:
        run = jnp.logical_and(run, ki * block_k < kv_len)
    if use_window:
        run = jnp.logical_and(run, _band_block_visible(
            qi, ki, block_q, block_k, offset, window))

    def _accumulate(rows, cols, mask):
        q, ks, vs, do, lse, delta = _bwd_operands(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, rows, cols)
        p = jnp.exp(mask(_scores(q, ks, sm_scale)) - lse)
        ds = _bwd_ds(p, do, vs, delta, sm_scale, ks.dtype)
        dq_acc[rows, :] += jnp.dot(ds, ks, preferred_element_type=jnp.float32)

    def _update(masked: bool):
        _accumulate(slice(None), slice(None), _block_mask(
            qi, ki, kv_len, window, causal=causal, block_q=block_q,
            block_k=block_k, offset=offset, use_lens=use_lens,
            use_window=use_window) if masked else _no_mask)

    def _update_diagonal():
        # p comes from the saved lse: the strips are independent
        for rows, cols in reversed(_causal_strips(block_q, tile)):
            _accumulate(rows, cols, _strip_mask(rows.start))

    if causal or use_lens or use_window:
        crosses = _block_crosses_mask(qi, ki, block_q, block_k, offset,
                                      causal, use_lens, kv_len,
                                      use_window, window)
        pl.when(jnp.logical_and(run, crosses))(
            _update_diagonal if tile else lambda: _update(True))
        pl.when(jnp.logical_and(run, jnp.logical_not(crosses)))(
            lambda: _update(False))
    else:
        pl.when(run)(lambda: _update(False))

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(lens_ref, win_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, *rest, sm_scale, causal,
                    block_q, block_k, offset, use_lens, use_window, H,
                    emit_dq, tile):
    """K-sweep backward kernel, two forms selected by the static
    ``emit_dq``:

    - ``emit_dq=False``: the dk/dv half of the classic two-kernel backward
      (dq comes from ``_bwd_dq_kernel``'s separate sweep).
    - ``emit_dq=True``: the fused single-sweep backward — this K-block's dq
      contribution is additionally emitted to a per-ki partial buffer
      (each (bh, ki, qi) block written exactly once; XLA sums over ki),
      removing the dq kernel's recomputation of s and dp and its extra
      pass over q/k/v/do: 7 → 5 matmul-equivalents.
    """
    if emit_dq:
        dqp_ref, dk_acc, dv_acc = rest
    else:
        dqp_ref, (dk_acc, dv_acc) = None, rest
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    kv_len = lens_ref[bh // H] if use_lens else 0
    window = win_ref[0] if use_window else 0

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = _block_visible(qi, ki, block_q, block_k, offset) if causal else True
    if use_lens:
        # the whole K block is beyond this row's live prefix: dk/dv stay 0
        run = jnp.logical_and(run, ki * block_k < kv_len)
    if use_window:
        run = jnp.logical_and(run, _band_block_visible(
            qi, ki, block_q, block_k, offset, window))

    def _accumulate(rows, cols, mask):
        q, ks, vs, do, lse, delta = _bwd_operands(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, rows, cols)
        p = jnp.exp(mask(_scores(q, ks, sm_scale)) - lse)
        dv_acc[cols, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = _bwd_ds(p, do, vs, delta, sm_scale, q.dtype)
        dk_acc[cols, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        if emit_dq:
            # a strip holds all of its rows' keys in this block, so the
            # partial dq block is still written once, strip by strip
            dqp_ref[0, 0, rows, :] = jnp.dot(
                ds, ks, preferred_element_type=jnp.float32)

    def _update(masked: bool):
        _accumulate(slice(None), slice(None), _block_mask(
            qi, ki, kv_len, window, causal=causal, block_q=block_q,
            block_k=block_k, offset=offset, use_lens=use_lens,
            use_window=use_window) if masked else _no_mask)

    def _update_diagonal():
        # p comes from the saved lse: the strips are independent.  Bottom
        # to top, so that the short strips' products fill the matrix unit
        # behind the long ones (_causal_tile's sweep)
        for rows, cols in reversed(_causal_strips(block_q, tile)):
            _accumulate(rows, cols, _strip_mask(rows.start))

    def _idle():
        # every dq-partial block must be written (unwritten = garbage)
        dqp_ref[0, 0] = jnp.zeros_like(dqp_ref[0, 0])

    if causal or use_lens or use_window:
        crosses = _block_crosses_mask(qi, ki, block_q, block_k, offset,
                                      causal, use_lens, kv_len,
                                      use_window, window)
        pl.when(jnp.logical_and(run, crosses))(
            _update_diagonal if tile else lambda: _update(True))
        pl.when(jnp.logical_and(run, jnp.logical_not(crosses)))(
            lambda: _update(False))
        if emit_dq:
            pl.when(jnp.logical_not(run))(_idle)
    else:
        # run is the literal True here: every block executes _update
        pl.when(run)(lambda: _update(False))

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


#: ki extent above which the fused single-sweep backward's dq-partial
#: buffer (nk x |dq| fp32) costs more HBM than the second sweep saves
MAX_FUSED_BWD_NK = 4


def _bwd(q3, k3, v3, o3, lse, do3, lens, win, causal, sm_scale, block_q,
         block_k, H):
    BH, Sq, D = q3.shape
    Sk = k3.shape[1]
    offset = Sk - Sq
    use_lens = lens is not None
    lens_arr = jnp.asarray(lens if lens is not None else [0], jnp.int32)
    win_arr = jnp.asarray([win] if win is not None else [0],
                          jnp.int32).reshape(1)
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[:, None, :]                   # (BH, 1, Sq)
    common = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  block_k=block_k, offset=offset, use_lens=use_lens,
                  use_window=win is not None, H=H,
                  tile=_causal_tile(Sq, Sk, block_q, block_k, causal,
                                    use_lens, win is not None,
                                    backward=True))

    nk = Sk // block_k
    if nk <= MAX_FUSED_BWD_NK:
        fused = functools.partial(_bwd_dkv_kernel, emit_dq=True, **common)
        dk, dv, dqp = pl.pallas_call(
            fused,
            grid=(BH, nk, Sq // block_q),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
                pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
                pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
                pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
                pl.BlockSpec((1, 1, block_q), lambda bh, ki, qi: (bh, 0, qi)),
                pl.BlockSpec((1, 1, block_q), lambda bh, ki, qi: (bh, 0, qi)),
            ],
            out_specs=[
                pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
                pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
                pl.BlockSpec((1, 1, block_q, D),
                             lambda bh, ki, qi: (bh, ki, qi, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, Sk, D), k3.dtype),
                jax.ShapeDtypeStruct((BH, Sk, D), v3.dtype),
                jax.ShapeDtypeStruct((BH, nk, Sq, D), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
            ],
            interpret=interpret_mode(),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            name="flash_bwd",
        )(lens_arr, win_arr, q3, k3, v3, do3, lse, delta)
        dq = jnp.sum(dqp, axis=1).astype(q3.dtype)
        return dq, dk, dv

    dq_kernel = functools.partial(_bwd_dq_kernel, **common)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH, Sq // block_q, Sk // block_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, ki: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda bh, qi, ki: (bh, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret_mode(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_bwd_dq",
    )(lens_arr, win_arr, q3, k3, v3, do3, lse, delta)

    dkv_kernel = functools.partial(_bwd_dkv_kernel, emit_dq=False, **common)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BH, Sk // block_k, Sq // block_q),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, ki, qi: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, ki, qi: (bh, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda bh, ki, qi: (bh, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, ki, qi: (bh, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), k3.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret_mode(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_bwd_dkv",
    )(lens_arr, win_arr, q3, k3, v3, do3, lse, delta)
    return dq, dk, dv


# ----------------------------------------------------------------- custom vjp

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q3, k3, v3, lens, win, causal, sm_scale, block_q, block_k, H):
    o, _ = _fwd(q3, k3, v3, lens, win, causal, sm_scale, block_q, block_k, H)
    return o


def _flash_fwd(q3, k3, v3, lens, win, causal, sm_scale, block_q, block_k, H):
    o, lse = _fwd(q3, k3, v3, lens, win, causal, sm_scale, block_q, block_k,
                  H)
    # name-tag the backward's residuals so a remat policy can SAVE them:
    # without the lse tag, ``remat_policy="attn_out"`` (which saves the
    # "ds_attn_out"-tagged o) still re-runs this whole forward kernel in
    # the backward just to regenerate lse — tagging both makes the policy
    # actually eliminate the kernel re-run.  checkpoint_name is a no-op
    # outside jax.checkpoint, so the non-remat path is unchanged.
    from jax.ad_checkpoint import checkpoint_name
    o = checkpoint_name(o, "ds_attn_out")
    lse = checkpoint_name(lse, "ds_attn_lse")
    return o, (q3, k3, v3, o, lse, lens, win)


def _flash_bwd(causal, sm_scale, block_q, block_k, H, res, do3):
    import numpy as np
    q3, k3, v3, o3, lse, lens, win = res
    dq, dk, dv = _bwd(q3, k3, v3, o3, lse, do3, lens, win, causal, sm_scale,
                      block_q, block_k, H)
    # int32 lens/window: float0 cotangents (non-differentiable inputs)
    lens_ct = None if lens is None else np.zeros(lens.shape, jax.dtypes.float0)
    win_ct = None if win is None else np.zeros(jnp.shape(win),
                                               jax.dtypes.float0)
    return dq, dk, dv, lens_ct, win_ct


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
    auto_blocks = block_q is None and block_k is None
    if block_q is None or block_k is None:
        env_q, env_k = resolve_env_blocks()
        block_q = env_q if block_q is None else block_q
        block_k = env_k if block_k is None else block_k
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
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
        return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                             kv_lens=kv_lens)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    if _tallies:
        plan = causal_tile_plan(Sq, Sk, bq, bk, causal, kv_lens is not None,
                                window is not None)
        for tally in _tallies:
            tally[0] += plan[0]
            tally[1] += plan[1]

    def kernel(q, k, v, kv_lens, window):
        b, _, h, _ = q.shape    # this shard's rows and heads under a mesh

        def to3(x):  # [B,S,H,D] → [B*H, S, D]
            return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], D)

        o3 = _flash(to3(q), to3(k), to3(v), kv_lens, window, causal, scale,
                    bq, bk, h)
        return o3.reshape(b, h, Sq, D).transpose(0, 2, 1, 3)

    mesh = _partition_mesh()
    if mesh is None:
        return kernel(q, k, v, kv_lens, window)
    # Mosaic kernels cannot be partitioned by the compiler: under a
    # multi-device mesh the call sits in a shard_map, rows over the
    # data-parallel axes and heads over the model axis (attention is
    # independent across both).  A dim its axes do not divide stays whole
    # and that work is repeated on each of their devices.
    from jax.sharding import PartitionSpec as P
    from ...parallel.mesh import DP_GROUP, MODEL_AXIS
    rows = tuple(a for a in DP_GROUP if mesh.shape[a] > 1)
    if not rows or B % math.prod(mesh.shape[a] for a in rows):
        rows = None
    heads = MODEL_AXIS if H % mesh.shape[MODEL_AXIS] == 0 else None
    qkv = P(rows, None, heads, None)
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=(qkv, qkv, qkv, P(rows), P()),
        out_specs=qkv, check_vma=False)(q, k, v, kv_lens, window)


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
