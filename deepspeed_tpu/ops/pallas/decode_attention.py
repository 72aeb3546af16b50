"""KV-cache decode attention kernel.

Counterpart of the reference's ``softmax_context`` inference kernel
(``csrc/transformer/inference/csrc/pt_binding.cpp`` — fused attention over
the KV cache with the current sequence length masked): one query token per
(batch, head) attends to cache slots ``0..pos`` of a statically-shaped
cache.  The Pallas kernel streams cache blocks through VMEM with the
online-softmax recurrence, and its grid is the LIVE blocks of the LIVE
rows and nothing else (``decode_sweep``): the decode step's grid steps and
its HBM traffic follow the live context, not ``B x S_max``.  A block beyond
a row's ``pos`` (or below its band) costs no step, and a row the caller
marks dead (a freed slot of a serving batch) neither a step nor a byte.
The dense sweep (``_decode``) stops at the frontier INSIDE a row's last
block too: it copies its own blocks, the live tokens of the last rounded
up to a tile's 16 rows, so a row of 300 tokens streams 304 and not 512
(``sweep_token_counts`` counts both; the grouped and the latent sweep still
stream whole blocks).

The decode kernel reads the slot pool WHERE IT LIES.  ``gpt_inference``
stores it ``[L, B, S_max, H*D]`` — a token's heads folded into one row, so
the stored order is row-major on the TPU (a last dimension of 64 would put
the tokens on the lanes instead) — and the kernel's blocks are
``[block_k, H*D]`` tiles of exactly that array: the layer index rides the
scalar prefetch beside ``pos`` and the sweep, a grid step takes all heads of
one block of one slot (the dense sweep by its own asynchronous copies out
of the pool in HBM, the next steps' in flight behind the current one), and
nothing is sliced, transposed or copied to feed it.  The chunk kernel
(``extend``: admission, speculative verify) takes one KEY-VALUE head a
step from a ``[B*Hkv, S_max, D]`` view of one layer, with the whole group
of query heads that share it: ``G * block_q`` query rows against one key
block, products in the query's dtype as the sweeps make them
(``_chunk_kernel``).  An admission that works on its slot's own row of the
pool (``cached_attention(row=)``) reads that row where it lies too
(``_row_chunk``): the same step over LANE BLOCKS of the folded bank, layer
and row by scalar prefetch, a head of 128 lanes with its group a block of
its own and two ungrouped heads of 64 sharing one, each scored with the
other's query lanes zeroed.

A latent cache (``models/latent_moe.py``) keeps ONE row per token and layer,
shared by all heads: ``[c | R(k_r)]``, stored ``[L, B, S_max, W]`` with ``W``
whole lane rows.  Its two kernels (``_latent_decode``, ``_latent_chunk``)
compute the absorbed form: every head's query ``[q' | R(q_r)]`` scores the
row and the probabilities weigh its first ``R`` elements (the latent ``c``
itself; the value up-projection follows outside).  The decode kernel walks
the same work list (``decode_sweep``) and reads the pool in place; a block
is read once for all heads, 121 operations a byte at 64 heads, so it is the
one kernel here that sits near the ridge and not far under it.
``cached_attention`` picks them when the cache has one bank.  A chunk long
enough to pay for it (``latent_up_projects``: the call's static shape
decides, no option does) takes the UP-PROJECTED form instead, in one kernel
of its own (``_latent_up_chunk``): un-absorbed queries, every head's keys
and values made from a block of rows in VMEM through the layer's ``W_kvb``
(``LatentUp``: a head-major stack, the layer by scalar prefetch), a third
of the operations a (query, key) pair; a bias (a selection, a ring's band)
rides along as an int8 mask.  A tick and a few tokens keep the absorbed
kernels, under a bias or under none.

Grouped heads (``kv_heads`` of ``cached_attention``): ``H`` query heads on
``H / G`` key-value heads.  The row stays ``H/G * D``; the decode kernel
(``_gqa_decode``) takes the block with all its key-value heads once and the
``G`` query heads of each share it, the chunk kernel takes a key-value
head's block once for the ``G`` heads of its group, their rows under one
another.  Nothing repeats a key-value head out to its query heads.  The
chunk kernel takes a band (``window``) with ``G > 1``; no family sweeps a
single token over grouped heads under a band (a ring below needs none), and
that pair stays refused.

A RING (``gpt_inference.KVCache.ring``: a window layer's last ``R`` tokens,
position ``p`` in cell ``p mod R``) needs no kernel of its own.  A single
token sweeps it as a pool ``R`` long at frontier ``min(p, R - 1)``: keys are
cached rotated, so the order of the cells does not matter to a softmax and
the sweep needs no band.  A chunk attends BEFORE it is written
(``ring_attention``): the ring is unrolled into the order of its positions,
the chunk's rows follow, and the chunk kernel walks a band of ``window +
chunk`` keys, bounded below by the first cell a token has reached
(``valid_from``).

Int8 cache variant (beyond the reference): k/v arrive as int8 codes with
per-vector fp32 scales and are dequantized IN VMEM after the block load,
so the HBM stream — the decode bottleneck — ships half the bytes.  Decode
is memory-bound, so this is a direct latency/batch-capacity lever, the
same trade the weight-only int8 path makes for weights.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .utils import interpret_mode, use_pallas

NEG_INF = float("-inf")


def dequantize_kv(codes, scale, dtype):
    """int8 codes [..., D] + per-vector scale [..., 1] → ``dtype``."""
    return (codes.astype(jnp.float32) * scale).astype(dtype)


def quantize_kv(x):
    """x [..., D] → (int8 codes, fp32 scale [..., 1]): symmetric
    per-vector quantization of one K or V head vector."""
    x32 = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x32), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax / 127.0, 1e-12)
    return jnp.clip(jnp.round(x32 / scale), -127, 127).astype(jnp.int8), scale


def cached_attention_reference(q, cache_k, cache_v, pos,
                               sm_scale: Optional[float] = None,
                               window=None, slopes=None, valid_from=None):
    """Ground truth: q [B,Sq,H,D] over cache [B,Smax,H,D]; query i (at
    absolute position pos+i) sees cache slots ≤ pos+i.  ``pos`` may be a
    scalar or a per-row [B] vector (ragged decode).  ``window`` (scalar,
    may be traced) bands visibility to ``0 <= dist < window``; ``slopes``
    ([H] fp32) adds the ALiBi bias ``-slope·dist``; ``valid_from`` (scalar
    or [B]) hides the slots before it."""
    B, Sq, H, D = q.shape
    Smax = cache_k.shape[1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, cache_k).astype(jnp.float32) * scale
    pos = jnp.asarray(pos)
    q_abs = (pos.reshape(-1, 1) if pos.ndim else pos) + jnp.arange(Sq)
    k_pos = jnp.arange(Smax)
    # [B or 1, Sq, Smax]
    dist = jnp.atleast_2d(q_abs)[:, :, None] - k_pos[None, None, :]
    mask = dist >= 0
    if window is not None:
        mask = jnp.logical_and(mask, dist < window)
    if valid_from is not None:
        mask = jnp.logical_and(mask, k_pos[None, None, :] >= jnp.asarray(
            valid_from).reshape(-1, 1, 1))
    if slopes is not None:
        s = s - slopes[None, :, None, None] * dist[:, None].astype(jnp.float32)
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), cache_v)


# finite floor for the running max: with a banded window a streamed block
# can be fully masked for every row it executes for; a -inf running max
# would then turn exp(m_prev - m_new) into nan.  Scores never approach
# this, so the recurrence is unchanged on visible keys.
M_FLOOR = -1e30


def _unpack_rest(rest, quantized, windowed, alibi):
    """Positional unpack of everything after the kernel's named scalar
    operands, mirroring the wrappers' argument order: [window?, slopes?,
    q, k, v, kscale?, vscale?, o, acc, m, l] (window is a scalar-prefetch
    operand like them, so it leads)."""
    i = 0
    window_ref = slopes_ref = kscale_ref = vscale_ref = None
    if windowed:
        window_ref = rest[i]; i += 1
    if alibi:
        slopes_ref = rest[i]; i += 1
    q_ref, k_ref, v_ref = rest[i:i + 3]; i += 3
    if quantized:
        kscale_ref, vscale_ref = rest[i:i + 2]; i += 2
    o_ref, acc_ref, m_ref, l_ref = rest[i:i + 4]
    return (window_ref, slopes_ref, q_ref, k_ref, v_ref, kscale_ref,
            vscale_ref, o_ref, acc_ref, m_ref, l_ref)


def _sweep_position(rows_ref, n_ref):
    """Where grid step ``program_id(0)`` stands in the sweep's work list:
    ``(step, row, live, first, last)``: its row, whether it is a live entry
    (before ``n``), and whether it is the first / the last of its row's
    consecutive entries."""
    step = pl.program_id(0)
    n = n_ref[0]
    row = rows_ref[step]
    first = jnp.logical_or(step == 0,
                           rows_ref[jnp.maximum(step - 1, 0)] != row)
    last = jnp.logical_or(
        step == n - 1,
        rows_ref[jnp.minimum(step + 1, rows_ref.shape[0] - 1)] != row)
    return step, row, step < n, first, last


def _online_softmax_step(s, values, acc_ref, m_ref, l_ref, value_scale=None):
    """One block of the online-softmax recurrence: masked float32 scores
    ``s`` [rows, keys] and the block's ``values`` [keys, width] into the
    running max ``m``, sum ``l`` and accumulator ``acc``.  ``value_scale``
    ([rows or 1, keys] float32): an int8 block's scales, which weigh the
    float32 probabilities so that the product takes the codes as they are."""
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    m_ref[...] = m_new
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    if value_scale is not None:
        p = p * value_scale
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p.astype(values.dtype), values, preferred_element_type=jnp.float32)


def _decode_kernel(rows_ref, blocks_ref, n_ref, pos_ref, layer_ref, *rest,
                   sm_scale, block_k, H, D, quantized, windowed, alibi,
                   copy_rows):
    """One online-softmax decode kernel serving every cache layout.  A
    grid step is ONE LIVE BLOCK of one live row: step ``s`` of the flat
    grid reads its row and its block from the sweep (``rows_ref[s]``,
    ``blocks_ref[s]``; ``decode_sweep``) and takes the block's LIVE tokens
    with ALL their heads as the pool stores them.  A row's blocks are
    consecutive steps in rising order; the running max/sum/accumulator are
    reset on its first and written out on its last.  Steps at or past
    ``n_ref[0]`` (the tail of a static grid, or the one step of an empty
    sweep) do nothing.

    The copy is the kernel's own.  ``k_ref``/``v_ref`` are the whole
    pool, left in HBM, and each bank has ``depth`` buffers of one block in
    VMEM: step ``s`` waits for the copy of its block into buffer ``s %
    depth`` and, before it computes, starts the copy of step ``s + depth -
    1`` (the next rows' first blocks at a row's end; the last live steps
    start none), so the stream runs on while a short block is multiplied.
    A copy moves the block's live tokens rounded up to ``copy_rows`` (a
    tile's rows), so a row's last block costs what it holds and not
    ``block_k``: ``copy_rows`` None (the interpreter, which cannot express
    a copy of traced size) moves whole blocks through the same buffers and
    semaphores.  The products stay whole: a step scores and weighs all
    ``block_k`` rows of its buffer and the mask ``k_pos <= pos`` discards
    what lies past the frontier (measured, PERF.md 6, PR 45: a step's two
    products take 0.74-0.86 us where a full block streams in 1.3, and with
    the next two steps' copies in flight the stream hides them; in slices
    up to the frontier the same sweep ran 3% slower).  So a dead row of
    the buffer is multiplied, and ``0 x NaN`` is NaN in ``p @ v``: step 0
    zeroes the buffers, after which they only ever hold cache rows, which
    are finite.

    The per-head products ride two plain matmuls: the query is spread to
    ``(H, H*D)`` with head ``h``'s lanes kept in row ``h`` and zeros
    elsewhere, so ``qx · kᵀ`` is every head's score row at once, and
    ``p · v`` leaves head ``h``'s output in row ``h``'s own lanes (the
    other lanes hold cross-head products nobody reads).  Scores and the
    running max/sum/accumulator are float32.

    With ``quantized`` the k/v blocks arrive as int8 codes (exact in the
    compute dtype) and the per-vector fp32 scales ``(block_k, H)``
    multiply the scores and the probabilities in VMEM instead of every
    element — half the HBM bytes on the memory-bound decode path.  The
    scales stay whole pipelined blocks (a sixteenth of the codes' bytes:
    Mosaic pads a 16-lane row of HBM to 128 and refuses to slice it), and
    a dead token's scale only ever meets a masked score or a zero
    probability.  ``windowed`` bands visibility to the trailing ``window``
    slots (SMEM scalar — it may alternate per layer; the sweep already
    left out the blocks wholly below the band); ``alibi`` adds the
    per-head ``-slope·dist`` bias from a ``(H, 1)`` slope column."""
    (window_ref, slopes_ref, q_ref, k_ref, v_ref, kscale_ref, vscale_ref,
     o_ref, acc_ref, m_ref, l_ref) = _unpack_rest(rest, quantized,
                                                  windowed, alibi)
    banks = (k_ref, v_ref)
    bufs, sems = rest[-3:-1], rest[-1]
    depth = bufs[0].shape[0]         # buffers a bank: copies in flight + 1
    step, row, live, first, last = _sweep_position(rows_ref, n_ref)
    ki = blocks_ref[step]
    pos = pos_ref[row]               # per-ROW visibility (ragged decode)
    slot = step % depth

    def tokens(s):
        """Live tokens of step ``s``'s block: to its row's frontier."""
        return jnp.clip(pos_ref[rows_ref[s]] + 1 - blocks_ref[s] * block_k,
                        1, block_k)

    def copies(s, slot):
        """The copies that bring step ``s``'s block into buffer ``slot``,
        one a bank: the same descriptors start them and wait for them."""
        n = block_k if copy_rows is None else pl.multiple_of(
            (tokens(s) + copy_rows - 1) // copy_rows * copy_rows, copy_rows)
        start = pl.multiple_of(blocks_ref[s] * block_k, block_k)
        return [pltpu.make_async_copy(
            bank.at[layer_ref[0], rows_ref[s], pl.ds(start, n)],
            buf.at[slot, pl.ds(0, n)], sems.at[i, slot])
            for i, (bank, buf) in enumerate(zip(banks, bufs))]

    def own():
        """(H, H*D) mask: lane c of row h belongs to head h."""
        return jax.lax.broadcasted_iota(jnp.int32, (H, H * D), 1) // D == \
            jax.lax.broadcasted_iota(jnp.int32, (H, H * D), 0)

    @pl.when(jnp.logical_and(live, step == 0))
    def _prime():
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)
        for ahead in range(depth - 1):
            @pl.when(ahead < n_ref[0])
            def _start():
                for copy in copies(ahead, ahead):
                    copy.start()

    @pl.when(step + depth - 1 < n_ref[0])
    def _next():
        for copy in copies(step + depth - 1, (step + depth - 1) % depth):
            copy.start()

    @pl.when(jnp.logical_and(live, first))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live)
    def _update():
        for copy in copies(step, slot):
            copy.wait()
        q = q_ref[...]                                     # (1, H*D)
        qx = jnp.where(own(), q.astype(jnp.float32), 0.0).astype(q.dtype)
        ks = _to_compute(bufs[0][slot], q.dtype)           # (BK, H*D)
        vs = _to_compute(bufs[1][slot], q.dtype)
        s = jax.lax.dot_general(qx, ks, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                   # (H, BK)
        if quantized:
            s = s * kscale_ref[...].T
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if alibi:
            s = s - slopes_ref[...] * (pos - k_pos).astype(jnp.float32)
        visible = k_pos <= pos
        if windowed:
            visible = jnp.logical_and(visible, k_pos > pos - window_ref[0])
        _online_softmax_step(jnp.where(visible, s, NEG_INF), vs, acc_ref,
                             m_ref, l_ref,
                             vscale_ref[...].T if quantized else None)

    @pl.when(jnp.logical_and(live, last))
    def _finalize():
        o = jnp.where(own(), acc_ref[...] / l_ref[...], 0.0)
        o_ref[...] = jnp.sum(o, axis=0, keepdims=True).astype(o_ref.dtype)


def _to_compute(x, dtype):
    """A cache block in the query's dtype, for the single-token sweeps and
    the chunk pass alike: the products then run natively (bf16 x bf16 is
    exact, accumulated in float32; an fp32 cast before the dot would only
    multiply the MXU passes).  int8 codes go through float32; every code
    is exact in bf16, and their scales weigh the float32 scores and
    probabilities instead."""
    if x.dtype == jnp.int8:
        x = x.astype(jnp.float32)
    return x.astype(dtype)


def _block_of(sizes, Smax: int, HD: int) -> Optional[int]:
    """The largest of ``sizes`` (tokens, falling) that tiles ``Smax`` and
    holds at most 2**18 cache elements of an ``HD``-wide row; 128, the
    floor, serves any wider row."""
    return next((b for b in sizes if Smax % b == 0
                 and (b * HD <= 1 << 18 or b == 128)), None)


def decode_block_k(Smax: int, HD: int) -> Optional[int]:
    """Tokens in one streamed block of the single-token sweep, or None
    where ``Smax`` does not tile (the dense reference serves it).  A step
    costs only when its block is live; when these sizes were measured the
    size traded the dead tail of a row's last block against a step's fixed
    cost (~0.7 us on a v5e): about 2**18 cache elements a step, so 256
    tokens of 16 heads of 64 and 128 of 32 (measured, PERF.md 6, PR 28: at
    64 slots x 1024 a width of 2048 takes 297 us a layer with 128, 352 with
    256; a width of 1024 takes 176 with 256, 179 with 128, 222 with 512).
    Since PR 45 the dense sweep's last block streams no dead tail (its
    copy stops at the frontier), so that side of the trade is gone for it
    and a larger block may now win: not re-measured, the numbers stand as
    history; the grouped sweep, which shares this size, still streams
    whole blocks.  Elements, not bytes: int8 codes stream half the
    bytes and pay the same per element to become the compute dtype, and
    measure best at the same 256.

    Since PR 46 the block follows the row's width UP to those 2**18
    elements too, and no longer stops at 256 tokens: a 512-wide row takes
    512, a row of 256 or narrower 1,024 (measured, PERF.md 6, PR 46: the
    grouped sweep alone at 128 slots x 16,384 of two key-value heads of
    128, ~8.2k live tokens a row, takes 2,546 us a layer call with 256,
    0.61 us a step whatever it moves; 1,802 with 512; 1,516-1,604 with
    1,024, though it streams 1.06 x the live tokens where 256 streams
    1.015 x; 1,610 with 2,048, 1.125 x).  A 1,024-wide row keeps 256 and a
    2,048-wide one 128, as before."""
    return _block_of((1024, 512, 256, 128), Smax, HD)


#: score elements a step of the chunk kernel holds at most: float32 scores
#: of 1,024 rows x 1,024 keys are 4 MB of VMEM, their probabilities as many
_CHUNK_TILE = 1 << 20


def chunk_block_k(Smax: int) -> Optional[int]:
    """Keys in one block of the chunk kernel (admission, verify), or None
    where ``Smax`` does not tile.  A step takes ONE key-value head's block,
    a head wide whatever the row, against the ``G * chunk_block_q`` query
    rows of its group, and what a step costs beside its two products (the
    running max and sum of every row, the accumulator's rescale, a step's
    own fixed cost) does not grow with the keys: so the largest block that
    leaves a row two blocks (a prefix under half the row then skips half
    of it), 1,024 keys at most, ``_CHUNK_TILE`` scores a step.  Measured on
    a v5e at the serving cells' shapes (PERF.md 6, PR 49; ms a call over a
    prompt's chunks, rows x keys a step): 8 heads a group, ``D`` 128, rows
    of 8,192 and a ring's 2,048 (three calls in four): 2,048 x 256 0.493,
    2,048 x 512 0.324, 1,024 x 512 0.402, **1,024 x 1,024 0.269**, 512 x
    1,024 0.294, 512 x 2,048 0.313 (a head a step, float32 products, as
    before: 0.769); 16 heads a group, rows of 16,384: 0.673 / **0.470** /
    0.567 for 2,048 x 512 / 1,024 x 1,024 / 512 x 2,048 (before: 1.935); 4
    heads a group, rows of 5,120 in chunks of 512: 0.139 / **0.096**
    (before: 0.289); ungrouped heads of 64, rows of 1,024 in chunks of 128:
    0.0144 with 512 keys, 0.0157 with the whole row a block (before:
    0.0158).  At 1,024 x 1,024 the two products are 69% of a step."""
    return next((b for b in (1024, 512, 256, 128)
                 if Smax % b == 0 and (2 * b <= Smax or b == 128)), None)


def chunk_block_q(Sq: int, G: int, block_k: int) -> Optional[int]:
    """Positions in one query tile of the chunk kernel, or None where the
    chunk does not tile in the sublane dimension: the largest that keeps a
    step's ``G * block_q`` rows x ``block_k`` keys at or under
    ``_CHUNK_TILE`` scores: against 1,024 keys 256 positions up to 4 query
    heads a key-value head, 128 for 8, 64 for 16."""
    return next((b for b in (256, 128, 64, 32, 16, 8) if Sq % b == 0
                 and (G * b * block_k <= _CHUNK_TILE or b == 8)), None)


def sweep_block_k(sweep, B: int, Smax: int) -> int:
    """The block a work list was built for: ``decode_sweep`` lists ``B *
    Smax / block_k`` entries.  The kernel takes its block from the list it
    is handed, so the two cannot disagree."""
    return Smax * B // sweep[0].shape[0]


def decode_sweep(pos, B: int, Smax: int, block_k: Optional[int],
                 active=None, window=None):
    """The single-token sweep's work list: one entry for every live block
    of every live row, rows in order and each row's blocks rising from its
    band start to its causal frontier — ``(rows, blocks, n)``, int32
    ``[B * Smax/block_k]``, ``[B * Smax/block_k]`` and ``[1]``.  Entries
    from ``n`` on repeat the last live one, so a grid that runs past ``n``
    maps to blocks already in VMEM and moves nothing.

    ``pos`` (scalar or [B]) is each row's frontier, ``active`` ([B] bool,
    default all) its liveness: a dead row has no entry.  ``window``
    (scalar, may be traced) drops the blocks wholly below the band.  The
    list is a function of the tick's inputs alone: callers that run many
    layers build it once and hand it to every ``cached_attention`` call,
    which builds its kernel for the list's block.  ``block_k`` is
    ``decode_block_k``'s."""
    if block_k is None or not use_pallas():
        return None                  # the dense reference sweeps nothing
    nb = Smax // block_k
    p = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    hi = jnp.clip(p // block_k, 0, nb - 1)
    lo = jnp.zeros_like(hi) if window is None else jnp.clip(
        (p - jnp.asarray(window, jnp.int32) + 1) // block_k, 0, hi)
    count = hi - lo + 1
    if active is not None:
        count = jnp.where(active, count, 0)
    ends = jnp.cumsum(count)
    n = ends[-1]
    s = jnp.minimum(jnp.arange(B * nb, dtype=jnp.int32),
                    jnp.maximum(n - 1, 0))
    # entry s belongs to the row after the last that ends at or before s:
    # one [entries, B] comparison, then sums (no gather, no search loop)
    ended = (ends[None, :] <= s[:, None]).astype(jnp.int32)
    rows = jnp.minimum(jnp.sum(ended, axis=1), B - 1)
    # its block is lo[row] + s - start[row]; (lo - start)[row] summed up
    # from the row-to-row differences of the rows that have ended
    shift = lo - (ends - count)
    step = jnp.diff(shift, append=shift[-1:])
    blocks = s + shift[0] + jnp.sum(ended * step[None, :], axis=1)
    return (rows.astype(jnp.int32), blocks.astype(jnp.int32),
            n.reshape(1).astype(jnp.int32))


def _live_blocks(p: int, nb: int, block_k: int, window):
    """``(lo, hi)``: the first and the last block the sweep lists for a row
    at frontier ``p`` (``decode_sweep``'s rule, on host ints)."""
    hi = min(p // block_k, nb - 1)
    lo = 0 if window is None else \
        min(max((p - window + 1) // block_k, 0), hi)
    return lo, hi


def sweep_block_counts(positions, rows: int, Smax: int,
                       block_k: Optional[int], windows=((None, 1),)):
    """What ``decode_sweep`` lists, counted on the host from lengths the
    caller already holds (no device read): ``(live, grid)`` blocks over
    the kernel calls of one decode step.  ``positions`` are the live rows'
    frontiers, ``rows`` the batch's rows, live or not, and ``windows`` the
    step's calls as ``(window or None, how many layers)`` pairs.  ``grid``
    is what a sweep of every block of every row would step."""
    if block_k is None:
        return 0, 0
    nb = Smax // block_k
    live = 0
    for window, layers in windows:
        for p in positions:
            lo, hi = _live_blocks(p, nb, block_k, window)
            live += layers * (hi - lo + 1)
    return live, sum(n for _, n in windows) * rows * nb


def sweep_token_counts(positions, Smax: int, block_k: Optional[int],
                       windows=((None, 1),), copy_rows: Optional[int] = None):
    """``sweep_block_counts``'s sibling in tokens: ``(live, streamed)`` over
    the kernel calls of one decode step, from the same host lengths.
    ``live`` is what a row's query sees (``p + 1`` tokens a call, a banded
    layer's ``window`` of them), ``streamed`` what the sweep's copies move:
    whole blocks from the band's first to the one below the frontier's
    and, of the last, the live tokens rounded up to ``copy_rows``
    (``decode_copy_rows``: the dense kernel's copy ends on a tile);
    ``copy_rows`` None is a kernel that streams whole blocks (the grouped
    and the latent sweep)."""
    if block_k is None:
        return 0, 0
    nb = Smax // block_k
    tail = block_k if copy_rows is None else copy_rows
    live = streamed = 0
    for window, layers in windows:
        for p in positions:
            lo, hi = _live_blocks(p, nb, block_k, window)
            last = min(p + 1 - hi * block_k, block_k)
            live += layers * min(p + 1, Smax, window or Smax)
            streamed += layers * ((hi - lo) * block_k
                                  + -(-last // tail) * tail)
    return live, streamed


def decode_copy_rows(itemsize: int) -> int:
    """Rows the dense sweep's copy of a row's last block ends on: a tile of
    the bank, 16 rows of two bytes (or four), 32 of one."""
    return max(16, 32 // itemsize)


#: the pools a sweep plan counts by: whole rows, and rings (``SweepPlan``)
KINDS = ("full", "window")

#: the three single-token sweeps, by the name each kernel is launched under
DENSE_SWEEP = "decode_attention"
GROUPED_SWEEP = "gqa_decode_attention"
LATENT_SWEEP = "latent_decode_attention"
#: the chunk kernel over latent rows that up-projects its keys and values
LATENT_UP_CHUNK = "latent_chunk_attention_up"


class SweepPlan(NamedTuple):
    """The single-token sweep of one cached row (``sweep_plan``).

    ``kernel``: which of the three sweeps serves the row, or None where
    none does (``Smax`` does not tile, or grouped heads the grouped sweep
    does not take, ``grouped_sweep_serves``: the chunk kernel or the dense
    reference then serves the token).  ``block_k``: tokens a step, None
    only where ``Smax`` does not tile.  ``copy_rows``: where the copy of a
    row's last block ends, a tile for the dense sweep, None for the two
    that stream whole blocks.
    ``windows``: the sweep's calls in one decode step as ``(window or None,
    layers)`` pairs, the caller's.  ``ring``: the plan of the family's
    SECOND pool, where it has one (``gpt_inference.KVCache.ring``): the
    window layers' rings, ``ring.Smax`` rows a slot, a token at position
    ``p`` in cell ``p mod ring.Smax``.  A ring's sweep is a plain sweep of a
    short pool at frontier ``min(p, ring.Smax - 1)``, which is what the
    counting functions make of ``p`` for an ``Smax`` that short; the counts
    below are both pools', and by pool (``KINDS``) in ``by_kind``."""
    kernel: Optional[str]
    block_k: Optional[int]
    copy_rows: Optional[int]
    Smax: int
    windows: Tuple = ((None, 1),)
    ring: Optional["SweepPlan"] = None

    def block_counts(self, positions, rows: int):
        """``sweep_block_counts`` of this plan: ``(live, grid)`` blocks."""
        own = sweep_block_counts(positions, rows, self.Smax, self.block_k,
                                 self.windows)
        if self.ring is None:
            return own
        ring = self.ring.block_counts(positions, rows)
        return own[0] + ring[0], own[1] + ring[1]

    def token_counts(self, positions):
        """``sweep_token_counts`` of this plan: ``(live, streamed)``
        tokens."""
        by_kind = self.by_kind(positions)
        return (sum(v[0] for v in by_kind.values()),
                sum(v[1] for v in by_kind.values()))

    def by_kind(self, positions):
        """``pool -> (live, streamed, calls)`` of one decode step: the
        tokens as ``sweep_token_counts`` has them and the kernel's calls
        (one a layer), for the pool of whole rows (``"full"``) and, where
        there is one, the rings' (``"window"``)."""
        out = {KINDS[0]: sweep_token_counts(
            positions, self.Smax, self.block_k, self.windows,
            self.copy_rows) + (sum(n for _, n in self.windows),)}
        if self.ring is not None:
            out[KINDS[1]] = self.ring.by_kind(positions)[KINDS[0]]
        return out

    @property
    def share_of_one_geometry(self) -> float:
        """Cached rows a slot holds over what it would hold with every
        layer's row ``Smax`` long: 1 without a ring."""
        own = sum(n for _, n in self.windows)
        if self.ring is None:
            return 1.0
        rings = sum(n for _, n in self.ring.windows)
        return (own * self.Smax + rings * self.ring.Smax) \
            / ((own + rings) * self.Smax)


def grouped_sweep_serves(D: int, Hkv: int) -> bool:
    """Whether the grouped sweep takes ``Hkv`` key-value heads of ``D``
    elements: a lane-aligned head (``D`` a multiple of 128), or a head of
    half a lane row (64) where the ROW is whole lane rows (an even number of
    key-value heads): the kernel spreads a row's queries over the row's
    lanes under a mask, so what must tile is the row; its pieces are then
    64 lanes wide, which Mosaic concatenates and stores (compiled for a v5e
    and run on one at 32 heads on 8: PERF.md 6, PR 61).  Narrower heads are
    not served by a sweep (untested)."""
    return D % 128 == 0 or (D == 64 and (Hkv * D) % 128 == 0)


def sweep_plan(widths, Smax: int, heads: int, kv_heads: Optional[int] = None,
               itemsize: int = 2, windows=((None, 1),)) -> SweepPlan:
    """Which sweep serves a row, its block and where its last copy ends:
    the one place that says so.  ``cached_attention`` dispatches on it, a
    family's work list (``decode_sweep``) is built for its ``block_k`` and
    the host counts by its methods.

    ``widths``: the row's banks (one: a latent row shared by all heads;
    two: K and V of ``kv_heads`` key-value heads, default ``heads``);
    ``itemsize``: a bank element's bytes (the dense copy ends on a tile of
    them).  A latent row's rank plays no part: it says how much of the row
    the probabilities weigh, not how the row is streamed."""
    if len(widths) == 1:
        block_k = latent_block_k(Smax)
        kernel, copy_rows = LATENT_SWEEP, None
    else:
        Hkv = heads if kv_heads is None else int(kv_heads)
        block_k = decode_block_k(Smax, widths[0])
        if Hkv == heads:
            kernel, copy_rows = DENSE_SWEEP, decode_copy_rows(itemsize)
        else:
            kernel = GROUPED_SWEEP if grouped_sweep_serves(
                widths[0] // Hkv, Hkv) else None
            copy_rows = None
    return SweepPlan(kernel if block_k is not None else None, block_k,
                     copy_rows, Smax, tuple(windows))


# buffers a bank: the copies of the next ``_BUFFERS - 1`` steps are in
# flight while a step computes
_BUFFERS = 3


def _decode(q, k, v, layer, pos, sweep, sm_scale, block_k, H, copy_rows,
            ks=None, vs=None, window=None, slopes=None):
    """Single scalar-prefetch build for every decode variant, reading the
    stacked pool ``k``/``v`` [L, B, Smax, H*D] where it lies.  The sweep
    (``decode_sweep``), ``pos``, ``layer`` (and window, when banded) are
    available BEFORE the body, so the index maps pick each step's row from
    them and the kernel's own copies each step's block and layer, the next
    step's behind the current one and no further than the row is live
    (``_decode_kernel``): the pool's banks are handed over whole, in HBM,
    and ``_BUFFERS`` buffers of one block a bank are the kernel's scoped
    VMEM.  The
    grid is flat, one step per entry of the sweep: on the chip its bound is
    the live-block count itself (a dynamic grid bound), so a dead block
    costs no step and a dead row neither a step nor a byte; under the
    interpreter, which refuses dynamic bounds and copies of traced size,
    the same body runs the static ``B * Smax/block_k`` steps, skips the
    tail and copies whole blocks.  ``q`` and the result are ``[B, 1, H*D]``
    (a dead row's result is never written); ``ks``/``vs`` [L, B, Smax,
    H]; ``copy_rows``: the plan's (``sweep_plan``)."""
    B, _, HD = q.shape
    quantized = ks is not None
    windowed = window is not None
    interpret = interpret_mode()
    if interpret:
        copy_rows = None
    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               block_k=block_k, H=H, D=HD // H,
                               quantized=quantized, windowed=windowed,
                               alibi=slopes is not None, copy_rows=copy_rows)
    rows, blocks, n = sweep

    def row_idx(s, rows_ref, *_):
        return (rows_ref[s], 0, 0)

    def kv_idx(s, rows_ref, blocks_ref, n_ref, pos_ref, layer_ref, *_):
        return (layer_ref[0], rows_ref[s], blocks_ref[s], 0)

    row_spec = pl.BlockSpec((None, 1, HD), row_idx)
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    scale_spec = pl.BlockSpec((None, None, block_k, H), kv_idx)
    slope_specs = [pl.BlockSpec((H, 1), lambda s, *_: (0, 0))] \
        if slopes is not None else []
    slope_args = (jnp.asarray(slopes, jnp.float32).reshape(H, 1),) \
        if slopes is not None else ()
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    prefetch = (rows, blocks, n, pos_arr,
                jnp.asarray(layer, jnp.int32).reshape(1)) + \
        ((jnp.asarray(window, jnp.int32).reshape(1),) if windowed else ())
    grid_spec = pltpu.PrefetchScalarGridSpec(
        # rows, blocks, n, pos, layer [, window]
        num_scalar_prefetch=len(prefetch),
        grid=(rows.shape[0] if interpret else jnp.maximum(n[0], 1),),
        in_specs=slope_specs + [row_spec, pool_spec, pool_spec]
        + ([scale_spec, scale_spec] if quantized else []),
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((H, HD), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((_BUFFERS, block_k, HD), k.dtype),
            pltpu.VMEM((_BUFFERS, block_k, HD), v.dtype),
            pltpu.SemaphoreType.DMA((2, _BUFFERS)),
        ],
    )
    # prefetch refs arrive in arg order — [rows, blocks, n, pos, layer,
    # window?] then slopes? — matching _unpack_rest's ordering contract;
    # the buffers and their semaphores follow its scratch
    args = prefetch + slope_args + (q, k, v) + \
        ((ks, vs) if quantized else ())
    return pl.pallas_call(kernel, grid_spec=grid_spec,
                          out_shape=jax.ShapeDtypeStruct((B, 1, HD), q.dtype),
                          interpret=interpret,
                          name="decode_attention")(*args)


def _chunk_live_range(q_lo, block_q: int, block_k: int, window, first):
    """``(lo, hi)``: the first and the last key block a pair of the query
    tile at ``q_lo`` can be visible in (band start or first real key, causal
    frontier): the range the chunk kernel's index map clamps a step's block
    to, so that a dead step maps to a block already in VMEM."""
    lo = 0
    if window is not None:
        lo = jnp.maximum((q_lo - window + 1) // block_k, 0)
    if first is not None:
        lo = jnp.maximum(lo, first // block_k)
    return lo, (q_lo + block_q - 1) // block_k


def _chunk_kernel(pos_ref, *rest, sm_scale, block_q, block_k, Hkv, G,
                  quantized, windowed, alibi, bounded=False):
    """Chunked-prefill attention over the padded cache: queries are a
    whole chunk at absolute positions ``pos .. pos+Sq-1`` (online softmax
    per row, cache blocks streamed through VMEM, blocks beyond the
    chunk's causal frontier — and, when windowed, wholly below every
    row's band — skipped).  Memory-linear counterpart of the dense
    fallback ``extend`` would otherwise take — O(block) VMEM instead of
    an [Sq, Smax] score tensor.

    A grid step is a key-value head's GROUP against a key block: ``q_ref``
    is ``(1, G, block_q, D)``, ``block_q`` positions of the ``G`` query
    heads that share key-value head ``program_id(0)``, taken as ``G *
    block_q`` rows under one another (row ``r`` sits at position ``pos + qi
    * block_q + r mod block_q``), so a key block is fetched once a group
    and two products serve all its heads.  ``G == 1`` is the same body on a
    group of one.  The products are the single-token sweep's: the blocks in
    the query's dtype (``_to_compute``), float32 scores scaled after the
    product, the probabilities rounded to the values' dtype
    (``_online_softmax_step``).

    Every live block takes the mask, and the running max is floored at
    ``M_FLOOR`` (not -inf): a block on a band's edge can be fully masked
    for SOME of its q rows, and those rows' recurrences must stay
    nan-free.  (A block whose every pair is visible could go without its
    mask; measured, that is worth nothing at this tile: PERF.md 6, PR 49.)

    ``bounded``: a second scalar-prefetch vector leads ``rest``, each row's
    first real key (``valid_from`` of ``cached_attention``); the keys
    before it are masked and the blocks wholly before it skipped."""
    first_ref = None
    if bounded:
        first_ref, rest = rest[0], rest[1:]
    (window_ref, slopes_ref, q_ref, k_ref, v_ref, kscale_ref, vscale_ref,
     o_ref, acc_ref, m_ref, l_ref) = _unpack_rest(rest, quantized,
                                                  windowed, alibi)
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    pos = pos_ref[bh // Hkv]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_lo = pos + qi * block_q               # the tile's first position
    window = window_ref[0] if windowed else None
    first = first_ref[bh // Hkv] if bounded else None
    lo, hi = _chunk_live_range(q_lo, block_q, block_k, window, first)

    @pl.when(jnp.logical_and(lo <= ki, ki <= hi))
    def _update():
        q = q_ref[0].reshape(G * block_q, q_ref.shape[-1])  # (G*BQ, D)
        ks = _to_compute(k_ref[0], q.dtype)                 # (BK, D)
        vs = _to_compute(v_ref[0], q.dtype)
        s = jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                    # (G*BQ, BK)
        if quantized:   # the codes' scales, as the single-token sweep's
            s = s * kscale_ref[0].T
        r = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        if G > 1:
            r = jax.lax.rem(r, block_q)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        dist = q_lo + r - k_pos
        if alibi:       # G == 1 (``_chunk``): the step's one head's slope
            s = s - slopes_ref[bh % Hkv] * dist.astype(jnp.float32)
        visible = dist >= 0
        if windowed:
            visible = jnp.logical_and(visible, dist < window)
        if bounded:
            visible = jnp.logical_and(visible, k_pos >= first)
        _online_softmax_step(jnp.where(visible, s, NEG_INF), vs, acc_ref,
                             m_ref, l_ref,
                             vscale_ref[0].T if quantized else None)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...]).reshape(
            o_ref.shape[1:]).astype(o_ref.dtype)


def _chunk(q4, k3, v3, pos, sm_scale, block_q, block_k, Hkv, ks3=None,
           vs3=None, window=None, slopes=None, valid_from=None):
    """``q4`` is ``[B*Hkv, G, Sq, D]``, the ``G`` query heads of each
    key-value head beside one another (``G`` 1: every head its own), and
    ``k3``/``v3`` ``[B*Hkv, Smax, D]`` (scales ``[B*Hkv, Smax, 1]``): grid
    step ``(bh, qi, ki)`` takes key-value head ``bh``'s whole group, ``G *
    block_q`` query rows, against key block ``ki``, so a block is fetched
    once a group.  The result has ``q4``'s shape.  ``valid_from`` (scalar
    or [B]): each row's first real key."""
    BH, G, Sq, D = q4.shape
    Smax = k3.shape[1]
    B = BH // Hkv
    quantized = ks3 is not None
    windowed = window is not None
    bounded = valid_from is not None
    assert slopes is None or G == 1, "a slope a step: ungrouped heads"
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    kernel = functools.partial(_chunk_kernel, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k, Hkv=Hkv,
                               G=G, quantized=quantized, windowed=windowed,
                               alibi=slopes is not None, bounded=bounded)
    # single scalar-prefetch build (see _decode): dead k-block indices
    # clamp into this q block's live range [band start or first real key,
    # causal frontier], so chunked prefill/extend streams only the blocks
    # its rows can see
    def kv_idx(bh, qi, ki, pos_ref, *more):
        lo, hi = _chunk_live_range(
            pos_ref[bh // Hkv] + qi * block_q, block_q, block_k,
            more[-1][0] if windowed else None,
            more[0][bh // Hkv] if bounded else None)
        return (bh, jnp.clip(ki, lo, hi), 0)

    def q_idx(bh, qi, ki, *_):
        return (bh, 0, qi, 0)

    q_spec = pl.BlockSpec((1, G, block_q, D), q_idx)
    kv_spec = pl.BlockSpec((1, block_k, D), kv_idx)
    scale_spec = pl.BlockSpec((1, block_k, 1), kv_idx)
    slope_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] \
        if slopes is not None else []
    slope_args = (jnp.asarray(slopes, jnp.float32),) \
        if slopes is not None else ()
    win_args = (jnp.asarray(window, jnp.int32).reshape(1),) \
        if windowed else ()
    if bounded:     # the kernel's ``first_ref`` leads the window
        win_args = (jnp.broadcast_to(jnp.asarray(
            valid_from, jnp.int32).reshape(-1), (B,)),) + win_args
    rows = G * block_q
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 + len(win_args),
        grid=(BH, Sq // block_q, Smax // block_k),
        in_specs=slope_specs + [q_spec, kv_spec, kv_spec]
        + ([scale_spec, scale_spec] if quantized else []),
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((rows, D), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
    )
    args = (pos_arr,) + win_args + slope_args + (q4, k3, v3) + \
        ((ks3, vs3) if quantized else ())
    return pl.pallas_call(kernel, grid_spec=grid_spec,
                          out_shape=jax.ShapeDtypeStruct(q4.shape, q4.dtype),
                          interpret=interpret_mode(),
                          name="chunk_attention")(*args)


# ------------------------------------------- a chunk over a row of the pool

def row_chunk_lanes(Hkv: int, G: int, D: int) -> Optional[Tuple[int, int]]:
    """``(kw, qw)``: the lanes of the folded bank ``[.., Hkv * D]`` and of the
    folded queries ``[.., H * D]`` that one LANE BLOCK of the chunk kernel
    over a row of the pool (``_row_chunk``) takes, or None where that kernel
    does not serve the heads.  A key-value head of whole lane rows (``D`` a
    multiple of 128) is a lane block of its own, beside the ``G * D`` lanes
    of its group's queries; ungrouped heads narrower than a lane row (``G``
    1, ``D`` 64: two heads in 128 lanes) share a block of 128 lanes of both,
    and a product contracts all 128 with the other heads' query lanes zeroed
    (half of the matrix unit's depth stands idle under a head of 64 either
    way: ``ROADMAP.md``'s two questions for the compiler, PR 43).  Grouped
    heads narrower than a lane row would want their queries shifted across
    lanes: they keep the heads-major view (``_chunk``)."""
    if D % 128 == 0:
        return D, G * D
    if G == 1 and 128 % D == 0 and (Hkv * D) % 128 == 0:
        return 128, 128
    return None


#: bytes of VMEM a step of ``_row_chunk`` is sized to, of the 16 MiB a v5e's
#: kernel may use (``_UP_VMEM``'s count, for the same reason)
_ROW_CHUNK_VMEM = 12 << 20


def row_chunk_blocks(lanes: int, kw: int, qw: int, G: int, block_q: int,
                     block_k: int, itemsize: int) -> int:
    """Lane blocks a step of ``_row_chunk`` takes of a ``lanes``-wide row:
    the largest divisor of their number whose step stays within
    ``_ROW_CHUNK_VMEM``, counting a lane block's two buffers a bank, its
    queries' and its result's two each and its float32 accumulator, beside
    the float32 scores and probabilities of the one lane block in hand.  A
    step costs ~0.35 us beside its products, so as many as fit run in one:
    ``gpt2-medium``'s 16 heads of 64 over 512 keys are all eight lane
    blocks, one step a key block (measured, PERF.md 6, PR 63: 16.6 / 13.5 /
    12.2 / 11.6 us a call of 128 rows at 1 / 2 / 4 / 8); a group of 8 heads
    of 128 over 1,024 keys fills the step with its scores and takes one."""
    rows = G * block_q
    scores = 3 * rows * block_k * 4
    block = 2 * 2 * block_k * kw * itemsize + 2 * 2 * block_q * qw * itemsize \
        + rows * kw * 4
    n = lanes // kw
    return max(p for p in range(1, n + 1) if n % p == 0 and (
        p == 1 or scores + p * block <= _ROW_CHUNK_VMEM))


def _lane_block(q, ks, vs, visible, m, l, acc, *, sm_scale, G, D):
    """One lane block of one key block, in values: ``q`` ``(block_q, qw)``,
    ``ks`` / ``vs`` ``(block_k, kw)``, ``visible`` the ``(G * block_q,
    block_k)`` mask, and the block's running max and sum ``m`` / ``l``
    (``(rows, 1)`` a head of the block) and accumulator ``acc`` ``(rows,
    kw)``: ``(m, l, acc)`` after the step.

    A lane block that IS a key-value head (``D == kw``) takes its group's
    ``G`` query heads as ``G * block_q`` rows under one another, as
    ``_chunk_kernel`` does.  One that holds several ungrouped heads scores
    each head with the others' query lanes zeroed and keeps, of its
    probabilities' product with all ``kw`` value lanes, that head's own."""
    kw = ks.shape[1]
    heads = kw // D

    def step(q, m_prev, l_prev):
        s = jax.lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(visible, s * sm_scale, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        return (m_new, l_prev * alpha + jnp.sum(p, axis=1, keepdims=True),
                alpha, jnp.dot(p.astype(vs.dtype), vs,
                               preferred_element_type=jnp.float32))

    if heads == 1:
        rows = q if G == 1 else jnp.concatenate(
            [q[:, g * D:(g + 1) * D] for g in range(G)], axis=0)
        m_new, l_new, alpha, pv = step(rows, m[0], l[0])
        return [m_new], [l_new], acc * alpha + pv
    own = jax.lax.broadcasted_iota(jnp.int32, q.shape, 1) // D
    ms, ls, alpha, pv = [], [], None, None
    for j in range(heads):
        m_new, l_new, a, w = step(jnp.where(own == j, q, jnp.zeros_like(q)),
                                  m[j], l[j])
        ms.append(m_new)
        ls.append(l_new)
        alpha = a if j == 0 else jnp.where(own == j, a, alpha)
        pv = w if j == 0 else jnp.where(own == j, w, pv)
    return ms, ls, acc * alpha + pv


def _row_chunk_kernel(pos_ref, rows_ref, layer_ref, *rest, sm_scale, block_q,
                      block_k, G, D, kw, P, windowed):
    """``_chunk_kernel`` over the folded row where it lies: ``k_ref`` /
    ``v_ref`` are ``(block_k, P * kw)``, ``P`` lane blocks of one key block of
    row ``rows_ref[b]`` of layer ``layer_ref[0]`` of the pool, and ``q_ref`` /
    ``o_ref`` ``(block_q, P * qw)``, the same heads' lanes of the folded
    queries (``row_chunk_lanes``).  The lane blocks are walked inside the
    step (``_lane_block``), each with its own running max, sum and
    accumulator, by a ROLLED loop over lane-aligned slices: unrolled in
    Python the 16 heads of a ``gpt2-medium`` step ran 12% faster (11.6 us a
    call of 128 rows for 13.3) and cost a warm server's start 3.8 s of 10.5
    in tracing and lowering its three instances (PERF.md 6, PR 63)."""
    window_ref = None
    if windowed:
        window_ref, rest = rest[0], rest[1:]
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref = rest
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    heads = kw // D                 # heads a lane block (1: a group's)
    qw = G * D if heads == 1 else kw

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_lo = pos_ref[b] + qi * block_q        # the tile's first position
    window = window_ref[0] if windowed else None
    lo, hi = _chunk_live_range(q_lo, block_q, block_k, window, None)

    def lanes(i, w):
        return pl.ds(pl.multiple_of(i * w, w), w)

    @pl.when(jnp.logical_and(lo <= ki, ki <= hi))
    def _update():
        shape = (G * block_q, block_k)
        r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        if G > 1:
            r = jax.lax.rem(r, block_q)
        dist = q_lo + r - ki * block_k \
            - jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        visible = dist >= 0
        if windowed:
            visible = jnp.logical_and(visible, dist < window)

        def block(p, _):
            cs = lanes(p, kw)
            m, l, acc_ref[p] = _lane_block(
                q_ref[:, lanes(p, qw)], k_ref[:, cs], v_ref[:, cs], visible,
                [m_ref[p * heads + j] for j in range(heads)],
                [l_ref[p * heads + j] for j in range(heads)],
                acc_ref[p], sm_scale=sm_scale, G=G, D=D)
            for j in range(heads):
                m_ref[p * heads + j] = m[j]
                l_ref[p * heads + j] = l[j]

        jax.lax.fori_loop(0, P, block, None)

    @pl.when(ki == nk - 1)
    def _finalize():
        own = jax.lax.broadcasted_iota(jnp.int32, (block_q, kw), 1) // D

        def block(p, _):
            total = l_ref[p * heads]
            for j in range(1, heads):   # each lane under its own head's sum
                total = jnp.where(own == j, l_ref[p * heads + j], total)
            o = (acc_ref[p] / total).astype(o_ref.dtype)
            if G == 1:
                o_ref[:, lanes(p, kw)] = o
            else:       # (G * block_q, D): the group's heads under one another
                for g in range(G):
                    o_ref[:, lanes(p * G + g, D)] = \
                        o[g * block_q:(g + 1) * block_q]

        jax.lax.fori_loop(0, P, block, None)


def _row_chunk(q, k, v, layer, rows, pos, sm_scale, block_q, block_k, Hkv,
               window=None):
    """A chunk's queries over rows of the pool WHERE THEY LIE: ``q`` [B, Sq,
    H * D], folded as the projection writes them, against cells of row
    ``rows[b]`` of layer ``layer`` of the banks ``k`` / ``v`` [L, slots,
    Smax, Hkv * D]; [B, Sq, H * D].  The layer and the rows ride the scalar
    prefetch beside ``pos`` and the index maps address the folded bank by
    them, as the single-token sweeps' do: no layer is sliced out (a layer
    of a pool is all its slots) and no head-major copy is made of either
    side.  Grid step ``(b, lanes, qi, ki)`` takes ``row_chunk_blocks`` lane
    blocks of key block ``ki`` (clamped into the tile's live range, so a
    dead step fetches nothing); the result is rank 3 with a chunk's rows,
    never one (a single-token sweep's is told by that)."""
    B, Sq, HD = q.shape
    Smax, W = k.shape[2], k.shape[3]
    D = W // Hkv
    G = HD // W
    kw, qw = row_chunk_lanes(Hkv, G, D)
    P = row_chunk_blocks(W, kw, qw, G, block_q, block_k, k.dtype.itemsize)
    heads = kw // D
    windowed = window is not None
    kernel = functools.partial(
        _row_chunk_kernel, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, G=G, D=D, kw=kw, P=P, windowed=windowed)

    def kv_idx(b, lp, qi, ki, pos_ref, rows_ref, layer_ref, *more):
        lo, hi = _chunk_live_range(
            pos_ref[b] + qi * block_q, block_q, block_k,
            more[0][0] if windowed else None, None)
        return (layer_ref[0], rows_ref[b], jnp.clip(ki, lo, hi), lp)

    q_spec = pl.BlockSpec((None, block_q, P * qw),
                          lambda b, lp, qi, ki, *_: (b, qi, lp))
    kv_spec = pl.BlockSpec((None, None, block_k, P * kw), kv_idx)
    rows_n = G * block_q

    def as_b(x):
        return jnp.broadcast_to(jnp.asarray(x, jnp.int32).reshape(-1), (B,))

    prefetch = (as_b(pos), as_b(rows),
                jnp.asarray(layer, jnp.int32).reshape(1)) + (
        (jnp.asarray(window, jnp.int32).reshape(1),) if windowed else ())
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, W // (P * kw), Sq // block_q, Smax // block_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((P, rows_n, kw), jnp.float32),
            pltpu.VMEM((P * heads, rows_n, 1), jnp.float32),
            pltpu.VMEM((P * heads, rows_n, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(kernel, grid_spec=grid_spec,
                          out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
                          interpret=interpret_mode(),
                          name="chunk_attention")(*prefetch, q, k, v)


# ----------------------------------------------------------- grouped heads

def _gqa_decode_kernel(rows_ref, blocks_ref, n_ref, pos_ref, layer_ref,
                       q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                       sm_scale, block_k, H, G, D):
    """``_decode_kernel`` for grouped heads: ``H`` query heads on ``H // G``
    key-value heads, the row the pool keeps still ``(H // G) * D`` wide.  A
    grid step is one live block of one live row with ALL its key-value
    heads; ``q_ref`` is the row's ``(H, D)`` queries, spread to ``(H, H/G *
    D)`` with head ``h``'s lanes under key-value head ``h // G``, so the
    ``G`` query heads of a key-value head share the block in VMEM and two
    plain matmuls score and weigh every head at once.  Head ``h``'s result
    is row ``h``'s lanes under its key-value head."""
    step, row, live, first, last = _sweep_position(rows_ref, n_ref)
    ki = blocks_ref[step]
    pos = pos_ref[row]
    W = (H // G) * D

    @pl.when(jnp.logical_and(live, first))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live)
    def _update():
        q = q_ref[...]                                     # (H, D)
        own = jax.lax.broadcasted_iota(jnp.int32, (H, W), 1) // D == \
            jax.lax.broadcasted_iota(jnp.int32, (H, W), 0) // G
        qx = jnp.where(own, jnp.concatenate([q] * (H // G), axis=1)
                       .astype(jnp.float32), 0.0).astype(q.dtype)
        s = jax.lax.dot_general(qx, k_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                   # (H, BK)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _online_softmax_step(jnp.where(k_pos <= pos, s, NEG_INF), v_ref[...],
                             acc_ref, m_ref, l_ref)

    @pl.when(jnp.logical_and(live, last))
    def _finalize():
        o = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)    # (H, W)
        for h in range(H):
            kv = h // G
            o_ref[:, h * D:(h + 1) * D] = o[h:h + 1, kv * D:(kv + 1) * D]


def _gqa_decode(q, k, v, layer, pos, sweep, sm_scale, block_k, G):
    """The grouped-head decode sweep: ``q`` [B, H, D] against layer
    ``layer`` of the pool ``k``/``v`` [L, B, Smax, H/G * D] where it lies;
    [B, 1, H*D].  The work list, the dynamic grid and the index maps are
    ``_decode``'s."""
    B, H, D = q.shape
    W = k.shape[-1]
    rows, blocks, n = sweep
    kernel = functools.partial(_gqa_decode_kernel, sm_scale=sm_scale,
                               block_k=block_k, H=H, G=G, D=D)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    prefetch = (rows, blocks, n, pos_arr,
                jnp.asarray(layer, jnp.int32).reshape(1))
    interpret = interpret_mode()

    def kv_idx(s, rows_ref, blocks_ref, n_ref, pos_ref, layer_ref):
        return (layer_ref[0], rows_ref[s], blocks_ref[s], 0)

    kv_spec = pl.BlockSpec((None, None, block_k, W), kv_idx)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(rows.shape[0] if interpret else jnp.maximum(n[0], 1),),
        in_specs=[
            pl.BlockSpec((None, H, D),
                         lambda s, rows_ref, *_: (rows_ref[s], 0, 0)),
            kv_spec, kv_spec],
        out_specs=pl.BlockSpec((None, 1, H * D),
                               lambda s, rows_ref, *_: (rows_ref[s], 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, W), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H * D), q.dtype),
        interpret=interpret, name="gqa_decode_attention")(
            *prefetch, q, k, v)


# ------------------------------------------------------------ latent rows

def latent_attention_reference(q, bank, pos, sm_scale: float, rank: int,
                               bias=None):
    """Ground truth of the absorbed form: ``q`` [B, Sq, H, W] over latent
    rows ``bank`` [B, Smax, W]; query i (at ``pos + i``) sees rows at or
    before it and weighs their first ``rank`` elements: [B, Sq, H, rank].
    ``bias`` [B, Sq, Smax] float32 (0 or -inf) is added to every head's
    scores: a selection, or a ring's band."""
    B, Sq = q.shape[:2]
    Smax = bank.shape[1]
    s = jnp.einsum("bqhw,bkw->bhqk", q, bank,
                   preferred_element_type=jnp.float32) * sm_scale
    pos = jnp.asarray(pos)
    q_abs = (pos.reshape(-1, 1) if pos.ndim else pos) + jnp.arange(Sq)
    mask = jnp.atleast_2d(q_abs)[:, :, None] >= jnp.arange(Smax)[None, None]
    if bias is not None:
        s = s + bias[:, None]
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkr->bqhr", p.astype(q.dtype), bank[..., :rank])


def latent_block_k(Smax: int) -> Optional[int]:
    """Tokens in one streamed block of the latent sweep, or None where
    ``Smax`` does not tile.  A block is read once for all heads, so a step
    carries far more work than a dense one and a larger block pays
    (measured at 128 slots x 8,192, PERF.md 6, PR 33: 5,981 tokens/s with
    256, 6,390 with 512; at 1,024 the chunk pass overruns its VMEM)."""
    return next((b for b in (512, 256, 128) if Smax % b == 0), None)


def _latent_decode_kernel(rows_ref, blocks_ref, n_ref, pos_ref, layer_ref,
                          q_ref, k_ref, *rest, sm_scale, block_k, H, R,
                          biased=False):
    """``_decode_kernel`` for a latent pool: a grid step is one live block
    of one live row, ``k_ref`` its ``(block_k, W)`` latent rows, ``q_ref``
    the row's ``(H, W)`` absorbed queries.  Two plain matmuls, no spreading:
    every head scores the same rows and weighs the same ``R`` columns.  The
    result leaves as one ``(1, H*R)`` row, head after head.  ``biased``: a
    ``(1, block_k)`` float32 block of the row's bias (0 or -inf a token: a
    selection, a ring's band) leads ``rest`` and is added to every head's
    scores; a block it masks whole leaves the recurrence as it was."""
    bias_ref = None
    if biased:
        bias_ref, rest = rest[0], rest[1:]
    o_ref, acc_ref, m_ref, l_ref = rest
    step, row, live, first, last = _sweep_position(rows_ref, n_ref)
    ki = blocks_ref[step]
    pos = pos_ref[row]

    @pl.when(jnp.logical_and(live, first))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(live)
    def _update():
        q = q_ref[...]                                     # (H, W)
        kb = k_ref[...]                                    # (BK, W)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                   # (H, BK)
        if biased:
            s = s + bias_ref[...]
        k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _online_softmax_step(jnp.where(k_pos <= pos, s, NEG_INF), kb[:, :R],
                             acc_ref, m_ref, l_ref)

    @pl.when(jnp.logical_and(live, last))
    def _finalize():
        o = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)    # (H, R)
        for h in range(H):
            o_ref[:, h * R:(h + 1) * R] = o[h:h + 1]


def _latent_decode(q, bank, layer, pos, sweep, sm_scale, block_k, R,
                   bias=None):
    """The latent decode sweep: ``q`` [B, H, W] against layer ``layer`` of
    the pool ``bank`` [L, B, Smax, W] where it lies; [B, 1, H*R].  ``bias``
    [B, 1, Smax] float32: see the kernel."""
    B, H, W = q.shape
    rows, blocks, n = sweep
    biased = bias is not None
    kernel = functools.partial(_latent_decode_kernel, sm_scale=sm_scale,
                               block_k=block_k, H=H, R=R, biased=biased)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    prefetch = (rows, blocks, n, pos_arr,
                jnp.asarray(layer, jnp.int32).reshape(1))
    interpret = interpret_mode()
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(rows.shape[0] if interpret else jnp.maximum(n[0], 1),),
        in_specs=[
            pl.BlockSpec((None, H, W),
                         lambda s, rows_ref, *_: (rows_ref[s], 0, 0)),
            pl.BlockSpec((None, None, block_k, W),
                         lambda s, rows_ref, blocks_ref, n_ref, pos_ref,
                         layer_ref: (layer_ref[0], rows_ref[s],
                                     blocks_ref[s], 0)),
        ] + ([pl.BlockSpec((None, 1, block_k),
                           lambda s, rows_ref, blocks_ref, *_:
                           (rows_ref[s], 0, blocks_ref[s]))]
             if biased else []),
        out_specs=pl.BlockSpec((None, 1, H * R),
                               lambda s, rows_ref, *_: (rows_ref[s], 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, R), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H * R), q.dtype),
        interpret=interpret, name="latent_decode_attention")(
            *prefetch, q, bank, *((bias,) if biased else ()))


def _latent_chunk_kernel(pos_ref, layer_ref, q_ref, k_ref, *rest, sm_scale,
                         block_q, block_k, H, R, biased=False):
    """A chunk of queries against latent rows: ``q_ref`` is ``(block_q * H,
    W)``, the heads of ``block_q`` consecutive positions row after row, so
    one matmul scores them all against the block's rows; row ``i`` sits at
    position ``pos + qi * block_q + i // H``.  ``biased``: a ``(block_q,
    block_k)`` float32 block of the positions' bias (0 or -inf a key) leads
    ``rest``; each position's row is added to all its heads' scores."""
    bias_ref = None
    if biased:
        bias_ref, rest = rest[0], rest[1:]
    o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    pos = pos_ref[b]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(ki * block_k <= pos + (qi + 1) * block_q - 1)
    def _update():
        q = q_ref[...]                                     # (BQ*H, W)
        kb = k_ref[...]                                    # (BK, W)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        if biased:
            s = s + jnp.broadcast_to(
                bias_ref[...][:, None, :], (block_q, H, block_k)
            ).reshape(block_q * H, block_k)
        q_pos = pos + qi * block_q + \
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // H
        k_pos = ki * block_k + \
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        _online_softmax_step(jnp.where(k_pos <= q_pos, s, NEG_INF),
                             kb[:, :R], acc_ref, m_ref, l_ref)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _latent_chunk(q, bank, layer, pos, sm_scale, block_q, block_k, R,
                  bias=None):
    """``q`` [B, Sq, H, W] against layer ``layer`` of ``bank`` [L, B, Smax,
    W]: [B, Sq, H, R].  Block indices past a query block's causal frontier
    clamp onto it, so they move nothing.  ``bias`` [B, Sq, Smax] float32:
    see the kernel."""
    B, Sq, H, W = q.shape
    Smax = bank.shape[2]
    biased = bias is not None
    kernel = functools.partial(_latent_chunk_kernel, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k, H=H, R=R,
                               biased=biased)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))

    def kv_idx(b, qi, ki, pos_ref, layer_ref):
        hi = (pos_ref[b] + (qi + 1) * block_q - 1) // block_k
        return (layer_ref[0], b, jnp.minimum(ki, hi), 0)

    rows = block_q * H
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Sq // block_q, Smax // block_k),
        in_specs=[
            pl.BlockSpec((None, rows, W), lambda b, qi, ki, *_: (b, qi, 0)),
            pl.BlockSpec((None, None, block_k, W), kv_idx),
        ] + ([pl.BlockSpec((None, block_q, block_k),
                           lambda b, qi, ki, pos_ref, layer_ref:
                           (b, qi, kv_idx(b, qi, ki, pos_ref, layer_ref)[2]))]
             if biased else []),
        out_specs=pl.BlockSpec((None, rows, R),
                               lambda b, qi, ki, *_: (b, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, R), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
            pltpu.VMEM((rows, 1), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq * H, R), q.dtype),
        interpret=interpret_mode(), name="latent_chunk_attention")(
            pos_arr, jnp.asarray(layer, jnp.int32).reshape(1),
            q.reshape(B, Sq * H, W), bank, *((bias,) if biased else ()))
    return o.reshape(B, Sq, H, R)


class LatentUp(NamedTuple):
    """A layer's key-value up-projection, for a call that hands
    ``latent_cached_attention`` its queries un-absorbed.  ``w``: a stack
    ``[layers, H, rank, d_nope + d_v]`` in the compute dtype, HEAD-major
    (``[k | v]`` of a head side by side), made once outside the layer scan:
    the kernel reads layer ``layer`` of it where it lies (a layer sliced
    out first is a copy of 16.8 MB at the published widths).  ``d_nope``:
    where a head's key part ends (whole lane rows: a narrower key part is
    padded with zero columns where the copy is made, and the queries with
    zero lanes)."""
    w: jax.Array
    layer: jax.Array
    d_nope: int


def latent_up_projects(Sq: int, H: int, W: int, rank: int, d_nope: int,
                       d_rope: int, d_v: int) -> bool:
    """Whether a call of ``Sq`` query positions over latent rows is cheaper
    UP-PROJECTED (every key and value made from its row once a head a call,
    ``2 rank H (d_nope + d_v)`` operations a key, then ``2 H (d_nope +
    d_rope + d_v)`` a (query, key) pair) than ABSORBED (``2 H (W + rank)`` a
    pair against the row as stored and nothing a key): one algorithm, two
    costs, decided by the call's static widths.  At 64 heads of 128 + 64 |
    128 over a rank of 512 stored 640 wide: from 158 positions on, so a
    prompt's chunk up-projects and a tick or a verify's few tokens do
    not."""
    return Sq * (2 * H * (W + rank) - 2 * H * (d_nope + d_rope + d_v)) \
        > 2 * rank * H * (d_nope + d_v)


#: bytes of VMEM the up-projected chunk kernel sizes its step to (what it
#: counts of its buffers and temporaries; the compiler's own scratch comes
#: on top, and a v5e kernel may use 16 MiB)
_UP_VMEM = 12 << 20


def latent_up_tiles(Sq: int, H: int, W: int, rank: int, d_nope: int,
                    d_v: int, block_k: int, itemsize: int = 2,
                    biased: bool = False):
    """``(heads, block_q)`` of the up-projected chunk kernel's step, or None
    where its tiles do not fit: every width in whole lane rows and the chunk
    in whole sublane tiles (of 32 rows under a bias: its mask is int8).  A
    step holds the chunk's queries of ``heads`` heads (the largest group of
    4, 2, 1 whose buffers stay under ``_UP_VMEM``) and scores them
    ``block_q`` positions at a time (512 at most: the float32 scores and
    probabilities of 512 x 512 are 1 MB each; half as many, and half
    again, where not even one head fits beside them).  ``biased``: the step
    also holds, twice, the chunk's ``(Sq, block_k)`` mask of the key
    block."""
    tile = 32 if biased else 16
    if any(n % 128 for n in (W, rank, d_nope, d_v)) or Sq % tile:
        return None
    block_q = Sq if Sq <= 512 else next(
        b for b in (512, 256, 128, 64, 32, 16) if Sq % b == 0)
    E, Eq = d_nope + d_v, d_nope + W - rank

    def step(block_q):
        return 2 * block_k * W * itemsize \
            + block_q * block_k * (8 + itemsize) \
            + block_k * (E * (4 + itemsize) + Eq * itemsize) \
            + (2 * Sq * block_k if biased else 0)

    def head(n):    # queries and results twice, the accumulator, max and sum
        return n * (Sq * (2 * (Eq + d_v) * itemsize + 4 * d_v + 2 * 4 * 128)
                    + 2 * rank * E * itemsize)
    while block_q % tile == 0:
        for n in (4, 2, 1):
            if H % n == 0 and step(block_q) + head(n) <= _UP_VMEM:
                return n, block_q
        block_q //= 2
    return None


def _latent_up_chunk_kernel(pos_ref, layer_ref, up_layer_ref, q_ref, c_ref,
                            w_ref, *rest, sm_scale, block_q, block_k, nk, R,
                            d_nope, biased=False):
    """A chunk of UN-ABSORBED queries against latent rows, keys and values
    up-projected in VMEM.  A grid step is a GROUP OF HEADS against a key
    block: ``q_ref`` ``(Hg, Sq, Eq)`` holds the whole chunk's queries of
    the group, ``[q_n | R(q_r) | 0]`` a head (``Eq = d_nope + W - R``
    lanes), across the key blocks; ``c_ref`` ``(block_k, W)`` the block's
    rows ``[c | R(k_r) | 0]``; ``w_ref`` ``(Hg, R, d_nope + d_v)`` the
    group's ``W_kvb``.  For each head the block's ``[k_n | v] = c W_kvb``
    is ONE product, rounded once to the compute dtype (as the published
    attention and the plain reference round their keys and values); the
    head's key is ``[k_n | R(k_r) | 0]``, the row's own lanes past ``R``
    beside it (shared by the heads; the queries' lanes there are zero where
    the row's hold nothing), so a score is one product ``Eq`` deep; then the
    float32 online softmax over values ``d_v`` wide.  So a key is
    up-projected once a head a call, which is what the form's cost counts.

    The key axis of the grid ends at the chunk's causal frontier (a dynamic
    bound: no step past it), and a ``block_q`` tile of positions skips a
    key block wholly past ITS frontier.  Row ``b``'s result is final after
    its own last block (a shorter row of a ragged batch waits there).

    ``biased``: the chunk's ``(Sq, block_k)`` int8 mask of the key block
    leads ``rest``, 1 where a position may see a key and 0 where its bias
    is -inf (a selection, a ring's band): a key is scored where the mask
    AND the causal order allow, which is the bias added before the causal
    mask, to the bit.  A step is a group of heads, so the mask is read once
    a head group a key block: a quarter of the float32 bias's bytes."""
    mask_ref = None
    if biased:
        mask_ref, rest = rest[0], rest[1:]
    o_ref, acc_ref, m_ref, l_ref = rest
    b = pl.program_id(0)
    ki = pl.program_id(2)
    Hg, Sq = q_ref.shape[:2]
    pos = pos_ref[b]
    last = jnp.minimum((pos + Sq - 1) // block_k, nk - 1)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)

    def tile(h, t, keys, values):
        rows = pl.ds(t * block_q, block_q)
        s = jax.lax.dot_general(q_ref[h, rows, :], keys,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale                                # (BQ, BK)
        q_pos = pos + t * block_q + \
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = ki * block_k + \
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = k_pos <= q_pos
        if biased:
            seen = seen & (mask_ref[rows, :].astype(jnp.int32) != 0)
        _online_softmax_step(jnp.where(seen, s, NEG_INF), values,
                             acc_ref.at[h, rows], m_ref.at[h, rows],
                             l_ref.at[h, rows])

    @pl.when(ki <= last)
    def _update():
        c = c_ref[...]                                  # (BK, W)
        for h in range(Hg):
            kv = jnp.dot(c[:, :R], w_ref[h],
                         preferred_element_type=jnp.float32).astype(c.dtype)
            keys = jnp.concatenate([kv[:, :d_nope], c[:, R:]], axis=1)
            values = kv[:, d_nope:]                     # (BK, d_v)
            for t in range(Sq // block_q):
                if t == Sq // block_q - 1:  # the last tile sees every block
                    tile(h, t, keys, values)
                else:
                    pl.when(ki * block_k <= pos + (t + 1) * block_q - 1)(
                        functools.partial(tile, h, t, keys, values))

    @pl.when(ki == last)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def _latent_up_chunk(q, bank, layer, pos, sm_scale, up: LatentUp, R, tiles,
                     bias=None):
    """``q`` [B, Sq, H, d_nope + d_rope] un-absorbed against layer ``layer``
    of ``bank`` [L, B, Smax, W], through layer ``up.layer`` of ``up.w``:
    each head's attention output [B, Sq, H, d_v].  ONE custom call; its
    result is ``[B * H, Sq, d_v]``.  ``bias`` [B, Sq, Smax] float32, 0 or
    -inf: it reaches the kernel as the int8 mask of its zeros."""
    B, Sq, H, e = q.shape
    Smax, W = bank.shape[2:]
    Hg, block_q = tiles
    block_k = latent_block_k(Smax)
    nk = Smax // block_k
    E = up.w.shape[-1]
    d_v, Eq = E - up.d_nope, up.d_nope + W - R
    # head-major, the lanes beside the row's ``[R(k_r) | 0]`` zero
    q = jnp.pad(q, ((0, 0),) * 3 + ((0, Eq - e),)).transpose(0, 2, 1, 3)
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    interpret = interpret_mode()
    # key blocks up to the longest row's causal frontier
    live = jnp.minimum((jnp.max(pos_arr) + Sq - 1) // block_k + 1, nk)
    biased = bias is not None
    kernel = functools.partial(_latent_up_chunk_kernel, sm_scale=sm_scale,
                               block_q=block_q, block_k=block_k, nk=nk, R=R,
                               d_nope=up.d_nope, biased=biased)

    def c_idx(b, g, ki, pos_ref, layer_ref, up_layer_ref):
        last = jnp.minimum((pos_ref[b] + Sq - 1) // block_k, nk - 1)
        return (layer_ref[0], b, jnp.minimum(ki, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, H // Hg, nk if interpret else live),
        in_specs=[
            pl.BlockSpec((None, Hg, Sq, Eq), lambda b, g, ki, *_: (b, g, 0, 0)),
            pl.BlockSpec((None, None, block_k, W), c_idx),
            pl.BlockSpec((None, Hg, R, E),
                         lambda b, g, ki, pos_ref, layer_ref, up_layer_ref:
                         (up_layer_ref[0], g, 0, 0)),
        ] + ([pl.BlockSpec((None, Sq, block_k),
                           lambda b, g, ki, *refs:
                           (b, 0, c_idx(b, g, ki, *refs)[2]))]
             if biased else []),
        out_specs=pl.BlockSpec((Hg, Sq, d_v),
                               lambda b, g, ki, *_: (b * (H // Hg) + g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hg, Sq, d_v), jnp.float32),
            pltpu.VMEM((Hg, Sq, 1), jnp.float32),
            pltpu.VMEM((Hg, Sq, 1), jnp.float32),
        ],
    )
    o = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, d_v), q.dtype),
        interpret=interpret, name=LATENT_UP_CHUNK)(
            pos_arr, jnp.asarray(layer, jnp.int32).reshape(1),
            jnp.asarray(up.layer, jnp.int32).reshape(1), q, bank, up.w,
            *(((bias == 0).astype(jnp.int8),) if biased else ()))
    return o.reshape(B, H, Sq, d_v).transpose(0, 2, 1, 3)


def latent_cached_attention(q, bank, pos, sm_scale: float, rank: int,
                            layer=None, active=None, sweep=None, bias=None,
                            up: Optional[LatentUp] = None):
    """Latent attention: ``q`` over the latent pool ``bank`` [L, B, Smax,
    W] at ``layer`` (or one layer [B, Smax, W]), visibility ``<= pos + i``.

    ABSORBED (``up`` None): ``q`` [B, Sq, H, W] holds the absorbed queries;
    returns each head's weighted sum of the rows' first ``rank`` elements,
    [B, Sq, H, rank].  ``active`` and ``sweep`` as in ``cached_attention``.
    ``bias`` [B, Sq, Smax] float32, 0 or -inf a (query, key): what more than
    causality hides (a query's selection, a ring's band); the sweep still
    steps and streams every live block.

    With ``up`` (``LatentUp``) ``q`` [B, Sq, H, d_nope + d_rope] is
    UN-ABSORBED and the result each head's attention output [B, Sq, H,
    d_v].  The call's shape decides the form (``latent_up_projects``): a
    chunk whose tiles fit (``latent_up_tiles``, which counts a bias's mask)
    up-projects its keys and values inside the one chunk kernel, under its
    ``bias`` or under none; any other call absorbs ``up`` into the queries
    here, takes the absorbed path and up-projects what it returns."""
    if layer is None:
        bank, layer = bank[None], 0
    Smax = bank.shape[2]
    if up is not None:
        B, Sq, H, e = q.shape
        W, d_nope = bank.shape[-1], up.d_nope
        d_v = up.w.shape[-1] - d_nope
        block_k = latent_block_k(Smax)
        tiles = block_k is not None and latent_up_tiles(
            Sq, H, W, rank, d_nope, d_v, block_k, q.dtype.itemsize,
            biased=bias is not None)
        if use_pallas() and tiles and latent_up_projects(
                Sq, H, W, rank, d_nope, e - d_nope, d_v):
            return _latent_up_chunk(q, bank, layer, pos, sm_scale, up, rank,
                                    tiles, bias=bias)
        w = jax.lax.dynamic_index_in_dim(up.w, up.layer, 0, keepdims=False)
        absorbed = jnp.einsum("bshe,hre->bshr", q[..., :d_nope],
                              w[..., :d_nope])
        weighed = latent_cached_attention(
            jnp.pad(jnp.concatenate([absorbed, q[..., d_nope:]], -1),
                    ((0, 0),) * 3 + ((0, W - rank - (e - d_nope)),)),
            bank, pos, sm_scale, rank, layer, active, sweep, bias)
        return jnp.einsum("bshr,hre->bshe", weighed, w[..., d_nope:])
    B, Sq, H, W = q.shape
    plan = sweep_plan((W,), Smax, H)
    block_k = plan.block_k
    tiles = use_pallas() and plan.kernel is not None
    if tiles and Sq == 1:
        if sweep is None:
            sweep = decode_sweep(pos, B, Smax, block_k, active)
        o = _latent_decode(q[:, 0], bank, layer, pos, sweep, sm_scale,
                           block_k, rank, bias=bias).reshape(B, 1, H, rank)
    else:
        # a step's ``block_q * H`` query rows of ``W`` lanes: at most 16
        # positions of 64 heads of a 640-lane row, whose buffers, scores and
        # accumulator fill the kernel's 16 MB of VMEM; 8 of 128 heads, or
        # of 64 heads of a 1,152-lane row
        block_q = next((b for b in (16, 8) if Sq % b == 0
                        and (b * H * W <= 1024 * 640 or b == 8)), None)
        if tiles and Sq > 1 and block_q is not None:
            return _latent_chunk(q, bank, layer, pos, sm_scale, block_q,
                                 block_k, rank, bias=bias)
        one = jax.lax.dynamic_index_in_dim(bank, layer, 0, keepdims=False)
        o = latent_attention_reference(q, one, pos, sm_scale, rank, bias)
        if Sq > 1:
            return o
    if active is None:
        return o
    return jnp.where(active[:, None, None, None], o, jnp.zeros_like(o))


def cached_attention(q, cache_k, cache_v, pos,
                     sm_scale: Optional[float] = None,
                     k_scale=None, v_scale=None,
                     window=None, slopes=None, layer=None,
                     active=None, sweep=None, latent_rank=None,
                     kv_heads: Optional[int] = None, valid_from=None,
                     latent_up: Optional[LatentUp] = None, row=None):
    """q [B,Sq,H,D] over a padded cache [B,Smax,H,D], visibility ≤ pos+i.

    ``kv_heads`` (default ``H``): grouped heads.  The cache holds
    ``kv_heads`` key-value heads a token (its row is ``kv_heads * D``) and
    query head ``h`` reads key-value head ``h // (H / kv_heads)``; the row
    is never repeated out to ``H`` heads.  Full-precision cache, no ALiBi,
    a band (``window``) for a chunk's call only (``Sq > 1``: a single token
    over grouped heads sweeps whole rows or a ring); the grouped sweep
    wants ``D`` a multiple of 128, or 64 in a row of whole lane rows
    (``grouped_sweep_serves``).

    ``valid_from`` (scalar or [B], a chunk's call only): the row's first
    real key; the slots before it are hidden (``ring_attention``'s unrolled
    ring starts with the cells no token has reached).

    A cache of ONE bank (``cache_v`` None) is a latent pool: ``q`` holds the
    absorbed queries, ``latent_rank`` says how much of a row the
    probabilities weigh, and ``latent_cached_attention`` serves the call
    (``latent_up``: its ``up``, with un-absorbed queries).

    With ``layer`` (scalar, may be traced — a layer scan's index) the
    cache operands are the whole stacked pool as ``gpt_inference`` stores
    it, [L,B,Smax,H*D] (scales [L,B,Smax,H]), and the decode kernel reads
    that layer of it WHERE IT LIES: the layer index rides the scalar
    prefetch beside ``pos``; no layer is sliced out and nothing is
    transposed to feed the kernel.  A per-layer [B,Smax,H,D] cache is the
    same call on a stack of one.

    With ``row`` (scalar or [B], may be traced; a chunk's call on a stacked
    two-bank pool only) query row ``b`` reads row ``row[b]`` of the pool,
    which may hold any number of them: an admission's chunks over the slot
    they are written to.  Where the heads allow (``row_chunk_lanes``) and
    the call brings no scales, slopes or ``valid_from``, the chunk kernel
    addresses ``(layer, row)`` of the folded banks through its index maps
    (``_row_chunk``) and takes the queries folded too; any other such call
    slices its rows out of the layer and goes the way of a call without.

    ``pos``: scalar, or a per-row [B] vector for ragged decode.
    Single-token decode (Sq=1) takes the Pallas streaming kernel, whose
    steps are the live blocks of the live rows and nothing else
    (``decode_sweep``): its time and its bytes follow the live context,
    not ``B x Smax``.  ``active`` ([B] bool, default all rows) names the
    live rows of a slot batch — ``pos`` cannot, a row at ``pos`` 0 sees one
    key — and a dead row costs no step, streams nothing and returns zeros.
    ``sweep`` is ``decode_sweep`` of the same ``pos``, ``active`` and
    ``window``, built by a caller that makes this call once per layer, and
    the kernel's block is the one the list was built for
    (``sweep_block_k``); left out, it is built here for the plan's block
    (``sweep_plan``, which also says which of the sweeps serves the row).
    Multi-token chunks (chunked prefill / ``extend``) take the chunk kernel
    when the shapes tile — O(block) VMEM instead of a dense [Sq, Smax]
    score tensor; remaining shapes use the dense reference.  Its grid step
    is a key-value head's group against one key block (``q`` re-laid
    ``[B*Hkv, G, Sq, D]``, ``G`` read from the shapes, 1 for ungrouped
    heads), its tile ``chunk_block_q`` positions x ``chunk_block_k`` keys
    from ``(Sq, Smax, G)``.

    With ``k_scale``/``v_scale`` ([B,Smax,H,1] fp32; stacked [L,B,Smax,H])
    the cache holds int8 codes; the decode kernel streams the codes
    (halving the HBM stream) and applies the scales to the scores and the
    probabilities in VMEM, the chunk kernel does the same with the blocks
    its pipeline brings, and the non-kernel fallbacks dequantize before
    the dense math.

    ``window`` (scalar, possibly traced — GPT-Neo's alternating stack
    carries it through a layer scan) bands visibility to the trailing
    ``window`` slots: a row's live blocks run from its band's start to its
    frontier, so banded decode streams O(window) HBM bytes per step
    instead of O(Smax), and short rows of a ragged batch stop at their own
    frontier (the chunk kernel clamps dead block indices into the live
    range instead: no bytes, but a step each).  ``slopes`` ([H] fp32) adds
    the ALiBi ``-slope·dist`` bias (BLOOM family) inside the kernel.  Both
    compose with the int8 cache.
    """
    if cache_v is None:
        return latent_cached_attention(q, cache_k, pos, sm_scale,
                                       latent_rank, layer=layer,
                                       active=active, sweep=sweep,
                                       up=latent_up)
    B, Sq, H, D = q.shape
    Hkv = H if kv_heads is None else int(kv_heads)
    G = H // Hkv
    int8_cache = k_scale is not None
    if G > 1 and (int8_cache or slopes is not None):
        raise NotImplementedError(
            "grouped heads: full-precision cache, no ALiBi")
    if G > 1 and window is not None and Sq == 1:
        raise NotImplementedError(
            "grouped heads: no window under a single token's sweep (a "
            "chunk's call takes one; a ring needs none)")
    if valid_from is not None and Sq == 1:
        raise NotImplementedError(
            "valid_from bounds a chunk's call; a single token's frontier "
            "is its pos")
    if row is not None and (Sq == 1 or layer is None):
        raise NotImplementedError(
            "row= names a chunk's rows of a stacked pool; a single token "
            "sweeps every row by its work list")
    banks = (cache_k, cache_v) + ((k_scale, v_scale) if int8_cache else ())
    if layer is None:
        # [B,Smax,H,*] → a pool of one layer, heads folded into the row
        banks = tuple(x.reshape((1,) + x.shape[:2] + (-1,)) for x in banks)
        layer = 0
    Smax = banks[0].shape[2]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(D)
    plan = sweep_plan((Hkv * D,) * 2, Smax, H, Hkv,
                      banks[0].dtype.itemsize)
    # chunk path: pos may be scalar OR per-row [B] (ragged chunks — the
    # kernel reads its row's frontier from pos_ref everywhere: mask, live
    # range, and DMA clamp); the chunk must tile in the q (sublane)
    # dimension
    key_block = chunk_block_k(Smax)
    block_q = chunk_block_q(Sq, G, key_block) \
        if Sq > 1 and key_block is not None else None

    def dead_rows_zero(o):
        """The kernel never writes a dead row's result; the dense path
        computes one nobody may lean on."""
        if active is None:
            return o
        return jnp.where(active[:, None, None, None], o, jnp.zeros_like(o))

    if use_pallas() and plan.kernel is not None and Sq == 1:
        if sweep is None:
            sweep = decode_sweep(pos, B, Smax, plan.block_k, active, window)
        block_k = sweep_block_k(sweep, B, Smax)
        if plan.kernel == GROUPED_SWEEP:
            o = _gqa_decode(q[:, 0], banks[0], banks[1], layer, pos, sweep,
                            scale, block_k, G)
        else:
            ks, vs = banks[2:] if int8_cache else (None, None)
            o = _decode(q.reshape(B, 1, H * D), banks[0], banks[1], layer,
                        pos, sweep, scale, block_k, H, plan.copy_rows,
                        ks=ks, vs=vs, window=window, slopes=slopes)
        return dead_rows_zero(o.reshape(B, 1, H, D))

    tiles = use_pallas() and key_block is not None and block_q is not None
    if row is not None:
        if tiles and not int8_cache and slopes is None and valid_from is \
                None and row_chunk_lanes(Hkv, G, D) is not None:
            return _row_chunk(q.reshape(B, Sq, H * D), banks[0], banks[1],
                              layer, row, pos, scale, block_q, key_block,
                              Hkv, window=window).reshape(q.shape)
        rows = jnp.broadcast_to(jnp.asarray(row, jnp.int32).reshape(-1), (B,))
        banks = [jax.vmap(lambda r, x=x: jax.lax.dynamic_slice(
            x, (layer, r, 0, 0), (1, 1) + x.shape[2:])[0, 0])(rows)[None]
            for x in banks]
        layer = 0
    # one layer, heads unfolded: [B,Smax,Hkv,D] (scales [B,Smax,H,1])
    banks = [jax.lax.dynamic_index_in_dim(x, layer, 0, keepdims=False)
             .reshape(B, Smax, Hkv, -1) for x in banks]
    if tiles:
        def to3(x):
            return x.transpose(0, 2, 1, 3).reshape(B * x.shape[2],
                                                   x.shape[1], -1)

        # the G query heads of a key-value head beside one another: a
        # step of the kernel takes the group against one key block
        ks3, vs3 = map(to3, banks[2:]) if int8_cache else (None, None)
        o4 = _chunk(to3(q).reshape(B * Hkv, G, Sq, D), to3(banks[0]),
                    to3(banks[1]), pos, scale, block_q, key_block, Hkv,
                    ks3=ks3, vs3=vs3, window=window, slopes=slopes,
                    valid_from=valid_from)
        return o4.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)

    if int8_cache:
        banks = [dequantize_kv(banks[0], banks[2], q.dtype),
                 dequantize_kv(banks[1], banks[3], q.dtype)]
    if G > 1:       # the dense formula reads a key-value head per query head
        banks = [jnp.repeat(x, G, axis=2) for x in banks]
    o = cached_attention_reference(q, banks[0], banks[1], pos, scale,
                                   window=window, slopes=slopes,
                                   valid_from=valid_from)
    return dead_rows_zero(o) if Sq == 1 else o


def ring_attention(q, ring_k, ring_v, fresh_k, fresh_v, pos, window: int,
                   layer, sm_scale: Optional[float] = None,
                   kv_heads: Optional[int] = None):
    """A chunk's queries over a RING of cached rows and the chunk's own.

    ``ring_k``/``ring_v`` [L, B, R, Hkv*D] keep, at layer ``layer``, the
    token at position ``p`` in cell ``p mod R``: the last ``R`` tokens
    BEFORE the chunk (which is not written yet: a chunk written first would
    overwrite keys its first queries still see).  ``q`` [B, Sq, H, D] sits
    at ``pos .. pos + Sq - 1`` (``pos`` scalar or [B]) and ``fresh_k`` /
    ``fresh_v`` [B, Sq, Hkv, D] are the chunk's own rows; query ``i`` sees
    the keys at ``0 <= pos + i - j < window``.

    The ring is unrolled into the order of its positions (cell ``pos mod
    R`` first: two slices of it, a copy of ``R`` rows), the chunk's rows
    follow, and ``cached_attention`` takes the ``R + Sq`` rows as a cache
    whose first query sits at ``R``, banded by ``window`` and bounded below
    by the first cell a token has reached (``R - pos``): the chunk kernel
    visits the key blocks a band of ``window + Sq`` touches and no more."""
    B, Sq = q.shape[:2]
    R = ring_k.shape[2]
    p = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))

    def unrolled(bank, fresh):
        one = jax.lax.dynamic_index_in_dim(bank, layer, 0, keepdims=False)
        twice = jnp.concatenate([one, one], axis=1)
        old = jax.vmap(lambda t, s: jax.lax.dynamic_slice_in_dim(
            t, s, R, 0))(twice, p % R)
        rows = jnp.concatenate(
            [old, fresh.reshape(B, Sq, -1).astype(bank.dtype)], axis=1)
        return rows.reshape(B, R + Sq, fresh.shape[2], -1)

    return cached_attention(
        q, unrolled(ring_k, fresh_k), unrolled(ring_v, fresh_v),
        jnp.full((B,), R, jnp.int32), sm_scale=sm_scale, window=window,
        kv_heads=kv_heads, valid_from=jnp.maximum(R - p, 0))


# ------------------------------------------------- a learned selection

def index_block_k(Smax: int) -> Optional[int]:
    """Tokens in one block of the index's kernels, or None where ``Smax``
    does not tile: an index key is a single lane row, a sixth of a latent
    row, so a step takes four times the latent sweep's tokens."""
    return next((b for b in (2048, 1024, 512, 256, 128) if Smax % b == 0),
                None)


def _index_decode_kernel(rows_ref, blocks_ref, n_ref, layer_ref, q_ref, w_ref,
                         k_ref, o_ref):
    """One live block of one live row (``decode_sweep``'s list, at the
    index's block): the row's ``(Hi, Di)`` index queries against the block's
    ``(block_k, Di)`` index keys, ``relu`` of every head's float32 score,
    the heads summed under the row's weights ``(Hi, 1)``: one ``(1,
    block_k)`` block of scores.  Nothing is carried from step to step; a
    block no step visits keeps what the array held."""
    @pl.when(pl.program_id(0) < n_ref[0])
    def _score():
        s = jax.lax.dot_general(q_ref[...], k_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        o_ref[...] = jnp.sum(jnp.maximum(s, 0.0) * w_ref[...], axis=0,
                             keepdims=True)


def _index_decode(q, w, bank, layer, sweep, block_k):
    """``q`` [B, Hi, Di], ``w`` [B, Hi] float32, against layer ``layer`` of
    the index keys ``bank`` [L, B, Smax, Di] where they lie: [B, Smax]
    float32, written where the sweep is live."""
    B, Hi, Di = q.shape
    Smax = bank.shape[2]
    rows, blocks, n = sweep
    prefetch = (rows, blocks, n, jnp.asarray(layer, jnp.int32).reshape(1))
    interpret = interpret_mode()
    by_row = lambda s, rows_ref, *_: (rows_ref[s], 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(rows.shape[0] if interpret else jnp.maximum(n[0], 1),),
        in_specs=[
            pl.BlockSpec((None, Hi, Di), by_row),
            pl.BlockSpec((None, Hi, 1), by_row),
            pl.BlockSpec((None, None, block_k, Di),
                         lambda s, rows_ref, blocks_ref, n_ref, layer_ref:
                         (layer_ref[0], rows_ref[s], blocks_ref[s], 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, block_k),
                               lambda s, rows_ref, blocks_ref, *_:
                               (rows_ref[s], 0, blocks_ref[s])),
    )
    return pl.pallas_call(
        _index_decode_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, Smax), jnp.float32),
        interpret=interpret, name="index_decode_scores")(
            *prefetch, q, w.astype(jnp.float32)[..., None], bank)[:, 0]


def _index_chunk_kernel(pos_ref, layer_ref, q_ref, w_ref, k_ref, o_ref, *,
                        block_q, block_k, Hi):
    """A tile of a chunk's index scores: ``q_ref`` is ``(Hi * block_q,
    Di)``, HEAD-major (row ``h * block_q + i`` is head ``h`` of the tile's
    position ``i``), so the heads' sum is ``Hi`` aligned slices added up;
    ``w_ref`` the rows' weights ``(Hi * block_q, 1)``.  Blocks past the
    tile's causal frontier are not computed."""
    b, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(ki * block_k <= pos_ref[b] + (qi + 1) * block_q - 1)
    def _score():
        s = jax.lax.dot_general(q_ref[...], k_ref[...],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.maximum(s, 0.0) * w_ref[...]
        acc = s[:block_q]
        for h in range(1, Hi):
            acc = acc + s[h * block_q:(h + 1) * block_q]
        o_ref[...] = acc


def _index_chunk(q, w, bank, layer, pos, block_q, block_k):
    """``q`` [B, Sq, Hi, Di], ``w`` [B, Sq, Hi] against layer ``layer`` of
    ``bank`` [L, B, Smax, Di]: [B, Sq, Smax] float32, written up to each
    tile's causal frontier."""
    B, Sq, Hi, Di = q.shape
    Smax = bank.shape[2]
    nq = Sq // block_q
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))

    def tiles(x):   # [B, Sq, Hi, *] -> [B, nq, Hi * block_q, *], head-major
        return x.reshape(B, nq, block_q, Hi, -1).transpose(0, 1, 3, 2, 4) \
            .reshape(B, nq, Hi * block_q, x.shape[-1])

    def kv_idx(b, qi, ki, pos_ref, layer_ref):
        hi = (pos_ref[b] + (qi + 1) * block_q - 1) // block_k
        return (layer_ref[0], b, jnp.minimum(ki, hi), 0)

    by_tile = lambda b, qi, ki, *_: (b, qi, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nq, Smax // block_k),
        in_specs=[
            pl.BlockSpec((None, None, Hi * block_q, Di), by_tile),
            pl.BlockSpec((None, None, Hi * block_q, 1), by_tile),
            pl.BlockSpec((None, None, block_k, Di), kv_idx),
        ],
        out_specs=pl.BlockSpec((None, block_q, block_k),
                               lambda b, qi, ki, *_: (b, qi, ki)),
    )
    kernel = functools.partial(_index_chunk_kernel, block_q=block_q,
                               block_k=block_k, Hi=Hi)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Sq, Smax), jnp.float32),
        interpret=interpret_mode(), name="index_chunk_scores")(
            pos_arr, jnp.asarray(layer, jnp.int32).reshape(1), tiles(q),
            tiles(w.astype(jnp.float32)[..., None]), bank)


def index_scores(q, w, keys, pos=None, layer=None, active=None, sweep=None):
    """The learned index's scores: ``q`` [B, Sq, Hi, Di] index queries and
    ``w`` [B, Sq, Hi] float32 head weights against the index keys ``keys``
    [B, Smax, Di] (or, with ``layer``, the bank [L, B, Smax, Di] read where
    it lies): ``sum_h w[.., h] relu(q[.., h, :] . k)`` in float32, [B, Sq,
    Smax].  ``pos`` (scalar or [B]; default 0: a whole sequence over
    itself) is the first query's position; the kernels score no further
    than a query's causal frontier and a dead row (``active``) not at all,
    and what they skip holds no value at all: ``topk_bias`` reads only the
    keys at or before a query.  ``sweep``: a single token's work list
    (``decode_sweep`` at ``index_block_k``), built here where not given."""
    B, Sq, Hi, Di = q.shape
    if layer is None:
        keys, layer = keys[None], 0
    Smax = keys.shape[2]
    pos = jnp.zeros((), jnp.int32) if pos is None else pos
    block_k = index_block_k(Smax)
    if use_pallas() and block_k is not None and Hi % 8 == 0:
        if Sq == 1:
            if sweep is None:
                sweep = decode_sweep(pos, B, Smax, block_k, active)
            return _index_decode(q[:, 0], w[:, 0], keys, layer, sweep,
                                 block_k)[:, None]
        block_q = next((b for b in (16, 8) if Sq % b == 0), None)
        if block_q is not None:
            return _index_chunk(q, w, keys, layer, pos, block_q,
                                min(block_k, 512))
    one = jax.lax.dynamic_index_in_dim(keys, layer, 0, keepdims=False)
    s = jnp.einsum("bqhd,bkd->bqhk", q, one,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(s, 0.0) * w.astype(jnp.float32)[..., None],
                   axis=2)


def topk_bias(scores, q_pos, k: int):
    """The EXACT selection of the ``min(t + 1, k)`` largest of a query's
    scores among the keys at or before it, ties to the lower position, as
    the bias of a masked pass: ``scores`` [B, Sq, S] float32, ``q_pos`` [B
    or 1, Sq] the queries' positions ``t``; returns [B, Sq, S] float32, 0 on
    a chosen key and -inf on every other.

    No sort: float32 scores order as their bit patterns do (negatives
    flipped), so the ``kk``-th largest is found bit by bit, 32 counts of
    ``key >= candidate`` over the row, and a key is chosen if it lies above
    that threshold or ties with it among the first (by position) that fill
    the count.  A score of -0.0 counts as 0.0, as a comparison has it."""
    S = scores.shape[-1]
    q_pos = jnp.asarray(q_pos, jnp.int32)
    eligible = jnp.arange(S, dtype=jnp.int32) <= q_pos[..., None]
    s = jnp.where(scores == 0, 0.0, scores).astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    ordered = jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits)
    key = jax.lax.bitcast_convert_type(ordered, jnp.uint32) \
        ^ jnp.uint32(0x80000000)
    key = jnp.where(eligible, key, jnp.uint32(0))
    kk = jnp.minimum(q_pos + 1, k)                      # [B or 1, Sq]

    def refine(i, t):
        cand = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        count = jnp.sum(key >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(count >= kk, cand, t)

    threshold = jax.lax.fori_loop(
        0, 32, refine, jnp.zeros(key.shape[:-1], jnp.uint32))[..., None]
    above = eligible & (key > threshold)
    ties = eligible & (key == threshold)
    room = kk[..., None] - jnp.sum(above, axis=-1, keepdims=True,
                                   dtype=jnp.int32)
    chosen = above | (ties & (jnp.cumsum(ties, axis=-1, dtype=jnp.int32)
                              <= room))
    return jnp.where(chosen, 0.0, NEG_INF).astype(jnp.float32)


def ring_bias(pos, R: int, window: int):
    """A single token's bias over its ring of ``R`` cells, [B, 1, R]
    float32: the token at ``pos`` ([B]; already written to cell ``pos mod
    R``) sees the cells whose token lies at most ``window - 1`` positions
    back, 0 there and -inf on the cells past the window or not yet
    reached."""
    p = jnp.asarray(pos, jnp.int32).reshape(-1, 1)
    back = (p - jnp.arange(R, dtype=jnp.int32)[None]) % R
    seen = (back < window) & (back <= p)
    return jnp.where(seen, 0.0, NEG_INF).astype(jnp.float32)[:, None]


def latent_ring_attention(q, ring, fresh, pos, window: int, layer,
                          sm_scale: float, rank: int,
                          up: Optional[LatentUp] = None):
    """``ring_attention`` for a ring of latent rows: a chunk's absorbed
    queries ``q`` [B, Sq, H, W] at ``pos .. pos + Sq - 1`` over the ring
    ``ring`` [L, B, R, W] as it stood BEFORE the chunk (position ``p`` in
    cell ``p mod R``) and the chunk's own rows ``fresh`` [B, Sq, W]; query
    ``i`` sees the keys at ``0 <= pos + i - j < window``.  The ring is
    unrolled into the order of its positions, the chunk's rows follow, and
    the latent chunk kernel takes the ``R + Sq`` rows (in whole key blocks)
    as a pool whose first query sits at ``R``, the band and the cells no
    token has reached hidden by its bias.  [B, Sq, H, rank]; with ``up``,
    ``q`` un-absorbed and the result the heads' outputs, as
    ``latent_cached_attention`` has them."""
    B, Sq = q.shape[:2]
    R = ring.shape[2]
    p = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    one = jax.lax.dynamic_index_in_dim(ring, layer, 0, keepdims=False)
    old = jax.vmap(lambda t, s: jax.lax.dynamic_slice_in_dim(
        t, s, R, 0))(jnp.concatenate([one, one], axis=1), p % R)
    # the pool is this call's own, so it is as long as suits the kernels:
    # whole key blocks of their largest size (``latent_block_k``), the rows
    # past the chunk's zeros that no query's causal frontier reaches
    keys = R + Sq
    if keys > 512:
        keys = -(-keys // 512) * 512
    rows = jnp.concatenate(
        [old, fresh.astype(ring.dtype)]
        + ([jnp.zeros((B, keys - R - Sq, ring.shape[-1]), ring.dtype)]
           if keys > R + Sq else []), axis=1)
    # key j of the unrolled pool lies at position pos - R + j
    dist = (R + jnp.arange(Sq, dtype=jnp.int32))[:, None] \
        - jnp.arange(keys, dtype=jnp.int32)[None, :]
    seen = ((dist >= 0) & (dist < window))[None] \
        & (jnp.arange(keys, dtype=jnp.int32)[None, None, :]
           >= (R - p)[:, None, None])
    return latent_cached_attention(
        q, rows, jnp.full((B,), R, jnp.int32), sm_scale, rank,
        bias=jnp.where(seen, 0.0, NEG_INF).astype(jnp.float32), up=up)
