"""Fused bias + GeLU + dropout as a Pallas TPU kernel (fwd + bwd).

Counterpart of the reference's fused transformer elementwise kernels
(``csrc/transformer/gelu_kernels.cu`` + ``dropout_kernels.cu`` — the
bias_add_gelu / bias_dropout fusions of the training block).  One kernel
streams the MLP hidden activation once: bias add, tanh-GeLU, and the
dropout mask (a counter-based hash PRNG over global element indices)
happen in VMEM, so HBM sees a single read + write instead of three
kernel-sized round-trips — and no dropout mask is ever materialized in
HBM: the backward *regenerates* it from the same seed.

Backward is a second kernel computing ``gelu'(x+b)·mask·g`` with the
identical PRNG stream (seeded per grid block), plus the bias grad as a
row-sum emitted per block and reduced outside.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .utils import cdiv, interpret_mode, use_pallas

_BLOCK_ROWS = 256
_BLOCK_BYTES = 1 << 20   # cap on one fp32 (rows, C) tile, see _specs
_DB_ROWS = 8    # sublanes of one bias-gradient output block
_SQRT_2_OVER_PI = math.sqrt(2.0 / math.pi)


def _gelu(x):
    inner = _SQRT_2_OVER_PI * (x + 0.044715 * x * x * x)
    return 0.5 * x * (1.0 + jnp.tanh(inner))


def _gelu_grad(x):
    x3 = 0.044715 * x * x * x
    inner = _SQRT_2_OVER_PI * (x + x3)
    t = jnp.tanh(inner)
    sech2 = 1.0 - t * t
    return 0.5 * (1.0 + t) + 0.5 * x * sech2 * _SQRT_2_OVER_PI * \
        (1.0 + 3.0 * 0.044715 * x * x)


def _keep_mask(shape, rate: float, seed, block_id, block_rows):
    """Bernoulli(1-rate) from a counter-based hash PRNG.

    Each element's stream position is its global (row, col) index mixed
    with the seed through a murmur3-style finalizer — stateless, so the
    backward regenerates the identical mask from (seed, block_id), and the
    same code runs on hardware and in interpret mode (the reference's
    philox-seeded dropout kernels play this role)."""
    rows = jax.lax.broadcasted_iota(jnp.uint32, shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, shape, 1)
    gid = (rows + jnp.uint32(block_id * block_rows)) * jnp.uint32(shape[1]) \
        + cols
    h = gid ^ (jnp.uint32(seed) * jnp.uint32(0x9E3779B9))
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    # keep iff u >= rate for u = (h >> 8) / 2^24, decided on the integers:
    # Mosaic has no uint32 -> float32 cast, and both u (a 24-bit integer
    # times a power of two) and the float32 rate are exact, so comparing
    # h >> 8 with ceil(rate * 2^24) gives the same mask bit for bit
    threshold = math.ceil(float(np.float32(rate)) * (1 << 24))
    keep = jax.lax.bitcast_convert_type(h >> 8, jnp.int32) >= threshold
    return keep.astype(jnp.float32)


def _fwd_kernel(seed_ref, x_ref, b_ref, o_ref, *, rate, block_rows):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y = _gelu(x)
    if rate > 0.0:
        y = y * _keep_mask(y.shape, rate, seed_ref[0], i, block_rows) \
            * (1.0 / (1.0 - rate))
    o_ref[...] = y.astype(o_ref.dtype)


def _bwd_kernel(seed_ref, x_ref, b_ref, g_ref, dx_ref, db_ref, *, rate,
                block_rows, total_rows):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    if rate > 0.0:  # SAME stream as the forward
        g = g * _keep_mask(x.shape, rate, seed_ref[0], i, block_rows) \
            * (1.0 / (1.0 - rate))
    dx = g * _gelu_grad(x)
    dx_ref[...] = dx.astype(dx_ref.dtype)
    # mask the last block's padding rows out of the bias reduction: their
    # dx writes are discarded, but a row-sum would carry undefined padding
    # contents into db on hardware
    row = i * block_rows + jax.lax.broadcasted_iota(jnp.int32, dx.shape, 0)
    db = jnp.sum(jnp.where(row < total_rows, dx, 0.0), axis=0, keepdims=True)
    # the per-block row-sum is written to all _DB_ROWS sublanes of its
    # output block: a (1, C) block would break Mosaic's (8, 128) tiling
    db_ref[...] = jnp.broadcast_to(db, db_ref.shape)


def _specs(rows, C):
    # the kernels hold several fp32 (block, C) temporaries next to the
    # double-buffered operands, all inside the compiler's scoped VMEM
    # limit: narrow the row block as C grows (C=4096 gives 64 rows)
    fit = max(8, _BLOCK_BYTES // (4 * C) // 8 * 8)
    block = min(_BLOCK_ROWS, fit, rows)
    grid = (cdiv(rows, block),)
    row_blk = pl.BlockSpec((block, C), lambda i: (i, 0))
    bias_blk = pl.BlockSpec((1, C), lambda i: (0, 0))
    return grid, block, row_blk, bias_blk


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _bias_gelu(x2, b, seed, rate):
    rows, C = x2.shape
    grid, block, row_blk, bias_blk = _specs(rows, C)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, rate=rate, block_rows=block),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), row_blk, bias_blk],
        out_specs=row_blk,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x2.dtype),
        interpret=interpret_mode(),
    )(seed, x2, b.reshape(1, -1))


def _bias_gelu_fwd(x2, b, seed, rate):
    return _bias_gelu(x2, b, seed, rate), (x2, b, seed)


def _bias_gelu_bwd(rate, res, g):
    x2, b, seed = res
    rows, C = x2.shape
    grid, block, row_blk, bias_blk = _specs(rows, C)
    dx, db_part = pl.pallas_call(
        functools.partial(_bwd_kernel, rate=rate, block_rows=block,
                          total_rows=rows),
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), row_blk, bias_blk,
                  row_blk],
        out_specs=[row_blk, pl.BlockSpec((_DB_ROWS, C), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, x2.dtype),
            jax.ShapeDtypeStruct((grid[0] * _DB_ROWS, C), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(seed, x2, b.reshape(1, -1), g)
    return dx, jnp.sum(db_part[::_DB_ROWS], axis=0).astype(b.dtype), None


_bias_gelu.defvjp(_bias_gelu_fwd, _bias_gelu_bwd)


def bias_gelu_dropout(x, bias, dropout_rate: float = 0.0,
                      seed: Optional[int] = 0):
    """``dropout(gelu(x + bias))`` fused.  x: [..., C], bias: [C].

    ``seed`` (int or scalar array) makes the mask deterministic — the
    backward regenerates it instead of storing it.  Falls back to plain
    XLA off-TPU (interpret-mode tests cover the kernel itself).
    """
    C = x.shape[-1]
    if not use_pallas() or C % 128 != 0:
        y = _gelu(x.astype(jnp.float32) + bias.astype(jnp.float32))
        if dropout_rate > 0.0:
            # fold_in honours int AND traced-array seeds identically
            key = jax.random.fold_in(jax.random.PRNGKey(0),
                                     jnp.asarray(seed, jnp.int32).reshape(()))
            keep = jax.random.bernoulli(key, 1.0 - dropout_rate, y.shape)
            y = jnp.where(keep, y / (1.0 - dropout_rate), 0.0)
        return y.astype(x.dtype)
    x2 = x.reshape(-1, C)
    seed_arr = jnp.asarray([seed] if not hasattr(seed, "shape")
                           else seed.reshape(1), jnp.int32)
    out = _bias_gelu(x2, bias, seed_arr, float(dropout_rate))
    return out.reshape(x.shape)
