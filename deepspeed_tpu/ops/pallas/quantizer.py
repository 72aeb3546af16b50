"""Grouped int8/int4 quantize/dequantize kernels.

Counterpart of the reference's CUDA quantizer
(``csrc/quantization/{quantize.cu,dequantize.cu,fake_quantizer.cu}``,
bindings ``pt_binding.cpp:159-178``: ``ds_quantize_*`` symmetric,
``ds_sr_quantize_*`` stochastic-rounding, asymmetric variants).  Serves the
same three clients: MoQ-style quantize-aware training (fake quant),
compression, and int8 inference/1-bit comm payloads.

Grouped scheme: the flat tensor is split into ``groups`` equal rows; each row
gets one fp32 scale (and offset when asymmetric).  Pallas path on TPU with
in-kernel stochastic rounding off the per-core PRNG; jnp reference elsewhere.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .utils import interpret_mode, use_pallas


def _qrange(bits: int) -> float:
    return float(2 ** (bits - 1) - 1)


# ----------------------------------------------------- shared symmetric math
#
# The symmetric grouped scheme (absmax scale per group, round-to-nearest,
# clip to the signed range) is shared verbatim with the quantized wire
# collectives (``runtime/comm/quantized.py``): the collective payloads must
# quantize exactly like the kernels so parity tests and EF bounds transfer.

def quantize_symmetric(x2: jnp.ndarray, bits: int = 8
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``x2 [groups, gsize]`` → ``(codes int8 [groups, gsize],
    scales f32 [groups])`` — symmetric per-group absmax quantization.

    Pure jnp (shard_map/jit-safe).  All-zero groups get the 1e-12 scale
    floor, so codes are 0 and the round trip is exactly 0 — no 0/0."""
    qmax = _qrange(bits)
    scale = jnp.maximum(
        jnp.max(jnp.abs(x2.astype(jnp.float32)), axis=1, keepdims=True)
        / qmax, 1e-12)
    q = jnp.clip(jnp.round(x2 / scale), -qmax, qmax).astype(jnp.int8)
    return q, scale[:, 0]


def dequantize_symmetric(codes: jnp.ndarray,
                         scales: jnp.ndarray) -> jnp.ndarray:
    """Inverse of :func:`quantize_symmetric`; returns f32 [groups, gsize]."""
    return codes.astype(jnp.float32) * scales[:, None]


# ------------------------------------------------------------------ reference

def _quantize_ref(x2, bits, symmetric, stochastic, key):
    qmax = _qrange(bits)
    if symmetric:
        if not stochastic:
            q, scales = quantize_symmetric(x2, bits)
            return q, scales, jnp.zeros_like(scales)
        scale = jnp.max(jnp.abs(x2), axis=1, keepdims=True) / qmax
        scale = jnp.maximum(scale, 1e-12)
        offset = jnp.zeros_like(scale)
        scaled = x2 / scale
    else:
        lo = jnp.min(x2, axis=1, keepdims=True)
        hi = jnp.max(x2, axis=1, keepdims=True)
        scale = jnp.maximum((hi - lo) / (2.0 * qmax), 1e-12)
        offset = (hi + lo) / 2.0
        scaled = (x2 - offset) / scale
    if stochastic:
        noise = jax.random.uniform(key, x2.shape) - 0.5
        q = jnp.round(scaled + noise)
    else:
        q = jnp.round(scaled)
    q = jnp.clip(q, -qmax, qmax).astype(jnp.int8)
    return q, scale[:, 0], offset[:, 0]


# -------------------------------------------------------------------- kernels

def _quant_kernel(seed_ref, x_ref, q_ref, scale_ref, offset_ref, *,
                  bits, symmetric, stochastic):
    qmax = _qrange(bits)
    x = x_ref[...].astype(jnp.float32)
    if symmetric:
        scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True) / qmax,
                            1e-12)
        offset = jnp.zeros_like(scale)
    else:
        lo = jnp.min(x, axis=1, keepdims=True)
        hi = jnp.max(x, axis=1, keepdims=True)
        scale = jnp.maximum((hi - lo) / (2.0 * qmax), 1e-12)
        offset = (hi + lo) / 2.0
    scaled = (x - offset) / scale
    if stochastic:
        pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
        bits = pltpu.bitcast(pltpu.prng_random_bits(scaled.shape), jnp.int32)
        # the 32 random bits as an unsigned value in float32, built from
        # two 16-bit halves: Mosaic has no uint32 -> float32 cast, each
        # half converts exactly, and the one rounding left (in the add)
        # is the rounding that cast would have made
        hi = jax.lax.shift_right_logical(bits, 16).astype(jnp.float32)
        lo = (bits & 0xFFFF).astype(jnp.float32)
        noise = (hi * 65536.0 + lo) * (1.0 / 4294967296.0) - 0.5
        q = jnp.round(scaled + noise)
    else:
        q = jnp.round(scaled)
    q_ref[...] = jnp.clip(q, -qmax, qmax).astype(jnp.int8)
    scale_ref[...] = scale
    offset_ref[...] = offset


def quantize(x: jnp.ndarray, groups: int = 1, bits: int = 8,
             symmetric: bool = True, stochastic: bool = False,
             key: Optional[jax.Array] = None
             ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Quantize ``x`` to int8 codes with per-group scale/offset.

    Returns ``(codes int8 [groups, n//groups], scale f32 [groups],
    offset f32 [groups])``.  ``bits`` ≤ 8 (codes stay int8; range shrinks).
    """
    n = x.size
    assert n % groups == 0, f"{n} elements not divisible into {groups} groups"
    gsize = n // groups
    x2 = x.reshape(groups, gsize)
    if key is None:
        key = jax.random.PRNGKey(0)
    # Mosaic tiling: the row block must be a multiple of 8 or span all
    # groups; it must also divide groups exactly or trailing groups would
    # never be written.
    if groups % 8 == 0:
        rows = 8
    elif groups * gsize * 4 <= (4 << 20):
        rows = groups  # single block, fits VMEM comfortably
    else:
        rows = 0
    if not use_pallas() or gsize < 128 or rows == 0:
        return _quantize_ref(x2, bits, symmetric, stochastic, key)
    seed = jax.random.randint(key, (1,), 0, 2**31 - 1, dtype=jnp.int32)
    kernel = functools.partial(_quant_kernel, bits=bits, symmetric=symmetric,
                               stochastic=stochastic)
    q, scale, offset = pl.pallas_call(
        kernel,
        grid=(groups // rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((rows, gsize), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, gsize), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2.shape, jnp.int8),
            jax.ShapeDtypeStruct((groups, 1), jnp.float32),
            jax.ShapeDtypeStruct((groups, 1), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(seed, x2)
    return q, scale[:, 0], offset[:, 0]


def dequantize(codes: jnp.ndarray, scale: jnp.ndarray,
               offset: Optional[jnp.ndarray] = None,
               dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of :func:`quantize`; [groups, n] codes → [groups, n] values."""
    out = codes.astype(jnp.float32) * scale[:, None]
    if offset is not None:
        out = out + offset[:, None]
    return out.astype(dtype)


def fake_quantize(x: jnp.ndarray, groups: int = 1, bits: int = 8,
                  symmetric: bool = True, stochastic: bool = False,
                  key: Optional[jax.Array] = None) -> jnp.ndarray:
    """Quantize→dequantize round trip (the reference's ``fake_quantizer.cu``)
    for quantize-aware training; straight-through gradient."""
    shape = x.shape

    @jax.custom_vjp
    def _fq(x):
        q, s, o = quantize(x, groups, bits, symmetric, stochastic, key)
        return dequantize(q, s, o if not symmetric else None,
                          dtype=x.dtype).reshape(shape)

    _fq.defvjp(lambda x: (_fq(x), None), lambda _, g: (g,))
    return _fq(x)
