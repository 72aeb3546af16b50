"""The single-token decode sweep as PR 25 left it, kept as the yardstick of
the flat sweep that replaced it: grid ``(B, Smax/block_k)``, one step for
every block of every row, a dead block's index clamped into the row's live
range and its compute skipped.  For a live row the flat sweep visits the same
blocks in the same order, so the two agree bit for bit
(``tests/unit/ops/test_decode_sweep.py``).  Test code only."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops.pallas.decode_attention import (M_FLOOR, NEG_INF,
                                                       _to_compute,
                                                       _unpack_rest)
from deepspeed_tpu.ops.pallas.utils import interpret_mode


def _dense_grid_kernel(pos_ref, layer_ref, *rest, sm_scale, block_k, H, D,
                   quantized, windowed, alibi):
    """PR 25's kernel body: grid step ``(row, block)``, dead blocks skipped."""
    (window_ref, slopes_ref, q_ref, k_ref, v_ref, kscale_ref, vscale_ref,
     o_ref, acc_ref, m_ref, l_ref) = _unpack_rest(rest, quantized,
                                                  windowed, alibi)
    ki = pl.program_id(1)
    nk = pl.num_programs(1)
    pos = pos_ref[pl.program_id(0)]  # per-ROW visibility (ragged decode)

    def own():
        """(H, H*D) mask: lane c of row h belongs to head h."""
        return jax.lax.broadcasted_iota(jnp.int32, (H, H * D), 1) // D == \
            jax.lax.broadcasted_iota(jnp.int32, (H, H * D), 0)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, M_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)

    live = ki * block_k <= pos
    if windowed:
        # skip blocks wholly below the band [pos-window+1, pos]
        live = jnp.logical_and(
            live, (ki + 1) * block_k - 1 >= pos - window_ref[0] + 1)

    @pl.when(live)
    def _update():
        q = q_ref[...]                                     # (1, H*D)
        qx = jnp.where(own(), q.astype(jnp.float32), 0.0).astype(q.dtype)
        ks = _to_compute(k_ref[...], q.dtype)              # (BK, H*D)
        vs = _to_compute(v_ref[...], q.dtype)
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
        s = jnp.where(visible, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_ref[...] = m_new
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        if quantized:
            p = p * vscale_ref[...].T
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(vs.dtype), vs, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        o = jnp.where(own(), acc_ref[...] / l_ref[...], 0.0)
        o_ref[...] = jnp.sum(o, axis=0, keepdims=True).astype(o_ref.dtype)


def dense_grid_decode(q, k, v, layer, pos, sm_scale, block_k, H, ks=None, vs=None,
            window=None, slopes=None):
    """PR 25's build: grid ``(B, Smax/block_k)``, dead block indices clamped
    into the row's live range ``[band start, causal frontier]``."""
    B, _, HD = q.shape
    Smax = k.shape[2]
    quantized = ks is not None
    windowed = window is not None
    kernel = functools.partial(_dense_grid_kernel, sm_scale=sm_scale,
                               block_k=block_k, H=H, D=HD // H,
                               quantized=quantized, windowed=windowed,
                               alibi=slopes is not None)

    def kv_idx(b, ki, pos_ref, layer_ref, *maybe_win):
        p = pos_ref[b]
        lo = jnp.maximum((p - maybe_win[0][0] + 1) // block_k, 0) \
            if windowed else 0
        return (layer_ref[0], b, jnp.clip(ki, lo, p // block_k), 0)

    kv_spec = pl.BlockSpec((None, None, block_k, HD), kv_idx)
    scale_spec = pl.BlockSpec((None, None, block_k, H), kv_idx)
    row_spec = pl.BlockSpec((None, 1, HD), lambda b, ki, *_: (b, 0, 0))
    slope_specs = [pl.BlockSpec((H, 1), lambda b, ki, *_: (0, 0))] \
        if slopes is not None else []
    slope_args = (jnp.asarray(slopes, jnp.float32).reshape(H, 1),) \
        if slopes is not None else ()
    pos_arr = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    prefetch = (pos_arr, jnp.asarray(layer, jnp.int32).reshape(1)) + \
        ((jnp.asarray(window, jnp.int32).reshape(1),) if windowed else ())
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # pos, layer [, window]
        grid=(B, Smax // block_k),
        in_specs=slope_specs + [row_spec, kv_spec, kv_spec]
        + ([scale_spec, scale_spec] if quantized else []),
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((H, HD), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
        ],
    )
    # prefetch refs arrive in arg order — [pos, layer, window?] then
    # slopes? — matching _unpack_rest's ordering contract
    args = prefetch + slope_args + (q, k, v) + \
        ((ks, vs) if quantized else ())
    return pl.pallas_call(kernel, grid_spec=grid_spec,
                          out_shape=jax.ShapeDtypeStruct((B, 1, HD), q.dtype),
                          interpret=interpret_mode(),
                          name="decode_attention")(*args)
