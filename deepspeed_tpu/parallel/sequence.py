"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference snapshot predates DeepSpeed-Ulysses and has **no** SP/CP
implementation (SURVEY.md §5 "Long-context"); its long-sequence story is
block-sparse attention plus seq-dim token utilities (``moe/mappings.py:27``).
For a TPU-native framework long context is first-class: both designs below
map directly onto ICI.

- **Ring attention** (`ring`): K/V shards rotate around the ``seq`` mesh
  axis via ``lax.ppermute`` while each device holds its query shard fixed,
  accumulating flash-attention-style online softmax statistics in fp32.
  Peak memory per device is O(S_local · S_local) per step instead of the
  O(S²) score matrix; the ppermute ring is exactly one ICI hop per step so
  communication overlaps compute for realistic block sizes.
- **Ulysses** (`ulysses`): one ``all_to_all`` scatters heads and gathers
  sequence ([B, S/sp, H, D] → [B, S, H/sp, D]), local full attention runs
  over the complete sequence on H/sp heads, and a second all_to_all restores
  the layout.  Cheaper than ring for moderate S when H ≥ sp.

Both are written as ``shard_map`` regions so they compose with TP (heads
already sharded over ``model``) and DP (batch over ``data``/``expert``)
inside one jitted train step.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

from .mesh import DATA_AXIS, EXPERT_AXIS, MODEL_AXIS, SEQ_AXIS, get_mesh_manager

NEG_INF = -1e30  # large-negative instead of -inf: keeps grads nan-free


def _sdpa(q, k, v, causal: bool, q_offset=0, k_offset=0):
    """Plain scaled-dot-product attention. q,k,v: [B, Sq, H, D] / [B, Sk, H, D].

    fp32 softmax; ``*_offset`` are global position offsets used for the
    causal mask when q/k are shards of a longer sequence.
    """
    D = q.shape[-1]
    scale = 1.0 / math.sqrt(D)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32)).astype(q.dtype)


# ------------------------------------------------------------------ ring

def _ring_attention_local(q, k, v, *, axis_name: str, sp: int, causal: bool):
    """Per-shard ring attention body (runs under shard_map).

    q, k, v: local shards [B, S_loc, H_loc, D].  Device i starts holding
    K/V chunk i; at ring step t it holds chunk (i - t) mod sp, computes that
    block's contribution with online-softmax accumulation, then passes its
    chunk to device i+1.
    """
    orig_dtype = q.dtype
    B, S, H, D = q.shape
    my = lax.axis_index(axis_name)
    scale = 1.0 / math.sqrt(D)
    qf = q.astype(jnp.float32) * scale

    # mark initial accumulators as device-varying so the scan carry type is
    # stable under shard_map's varying-manual-axes tracking
    vma = tuple(jax.typeof(q).vma)
    if vma:
        pvary = lambda x: lax.pcast(x, vma, to="varying")
    else:
        pvary = lambda x: x
    m0 = pvary(jnp.full((B, H, S), NEG_INF, jnp.float32))
    l0 = pvary(jnp.zeros((B, H, S), jnp.float32))
    o0 = pvary(jnp.zeros((B, S, H, D), jnp.float32))
    perm = [(j, (j + 1) % sp) for j in range(sp)]

    def step(carry, t):
        k_cur, v_cur, m, l, o = carry
        src = (my - t) % sp  # chunk id currently held
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, k_cur.astype(jnp.float32))
        if causal:
            q_pos = my * S + jnp.arange(S)
            k_pos = src * S + jnp.arange(S)
            mask = q_pos[:, None] >= k_pos[None, :]
            scores = jnp.where(mask[None, None], scores, NEG_INF)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.exp(m - m_new)                       # [B,H,S]
        p = jnp.exp(scores - m_new[..., None])           # [B,H,S,S]
        if causal:
            p = jnp.where(mask[None, None], p, 0.0)      # kill NEG_INF leakage
        l_new = l * alpha + p.sum(axis=-1)
        o_new = o * alpha.transpose(0, 2, 1)[..., None] + \
            jnp.einsum("bhqk,bkhd->bqhd", p, v_cur.astype(jnp.float32))
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m_new, l_new, o_new), None

    (k, v, m, l, o), _ = lax.scan(step, (k, v, m0, l0, o0), jnp.arange(sp))
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(orig_dtype)


# --------------------------------------------------------------- ulysses

def _ulysses_attention_local(q, k, v, *, axis_name: str, sp: int, causal: bool):
    """All-to-all head-scatter attention body (runs under shard_map).

    [B, S/sp, H, D] --a2a--> [B, S, H/sp, D] → full local attention →
    --a2a--> [B, S/sp, H, D].
    """
    assert q.shape[2] % sp == 0, (
        f"ulysses needs local heads {q.shape[2]} divisible by sp={sp}")
    a2a = partial(lax.all_to_all, axis_name=axis_name, split_axis=2,
                  concat_axis=1, tiled=True)
    q, k, v = a2a(q), a2a(k), a2a(v)
    out = _sdpa(q, k, v, causal)
    return lax.all_to_all(out, axis_name=axis_name, split_axis=1,
                          concat_axis=2, tiled=True)


# ---------------------------------------------------------------- public

def sp_attention(q, k, v, *, impl: str = "ring", causal: bool = True,
                 mesh: Optional[Mesh] = None,
                 batch_axes=(DATA_AXIS, EXPERT_AXIS),
                 heads_axis: Optional[str] = MODEL_AXIS):
    """Sequence-parallel self-attention over the ``seq`` mesh axis.

    q, k, v: global [B, S, H, D]; batch sharded over ``batch_axes``, S over
    ``seq``, H over ``heads_axis`` (TP).  Falls back to dense attention when
    the mesh has no seq axis.
    """
    if mesh is None:
        mesh = get_mesh_manager().mesh
    sp = mesh.shape.get(SEQ_AXIS, 1)
    if sp == 1:
        return _sdpa(q, k, v, causal)
    if impl == "ring":
        local = partial(_ring_attention_local, axis_name=SEQ_AXIS, sp=sp,
                        causal=causal)
    elif impl == "ulysses":
        local = partial(_ulysses_attention_local, axis_name=SEQ_AXIS, sp=sp,
                        causal=causal)
    else:
        raise ValueError(f"unknown sequence-parallel impl {impl!r}")
    spec = P(batch_axes, SEQ_AXIS, heads_axis, None)
    fn = shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                   out_specs=spec)
    return fn(q, k, v)


def ring_attention(q, k, v, *, causal: bool = True, mesh: Optional[Mesh] = None,
                   **kw):
    return sp_attention(q, k, v, impl="ring", causal=causal, mesh=mesh, **kw)


def ulysses_attention(q, k, v, *, causal: bool = True,
                      mesh: Optional[Mesh] = None, **kw):
    return sp_attention(q, k, v, impl="ulysses", causal=causal, mesh=mesh, **kw)
