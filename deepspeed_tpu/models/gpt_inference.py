"""KV-cached inference applys for the GPT family.

Counterpart of the reference's ``DeepSpeedTransformerInference``
(``model_implementations/transformers/ds_transformer.py:17``) and its
``softmax_context`` KV-cache attention
(``csrc/transformer/inference/csrc/pt_binding.cpp``): prefill runs the
training forward while recording K/V; decode advances one token against the
cache.  Both are pure functions over (params, cache) so the whole generate
loop jits into a single XLA program — the role CUDA-graph capture plays in
the reference (``inference/engine.py:464``), played instead by jit tracing.

Architecture variants ride the shared ``models/gpt.py`` helpers, so every
injected family (GPT-2 learned positions, OPT relu+offset, BLOOM alibi,
NeoX rotary + parallel residual, untied heads) decodes through this one
implementation — and so does GPT-MoE (``gpt_moe_inference``), which brings
only its own scan step: the cache class, the layer scan and the slot ops
below are the one cache family of the tree.

Cache layout [L, B, S_max, H*D]: static shapes (XLA requirement), masked by
the current length; decode attention reads the cache tiled over S_max with
positions beyond ``pos`` masked.  A token's heads are folded into ONE row
because the layout is the point: the TPU lays a bf16 array whose last
dimension is 64 (half a lane row) out with the long token dimension on the
lanes, and a [.., S_max, H, D] pool can then take no token's K/V in place —
the compiler re-lays a whole layer around every write and every kernel
call (PERF.md, PR 25).  With H*D last the stored order IS row-major: a
token's K (all heads) is one row, written in place at ``[layer, slot,
pos]``, and the decode kernel's ``[block_k, H*D]`` blocks are read straight
out of the pool.  The layer scan carries the stacked banks (never slices a
layer out), and a caller that donates the cache (the batcher's tick) gets
it updated in place.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import gpt

PyTree = Any


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KVCache:
    """``k_scale``/``v_scale`` are ``None`` for a full-precision cache; for
    an int8 cache (``kv_cache_dtype: "int8"``) k/v hold codes and the
    scales are per-vector fp32 [L, B, S_max, H] — half the cache HBM,
    dequantized inside the decode kernel's VMEM stream."""

    k: jnp.ndarray        # [L, B, S_max, H*D]
    v: jnp.ndarray        # [L, B, S_max, H*D]
    length: jnp.ndarray   # [] int32 — tokens already cached
    k_scale: Any = None
    v_scale: Any = None

    def tree_flatten(self):
        return (self.k, self.v, self.length, self.k_scale, self.v_scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def batch(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    @property
    def int8(self) -> bool:
        return self.k_scale is not None


def init_cache(config: gpt.GPTConfig, batch: int, max_len: int,
               kv_dtype=None) -> KVCache:
    """``kv_dtype``: None → cache in the compute dtype; ``"int8"``/
    ``jnp.int8`` → int8 codes + per-vector fp32 scales (beyond-reference:
    halves decode HBM traffic and doubles the context/batch a chip's
    cache budget holds)."""
    shape = (config.n_layer, batch, max_len, config.n_head * config.head_dim)
    if kv_dtype in ("int8", jnp.int8):
        scales = shape[:-1] + (config.n_head,)
        return KVCache(k=jnp.zeros(shape, jnp.int8),
                       v=jnp.zeros(shape, jnp.int8),
                       length=jnp.zeros((), jnp.int32),
                       k_scale=jnp.zeros(scales, jnp.float32),
                       v_scale=jnp.zeros(scales, jnp.float32))
    return KVCache(k=jnp.zeros(shape, config.dtype),
                   v=jnp.zeros(shape, config.dtype),
                   length=jnp.zeros((), jnp.int32))


def _cached_attention(q, cache_k, cache_v, pos, config: gpt.GPTConfig,
                      window=None, k_scale=None, v_scale=None, layer=None,
                      active=None, sweep=None):
    """q: [B, S_q, H, D] attending to cache[:, :pos+S_q]; with ``layer``
    the cache operands are the stacked [L, B, S_max, H*D] pool and the
    decode kernel reads that layer in place.  ``active`` / ``sweep``
    (single-token decode only): the live rows and the kernel's work list
    built from them, see ``cached_attention``.

    ``pos`` is the number of tokens already in the cache before this call;
    query i sits at absolute position pos+i and sees cache slots ≤ pos+i.
    ``window`` (traced per-layer scalar) bands visibility; with
    ``pos_embed == "alibi"`` the per-head ``-slope·dist`` bias is added.
    Both now ride the streaming kernels (window-skipping cache blocks /
    biasing in VMEM) with the dense reference as the non-tiling fallback
    — so an int8 cache (``k_scale``/``v_scale``) composes with
    alibi/windowed models and still dequantizes block-by-block in VMEM.
    """
    from ..ops.pallas.decode_attention import cached_attention
    scale = config.attn_softmax_scale
    slopes = None
    if config.pos_embed == "alibi":
        # train/prefill's _alibi_attention fixes the scale at 1/sqrt(D)
        # (gpt.py) — decode must agree or generation diverges from the
        # cache the prefill filled
        scale = None
        if window is None:
            # banded layers in train/prefill run _windowed_attention,
            # which carries NO alibi bias — window takes precedence here
            # too, for the same prefill/decode consistency
            slopes = gpt.alibi_slopes(config.n_head)
    if scale is None:
        scale = 1.0 / math.sqrt(config.head_dim)
    return cached_attention(q, cache_k, cache_v, pos, sm_scale=scale,
                            k_scale=k_scale, v_scale=v_scale,
                            window=window, slopes=slopes, layer=layer,
                            active=active, sweep=sweep)


def _block_tail(x, attn, p, config: gpt.GPTConfig):
    """Post-attention half of the block, honouring parallel_residual."""
    attn_out = gpt.attn_project(attn, p, config)
    if config.parallel_residual:
        return x + attn_out + gpt.mlp_out(x, p, config)
    return gpt.mlp_residual(x + attn_out, p, config)


def dense_step(params: PyTree, config: gpt.GPTConfig):
    """The dense stack's half of :func:`_layer_scan`: ``(stacks, body)``.
    One scan step is one block: attention at layer ``i``, then its tail."""
    def body(x, p, i, attend, banks):
        a, banks = attend(x, p, i, banks)
        return _block_tail(x, a, p, config), banks

    return params["blocks"], body


def _layer_scan(x, params, cache: KVCache, config: gpt.GPTConfig, positions,
                write, attn, step=dense_step):
    """The one layer-stack scan every cache-filling path shares.

    The stacked banks ride the scan's CARRY, never its ``xs``/``ys``: no
    layer is sliced out of the pool and none is written back into a
    second stack.  ``write(bank, layer, val)`` places this step's K/V (or
    scale) column(s) into layer ``layer`` of the stacked bank, in place;
    int8 caches quantize per vector first and write codes + scales through
    the same ``write``.  ``attn(q, k, v, ck, cv, ksc, vsc, layer)``
    computes the sublayer's attention (prefill reads the fresh unpadded
    k/v; extend/decode read layer ``layer`` of the updated stacks ``ck``/
    ``cv`` where it lies).

    ``step(params, config)`` is the model family's: the parameter stacks
    the scan walks and ``body(x, p, i, attend, banks) -> (x, banks)``, what
    scan step ``i`` does with ``attend(x, p, layer, banks) -> (attention
    output, banks)`` (:func:`dense_step`: one block a step; GPT-MoE: a
    dense and an expert block, layers ``2i`` and ``2i+1`` of the same
    pool).  Returns (hidden states, updated KVCache with the
    caller-provided ``length``-less fields filled in).
    """
    int8 = cache.int8
    if int8:
        from ..ops.pallas.decode_attention import quantize_kv

    def fold(t):
        """[B, S, H, *] → [B, S, H * *]: a token's heads as one row."""
        return t.reshape(t.shape[:2] + (-1,))

    def attend(x, p, idx, banks):
        ck, cv, ksc, vsc = banks
        q, k, v = gpt.qkv_proj(x, p, config, positions=positions)
        # the scopes name, in a profiler's trace, the two places a tick
        # touches the slot cache
        with jax.named_scope("cache_update"):
            if int8:
                kq, ks = quantize_kv(k)
                vq, vs = quantize_kv(v)
                ck, cv = write(ck, idx, fold(kq)), write(cv, idx, fold(vq))
                ksc = write(ksc, idx, fold(ks))
                vsc = write(vsc, idx, fold(vs))
            else:
                ck = write(ck, idx, fold(k.astype(ck.dtype)))
                cv = write(cv, idx, fold(v.astype(cv.dtype)))
        with jax.named_scope("cache_read"):
            a = attn(q, k, v, ck, cv, ksc, vsc, idx)
        return a, (ck, cv, ksc, vsc)

    stacks, body = step(params, config)

    def layer(carry, xs):
        (x, banks), (p, i) = carry, xs
        return body(x, p, i, attend, banks), None

    n_steps = jax.tree_util.tree_leaves(stacks)[0].shape[0]
    (x, (new_k, new_v, new_ksc, new_vsc)), _ = lax.scan(
        layer, (x, (cache.k, cache.v, cache.k_scale, cache.v_scale)),
        (stacks, jnp.arange(n_steps)))
    return x, dataclasses.replace(cache, k=new_k, v=new_v,
                                  k_scale=new_ksc, v_scale=new_vsc)


def prefill(params: PyTree, tokens: jnp.ndarray, config: gpt.GPTConfig,
            cache: KVCache, step=dense_step) -> Tuple[jnp.ndarray, KVCache]:
    """Run the prompt through the model, filling cache[0:S].

    Returns (logits [B, S, padded_vocab] fp32, cache).  Assumes an empty
    cache (length 0) — chunked prefill composes by calling with growing
    ``cache.length`` via :func:`extend`.  ``step`` (here, in ``extend``
    and in ``decode_step``) is the model family's scan step, see
    :func:`_layer_scan`.
    """
    B, S = tokens.shape
    positions = jnp.arange(S)
    x = gpt.embed(params, tokens, config, positions=positions)

    def write(bank, layer, val):
        return lax.dynamic_update_slice(bank, val[None], (layer, 0, 0, 0))

    def attn(q, k, v, ck, cv, ksc, vsc, idx):
        # prefill attention runs on the unpadded k/v (training flash path);
        # only decode reads back through the padded cache
        return gpt._attention(q, k, v, config,
                              window=gpt.layer_window(config, idx, S))

    x, cache = _layer_scan(x, params, cache, config, positions, write, attn,
                           step)
    logits = gpt.lm_logits(params, x, config)
    return logits, dataclasses.replace(cache,
                                       length=jnp.asarray(S, jnp.int32))


def extend(params: PyTree, tokens: jnp.ndarray, config: gpt.GPTConfig,
           cache: KVCache, lengths=None,
           step=dense_step) -> Tuple[jnp.ndarray, KVCache]:
    """Chunked prefill: append ``tokens`` [B, S_c] at positions
    ``cache.length .. cache.length+S_c-1``, attending causally over the
    cached prefix + the chunk.

    Composes: ``prefill(p, t[:, :c]) ; extend(p, t[:, c:])`` equals one
    full ``prefill`` (same logits for the appended chunk, same cache) —
    long prompts process in bounded-activation chunks, and a multi-turn
    server appends each new turn to the session's existing cache instead
    of re-prefilling the whole conversation.  Works on fp and int8
    caches (the chunk path reads the cache densely, dequantizing when
    int8).

    Returns (logits [B, S_c, padded_vocab] fp32, cache advanced by S_c).

    Overflow: appending past ``max_len`` is checked eagerly (host call
    with a concrete ``cache.length``); under an outer jit the length is
    traced and the caller must size the cache — a clamped write would
    silently corrupt the cached prefix.

    ``lengths`` [B] makes the chunk RAGGED (batched speculative verify:
    each row's S_c tokens sit at ITS frontier): row b's chunk lands at
    slots ``lengths[b] .. lengths[b]+S_c-1`` and attends through its own
    live prefix; ``cache.length`` advances to ``max(lengths) + S_c`` and
    the caller tracks per-row lengths.
    """
    B, Sc = tokens.shape
    ragged = lengths is not None
    pos0 = lengths if ragged else cache.length
    if not isinstance(pos0, jax.core.Tracer) and \
            int(jnp.max(pos0)) + Sc > cache.max_len:
        raise ValueError(
            f"extend of {Sc} tokens at length {int(jnp.max(pos0))} "
            f"overflows the cache (max_len {cache.max_len}); the write "
            "would clamp and corrupt the cached prefix")
    if ragged:
        positions = pos0[:, None] + jnp.arange(Sc)          # [B, S_c]
        rows = jnp.arange(B)[:, None]
        cols = positions

        def write(bank, layer, val):
            return bank.at[layer, rows, cols].set(val)
    else:
        positions = pos0 + jnp.arange(Sc)   # [S_c], shared across rows

        def write(bank, layer, val):
            return lax.dynamic_update_slice(bank, val[None],
                                            (layer, 0, pos0, 0))

    x = gpt.embed(params, tokens, config, positions=positions)

    def attn(q, k, v, ck, cv, ksc, vsc, idx):
        return _cached_attention(
            q, ck, cv, pos0, config,
            window=gpt.layer_window(config, idx, cache.max_len),
            k_scale=ksc, v_scale=vsc, layer=idx)

    x, cache = _layer_scan(x, params, cache, config, positions, write, attn,
                           step)
    logits = gpt.lm_logits(params, x, config)
    return logits, dataclasses.replace(cache,
                                       length=jnp.max(pos0) + Sc)


# ------------------------------------------------------------- slot ops
#
# A continuous-batching server owns ONE fixed-geometry multi-slot cache and
# retires/admits conversations per ROW without touching the others.  These
# three ops are that contract: ``row`` may be a traced scalar, so one
# compiled program serves every slot — admitting into slot 7 never
# recompiles the program that admitted into slot 2.


def write_slot(cache: KVCache, row, src: KVCache) -> KVCache:
    """Insert a batch-1 cache into slot ``row`` of a live multi-slot cache
    (admission: a newly prefilled prompt lands in a slot freed by a
    finished generation).  ``src`` must share the cache dtype layout;
    its ``max_len`` must not exceed the slot cache's.  ``length`` keeps
    max-frontier semantics — the slot engine tracks per-row lengths
    itself."""
    if src.int8 != cache.int8:
        raise ValueError(
            f"write_slot dtype mismatch: src int8={src.int8}, "
            f"cache int8={cache.int8}")
    if src.max_len > cache.max_len:
        raise ValueError(
            f"write_slot src max_len {src.max_len} exceeds the slot "
            f"cache's {cache.max_len}")

    def ins(dst, s):
        return lax.dynamic_update_slice(dst, s, (0, row, 0, 0))

    return dataclasses.replace(
        cache, k=ins(cache.k, src.k), v=ins(cache.v, src.v),
        length=jnp.maximum(cache.length, src.length),
        k_scale=ins(cache.k_scale, src.k_scale) if cache.int8 else None,
        v_scale=ins(cache.v_scale, src.v_scale) if cache.int8 else None)


def reset_slot(cache: KVCache, row) -> KVCache:
    """Zero slot ``row``'s K/V (and scales): a retired conversation's
    K/V never bleeds into the next tenant, even through a masked read."""
    def z(buf):
        blank = jnp.zeros((buf.shape[0], 1) + buf.shape[2:], buf.dtype)
        return lax.dynamic_update_slice(buf, blank, (0, row, 0, 0))

    return dataclasses.replace(
        cache, k=z(cache.k), v=z(cache.v),
        k_scale=z(cache.k_scale) if cache.int8 else None,
        v_scale=z(cache.v_scale) if cache.int8 else None)


def read_slot(cache: KVCache, row, length=None) -> KVCache:
    """Slot ``row`` as a batch-1 cache (retiring a live conversation back
    to a session).  ``length`` is the row's true frontier (the multi-slot
    ``cache.length`` only tracks the max)."""
    def rd(buf):
        return lax.dynamic_slice(buf, (0, row, 0, 0),
                                 (buf.shape[0], 1) + buf.shape[2:])

    return KVCache(
        k=rd(cache.k), v=rd(cache.v),
        length=jnp.asarray(length if length is not None else cache.length,
                           jnp.int32),
        k_scale=rd(cache.k_scale) if cache.int8 else None,
        v_scale=rd(cache.v_scale) if cache.int8 else None)


def _layer_sweeps(pos, B, config: gpt.GPTConfig, max_len, active):
    """The decode kernel's work list (``decode_sweep``) for every layer,
    built ONCE, before the layer scan: a function of the step's ``pos`` and
    ``active`` alone, and, in a banded stack, of each layer's window (all
    layers' lists in one vectorised build).  Returns ``idx -> sweep``."""
    from ..ops.pallas.decode_attention import decode_block_k, decode_sweep
    block_k = decode_block_k(max_len, config.n_head * config.head_dim)
    if config.local_attention_window <= 0:
        sweep = decode_sweep(pos, B, max_len, block_k, active)
        return lambda idx: sweep
    windows = gpt.layer_window(config, jnp.arange(config.n_layer), max_len)
    sweeps = jax.vmap(
        lambda w: decode_sweep(pos, B, max_len, block_k, active, w))(windows)
    return lambda idx: jax.tree_util.tree_map(lambda a: a[idx], sweeps)


def decode_step(params: PyTree, token: jnp.ndarray, config: gpt.GPTConfig,
                cache: KVCache, lengths=None, active=None,
                step=dense_step) -> Tuple[jnp.ndarray, KVCache]:
    """One-token decode: token [B] int32 at position cache.length — or,
    with ``lengths`` [B], at per-row positions (ragged right-padded
    prompts: each row's token lands on ITS next slot and sees only ITS
    live prefix; pad-slot K/V is overwritten as rows catch up).

    ``active`` [B] bool (default: every row) names the live rows of a slot
    batch.  A dead row's attention is not computed — the kernel neither
    steps nor streams for it — so its logits are junk; its token is still
    written to its own cell, as every row's is.

    Returns (logits [B, padded_vocab] fp32, cache advanced by one).
    """
    B = token.shape[0]
    ragged = lengths is not None
    pos = lengths if ragged else cache.length
    positions = pos[:, None] if ragged else pos[None]
    x = gpt.embed(params, token[:, None], config, positions=positions)
    sweep_of = _layer_sweeps(pos, B, config, cache.max_len, active)

    def write(bank, layer, val):
        """One new [B, 1, H * *] row per slot at [layer, :, pos] (pos
        shared or per-row)."""
        if ragged:
            return bank.at[layer, jnp.arange(B), pos].set(val[:, 0])
        return lax.dynamic_update_slice(bank, val[None], (layer, 0, pos, 0))

    def attn(q, k, v, ck, cv, ksc, vsc, idx):
        return _cached_attention(
            q, ck, cv, pos, config,
            window=gpt.layer_window(config, idx, cache.max_len),
            k_scale=ksc, v_scale=vsc, layer=idx, active=active,
            sweep=sweep_of(idx))

    x, cache = _layer_scan(x, params, cache, config, positions, write, attn,
                           step)
    logits = gpt.lm_logits(params, x[:, 0], config)
    new_len = (jnp.max(pos) + 1) if ragged else pos + 1
    return logits, dataclasses.replace(cache, length=new_len)
