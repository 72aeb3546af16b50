"""KV-cached inference for the GPT-MoE family.

Counterpart of the reference's MoE inference stack
(``ops/transformer/inference/moe_inference.py`` ``DeepSpeedMoEInference``
and the expert-group creation in ``inference/engine.py:190``): prefill and
single-token decode over the (dense, MoE) pair stack, with the gate running
in eval mode (dropless — see ``_moe_infer_obj``; no RTS/aux loss) and experts sharded
over the ``expert`` mesh axis declaratively — the all-to-all the reference
issues by hand falls out of XLA's dispatch/combine einsums.

Cache layout: two [n_pairs, B, S_max, H, D] banks (dense layers, MoE
layers) scanned together with the parameter pairs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import gpt
from .gpt_moe import GPTMoEConfig, _moe_obj

PyTree = Any


def _moe_infer_obj(config: GPTMoEConfig):
    """Dropless gate for serving: eval capacity gating can mask tokens
    when routing skews (capacity = max(int(t·k·cf/E), min_capacity)),
    which at inference silently corrupts served logits and — because
    capacity depends on the per-call token count — makes a K+1-token
    verify chunk route differently from K+1 single-token decodes.  The
    inference family therefore reserves worst-case capacity (= tokens per
    call; calls are small chunks, so the [t,E,C=t] dispatch stays cheap),
    making decode/extend/prefill exact and mutually consistent — the
    contract speculative verification rides.  Training/eval ``apply``
    keeps capacity gating for throughput, as the reference does
    (sharded_moe.py:278)."""
    return _moe_obj(config, drop_tokens=False)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class MoEKVCache:
    """Scale banks are ``None`` for a full-precision cache; for int8
    (``kv_cache_dtype: "int8"``) the k/v banks hold codes and the scales
    are per-vector fp32 [P, B, S_max, H, 1] — same layout contract as the
    dense family's :class:`gpt_inference.KVCache`."""

    dense_k: jnp.ndarray   # [P, B, S_max, H, D]
    dense_v: jnp.ndarray
    moe_k: jnp.ndarray
    moe_v: jnp.ndarray
    length: jnp.ndarray    # [] int32
    dense_k_scale: Any = None
    dense_v_scale: Any = None
    moe_k_scale: Any = None
    moe_v_scale: Any = None

    def tree_flatten(self):
        return (self.dense_k, self.dense_v, self.moe_k, self.moe_v,
                self.length, self.dense_k_scale, self.dense_v_scale,
                self.moe_k_scale, self.moe_v_scale), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def batch(self) -> int:
        return self.dense_k.shape[1]

    @property
    def max_len(self) -> int:
        return self.dense_k.shape[2]

    @property
    def int8(self) -> bool:
        return self.dense_k_scale is not None


def init_cache(config: GPTMoEConfig, batch: int, max_len: int,
               kv_dtype=None) -> MoEKVCache:
    shape = (config.n_pairs, batch, max_len, config.n_head, config.head_dim)
    if kv_dtype in ("int8", jnp.int8):
        zc = lambda: jnp.zeros(shape, jnp.int8)
        zs = lambda: jnp.zeros(shape[:-1] + (1,), jnp.float32)
        return MoEKVCache(dense_k=zc(), dense_v=zc(), moe_k=zc(),
                          moe_v=zc(), length=jnp.zeros((), jnp.int32),
                          dense_k_scale=zs(), dense_v_scale=zs(),
                          moe_k_scale=zs(), moe_v_scale=zs())
    if kv_dtype is not None:
        raise ValueError(f"unsupported MoE kv_dtype {kv_dtype!r}")
    z = lambda: jnp.zeros(shape, config.dtype)
    return MoEKVCache(dense_k=z(), dense_v=z(), moe_k=z(), moe_v=z(),
                      length=jnp.zeros((), jnp.int32))


def _moe_ffn(x, attn_p, moe_p, moe, config: GPTMoEConfig):
    """Post-attention expert FFN half (eval gating)."""
    h2 = gpt._layer_norm(x, attn_p["ln2_scale"], attn_p["ln2_bias"])
    moe_out, _aux, _counts = moe.apply(moe_p, h2, train=False, constrain=None)
    return x + moe_out


def _attend_prefill(x, p, config, positions):
    q, k, v = gpt.qkv_proj(x, p, config, positions=positions)
    attn = gpt._attention(q, k, v, config)
    return x + gpt.attn_project(attn, p, config), k, v


def _append_kv(ck, cv, ksc, vsc, k, v, pos, ragged=False):
    """Append fresh K/V at ``pos`` — THE quantize-on-append contract:
    with scale banks (int8 cache) each head vector quantizes per vector
    and codes + scales write together; without, the values land in the
    cache dtype.  Shared by prefill and the decode/extend path so the
    two can never diverge.  ``ragged``: pos is [B] and each row's S_c
    new columns land at ITS frontier (dense-family ragged contract —
    single-token decode and the batched speculative verify chunk are the
    S_c = 1 and S_c = K+1 cases of the same write)."""
    if ragged:
        B, Sc = k.shape[:2]
        rows = jnp.arange(B)[:, None]
        cols = pos[:, None] + jnp.arange(Sc)[None]

        def wr(buf, val):
            return buf.at[rows, cols].set(val)
    else:
        wr = lambda buf, val: lax.dynamic_update_slice(buf, val,
                                                       (0, pos, 0, 0))
    if ksc is not None:
        from ..ops.pallas.decode_attention import quantize_kv
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        return wr(ck, kq), wr(cv, vq), wr(ksc, ks), wr(vsc, vs)
    return wr(ck, k.astype(ck.dtype)), wr(cv, v.astype(cv.dtype)), None, None


def _attend_decode(x, p, config, ck, cv, pos, positions, ksc=None,
                   vsc=None, ragged=False, active=None):
    """Cache-append + cached attention for one sublayer; int8 caches
    dequantize inside the kernel's VMEM stream (dense-family contract).
    ``ragged``: pos is [B] — per-row append and per-row visibility.
    ``active``: the live rows of a one-token step (``cached_attention``)."""
    from .gpt_inference import _cached_attention
    q, k, v = gpt.qkv_proj(x, p, config, positions=positions)
    ck, cv, ksc, vsc = _append_kv(ck, cv, ksc, vsc, k, v, pos,
                                  ragged=ragged)
    attn = _cached_attention(q, ck, cv, pos, config, k_scale=ksc,
                             v_scale=vsc, active=active)
    return x + gpt.attn_project(attn, p, config), ck, cv, ksc, vsc


# dropless gating reserves capacity = tokens-per-call, so the dispatch/
# combine tensors are [t, E, t] — fine for decode/verify chunks, quadratic
# for a whole long prompt.  Prefill therefore processes at most this many
# tokens per gate call, walking longer prompts through `extend` (which
# composes exactly with prefill — tested contract).
_PREFILL_CHUNK = 128


def prefill(params: PyTree, tokens: jnp.ndarray, config: GPTMoEConfig,
            cache: MoEKVCache) -> Tuple[jnp.ndarray, MoEKVCache]:
    """Prompt pass filling both cache banks; returns (logits, cache).

    Long prompts (> ``_PREFILL_CHUNK`` gated tokens) run as a chain of
    ``extend`` chunks to keep the dropless dispatch tensors bounded at
    [B·chunk, E, B·chunk] instead of [B·S, E, B·S]."""
    B, S = tokens.shape
    if B * S > _PREFILL_CHUNK:
        # chunk bounds depend only on the static shape, so this also
        # unrolls under an outer jit (the engine's whole-generate program)
        step = max(_PREFILL_CHUNK // B, 1)
        outs = []
        for s0 in range(0, S, step):
            lg, cache = extend(params, tokens[:, s0:s0 + step], config,
                               cache)
            outs.append(lg)
        return jnp.concatenate(outs, axis=1), cache
    positions = jnp.arange(S)
    moe = _moe_infer_obj(config)
    x = gpt.embed(params, tokens, config, positions=positions)

    def pair(x, xs):
        dense_p, attn_p, moe_p, dck, dcv, mck, mcv, dks, dvs, mks, mvs = xs
        x, k, v = _attend_prefill(x, dense_p, config, positions)
        dck, dcv, dks, dvs = _append_kv(dck, dcv, dks, dvs, k, v, 0)
        x = gpt.mlp_residual(x, dense_p, config)
        x, k, v = _attend_prefill(x, attn_p, config, positions)
        mck, mcv, mks, mvs = _append_kv(mck, mcv, mks, mvs, k, v, 0)
        x = _moe_ffn(x, attn_p, moe_p, moe, config)
        return x, (dck, dcv, mck, mcv, dks, dvs, mks, mvs)

    # scale banks are None for fp caches — lax.scan threads None through
    # xs/ys as an empty pytree, so one scan serves both layouts
    x, (dk, dv, mk, mv, dks, dvs, mks, mvs) = lax.scan(
        pair, x, (params["dense_blocks"], params["moe_attn_blocks"],
                  params["moe_blocks"], cache.dense_k, cache.dense_v,
                  cache.moe_k, cache.moe_v, cache.dense_k_scale,
                  cache.dense_v_scale, cache.moe_k_scale,
                  cache.moe_v_scale))
    logits = gpt.lm_logits(params, x, config)
    return logits, MoEKVCache(
        dense_k=dk, dense_v=dv, moe_k=mk, moe_v=mv,
        length=jnp.asarray(S, jnp.int32),
        dense_k_scale=dks, dense_v_scale=dvs,
        moe_k_scale=mks, moe_v_scale=mvs)


def extend(params: PyTree, tokens: jnp.ndarray, config: GPTMoEConfig,
           cache: MoEKVCache, lengths=None,
           active=None) -> Tuple[jnp.ndarray, MoEKVCache]:
    """Chunked prefill continuation (the MoE counterpart of
    ``gpt_inference.extend``): append ``tokens`` [B, S_c] at positions
    ``cache.length..``, attending causally over prefix + chunk through
    both cache banks, expert FFN in eval gating.  ``prefill(t[:, :c]) ;
    extend(t[:, c:])`` equals one full ``prefill`` — the contract the
    speculative verify pass rides.  ``lengths`` [B] makes the chunk
    RAGGED (batched speculative verify): row b's S_c tokens land at ITS
    frontier with per-row visibility; ``cache.length`` advances to
    ``max(lengths) + S_c`` and the caller tracks per-row lengths.
    ``active`` [B] bool is ``decode_step``'s: the live rows of a one-token
    step, which alone read it."""
    B, Sc = tokens.shape
    ragged = lengths is not None
    pos0 = lengths if ragged else cache.length
    max_len = cache.dense_k.shape[2]
    if not isinstance(pos0, jax.core.Tracer) and \
            int(jnp.max(pos0)) + Sc > max_len:
        raise ValueError(
            f"extend of {Sc} tokens at length {int(jnp.max(pos0))} "
            f"overflows the cache (max_len {max_len}); the write would "
            "clamp and corrupt the cached prefix")
    positions = (pos0[:, None] if ragged else pos0) + jnp.arange(Sc)
    moe = _moe_infer_obj(config)
    x = gpt.embed(params, tokens, config, positions=positions)

    def pair(x, xs):
        dense_p, attn_p, moe_p, dck, dcv, mck, mcv, dks, dvs, mks, mvs = xs
        x, dck, dcv, dks, dvs = _attend_decode(
            x, dense_p, config, dck, dcv, pos0, positions, dks, dvs,
            ragged=ragged, active=active)
        x = gpt.mlp_residual(x, dense_p, config)
        x, mck, mcv, mks, mvs = _attend_decode(
            x, attn_p, config, mck, mcv, pos0, positions, mks, mvs,
            ragged=ragged, active=active)
        x = _moe_ffn(x, attn_p, moe_p, moe, config)
        return x, (dck, dcv, mck, mcv, dks, dvs, mks, mvs)

    # scale banks are None for fp caches (see prefill)
    x, (dk, dv, mk, mv, dks, dvs, mks, mvs) = lax.scan(
        pair, x, (params["dense_blocks"], params["moe_attn_blocks"],
                  params["moe_blocks"], cache.dense_k, cache.dense_v,
                  cache.moe_k, cache.moe_v, cache.dense_k_scale,
                  cache.dense_v_scale, cache.moe_k_scale,
                  cache.moe_v_scale))
    logits = gpt.lm_logits(params, x, config)
    return logits, MoEKVCache(
        dense_k=dk, dense_v=dv, moe_k=mk, moe_v=mv,
        length=jnp.max(pos0) + Sc,
        dense_k_scale=dks, dense_v_scale=dvs,
        moe_k_scale=mks, moe_v_scale=mvs)


# ------------------------------------------------------------- slot ops
#
# Dense-family contract (``gpt_inference.write_slot``/``reset_slot``/
# ``read_slot``) over the dual cache banks: a continuous-batching server
# admits/retires per ROW of one fixed-geometry cache, ``row`` traced so one
# compiled program serves every slot.

_BANKS = ("dense_k", "dense_v", "moe_k", "moe_v")
_SCALES = ("dense_k_scale", "dense_v_scale", "moe_k_scale", "moe_v_scale")


def write_slot(cache: MoEKVCache, row, src: MoEKVCache) -> MoEKVCache:
    """Insert a batch-1 cache into slot ``row`` across both banks."""
    if src.int8 != cache.int8:
        raise ValueError(
            f"write_slot dtype mismatch: src int8={src.int8}, "
            f"cache int8={cache.int8}")
    if src.max_len > cache.max_len:
        raise ValueError(
            f"write_slot src max_len {src.max_len} exceeds the slot "
            f"cache's {cache.max_len}")

    def ins(dst, s):
        return lax.dynamic_update_slice(dst, s, (0, row, 0, 0, 0))

    upd = {name: ins(getattr(cache, name), getattr(src, name))
           for name in _BANKS}
    if cache.int8:
        upd.update({name: ins(getattr(cache, name), getattr(src, name))
                    for name in _SCALES})
    return dataclasses.replace(
        cache, length=jnp.maximum(cache.length, src.length), **upd)


def reset_slot(cache: MoEKVCache, row) -> MoEKVCache:
    """Zero slot ``row`` across both banks (and scale banks when int8)."""
    def z(buf):
        blank = jnp.zeros((buf.shape[0], 1) + buf.shape[2:], buf.dtype)
        return lax.dynamic_update_slice(buf, blank, (0, row, 0, 0, 0))

    upd = {name: z(getattr(cache, name)) for name in _BANKS}
    if cache.int8:
        upd.update({name: z(getattr(cache, name)) for name in _SCALES})
    return dataclasses.replace(cache, **upd)


def read_slot(cache: MoEKVCache, row, length=None) -> MoEKVCache:
    """Slot ``row`` as a batch-1 cache; ``length`` is the row's true
    frontier."""
    def rd(buf):
        return lax.dynamic_slice(buf, (0, row, 0, 0, 0),
                                 (buf.shape[0], 1) + buf.shape[2:])

    upd = {name: rd(getattr(cache, name)) for name in _BANKS}
    if cache.int8:
        upd.update({name: rd(getattr(cache, name)) for name in _SCALES})
    else:
        upd.update({name: None for name in _SCALES})
    return MoEKVCache(
        length=jnp.asarray(length if length is not None else cache.length,
                           jnp.int32), **upd)


def decode_step(params: PyTree, token: jnp.ndarray, config: GPTMoEConfig,
                cache: MoEKVCache, lengths=None,
                active=None) -> Tuple[jnp.ndarray, MoEKVCache]:
    """One-token decode through both banks; token [B] int32 — a 1-token
    ``extend`` with the chunk axis squeezed.  With ``lengths`` [B]
    (ragged right-padded prompts, dense-family contract) each row's
    token lands on ITS next slot and sees only ITS live prefix; dropless
    gating keeps rows independent, so ragged batching cannot perturb a
    row's routing.  ``active`` [B] bool names the live rows of a slot
    batch (dense-family contract): the decode kernel neither steps nor
    streams for a dead row, whose logits are junk."""
    logits, cache = extend(params, token[:, None], config, cache,
                           lengths=lengths, active=active)
    return logits[:, 0], cache
