"""A latent-attention, routed-expert decoder (the DeepSeek-V3 generation's
block: multi-head latent attention, a few leading dense layers, then layers
of many small routed experts beside a shared one).

What differs from ``models/gpt.py``'s block, mechanism by mechanism:

- **RMSNorm** everywhere, no bias anywhere, an **untied head**; the
  residual stream, a sum over layers, stays float32 (each contribution's
  last product accumulates into it unrounded), every product's inputs are
  ``config.dtype``;
- **latent attention**: queries come through a low-rank bottleneck; keys and
  values of all heads are up-projections of ONE compressed row per token
  (``kv_rank`` elements) plus one rotary key shared by all heads
  (``d_rope``).  That row, ``[c | R(k_r)]``, is all a cache keeps of a
  token.  One algorithm, two forms, and a pass's SHAPE picks
  (``decode_attention.latent_up_projects``).  The *absorbed* form: the key
  up-projection is folded into the query (``q' = W_kvb[k]^T q_n``), scores
  are ``q'.c + R(q_r).R(k_r)`` against the row, the probabilities weigh
  ``c`` itself and the value up-projection follows the sum: nothing to do
  a key, 3.4 times the operations a (query, key) pair.  It is a tick's
  (one query a row: nothing to amortise an up-projection over), a few
  tokens' and the uncached ``apply``'s.  The *up-projected* form: keys and
  values of all heads made from the rows once a call, as the published
  model's own prompt pass makes them: a served chunk's (158 positions and
  more at the published widths), under a bias or under none, inside the
  chunk kernel, through the layer's ``W_kvb`` where it lies in a head-major
  copy of its stack (``head_major``, ``with_up``);
- **YaRN** rotary frequencies on the rotary part alone, pairs interleaved;
  a config may leave the bottleneck out (``q_rank`` None: queries straight
  from the stream) and the rotation (``rope`` False: the row's shared key
  and the queries' part for it are used as projected), as the
  linear-attention family's latent layers do (``linear_latent_moe``);
- **SwiGLU** feed-forwards: a dense one in the first ``first_k_dense``
  layers, then an expert layer (``moe/held_experts.py``): a float32 sigmoid
  router over all ``n_experts``, ``experts_per_token`` of them chosen with a
  selection bias, the pairs that land on the experts held here multiplied
  grouped, and a shared expert beside them.

Layer kinds come from ``first_k_dense``: parameters are two stacks,
``dense_blocks`` and ``moe_blocks``, each scanned.  ``held_experts`` names
the experts this process holds (expert parallelism's share; default all).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..moe.held_experts import held_experts_ffn, pair_counts, route
from .partitioning import EMBED, EXPERT, HEADS, KV, LAYERS, MLP, VOCAB

PyTree = Any


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    #: the module whose ``FAMILY`` ``models.cache_family`` serves this
    #: config through
    cache_family = "latent_moe_inference"

    vocab_size: int = 1024
    max_seq_len: int = 1024
    n_layer: int = 3
    n_head: int = 4
    d_model: int = 64
    d_ff: int = 256                 # the leading dense layers' SwiGLU width
    d_expert: int = 32              # one expert's SwiGLU width
    #: the queries' bottleneck; None: none (``wq`` straight from the stream)
    q_rank: Optional[int] = 48
    kv_rank: int = 32
    d_nope: int = 16
    d_rope: int = 8
    d_v: int = 16
    n_experts: int = 16             # the router's width: every expert there is
    experts_per_token: int = 2
    n_shared_experts: int = 1
    first_k_dense: int = 1
    #: global ids of the experts held here (None: all of them)
    held_experts: Optional[Tuple[int, ...]] = None
    routed_scale: float = 1.0
    norm_topk: bool = True
    eps: float = 1e-5
    rope_theta: float = 10000.0
    #: YaRN: (factor, original positions, beta_fast, beta_slow, mscale,
    #: mscale_all_dim), or None for plain rotary frequencies
    yarn: Optional[Tuple[float, int, float, float, float, float]] = None
    #: False: no rotation at all (the row's last ``d_rope`` elements and the
    #: queries' are used as projected; positions are unused)
    rope: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    vocab_round_to: int = 128

    def __post_init__(self):
        assert 0 <= self.first_k_dense <= self.n_layer
        assert self.d_rope % 2 == 0
        held = self.held
        assert len(set(held)) == len(held) and all(
            0 <= e < self.n_experts for e in held), held

    @property
    def held(self) -> Tuple[int, ...]:
        return tuple(range(self.n_experts)) if self.held_experts is None \
            else tuple(self.held_experts)

    @property
    def n_moe_layers(self) -> int:
        return self.n_layer - self.first_k_dense

    @property
    def padded_vocab(self) -> int:
        r = self.vocab_round_to
        return ((self.vocab_size + r - 1) // r) * r

    @property
    def row_elements(self) -> int:
        """What the mathematics keeps of a cached token in a layer."""
        return self.kv_rank + self.d_rope

    @property
    def cache_row(self) -> Tuple[int, ...]:
        """The cache family's banks for this model: one, the latent row,
        stored in whole lane rows (``row_elements`` rounded up to 128)."""
        return (-(-self.row_elements // 128) * 128,)

    @property
    def softmax_scale(self) -> float:
        scale = 1.0 / math.sqrt(self.d_nope + self.d_rope)
        if self.yarn is not None and self.yarn[5]:
            m = 0.1 * self.yarn[5] * math.log(self.yarn[0]) + 1.0
            scale *= m * m
        return scale


# ------------------------------------------------------------------ pieces

@jax.named_scope("norm")
def rms_norm(x, scale, eps, dtype=None):
    """In float32; the result in ``dtype`` (default: ``x``'s)."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dtype or x.dtype)


def swiglu(h, w_gu, w_down, cdt):
    """``W_d (silu(W_g h) * W_u h)`` with gate and up stored side by side;
    products in ``cdt``, the result float32 (it joins the residual sum)."""
    gu = jnp.einsum("...d,df->...f", h, w_gu.astype(cdt))
    f = gu.shape[-1] // 2
    act = (jax.nn.silu(gu[..., :f].astype(jnp.float32))
           * gu[..., f:].astype(jnp.float32)).astype(cdt)
    return jnp.einsum("...f,fd->...d", act, w_down.astype(cdt),
                      preferred_element_type=jnp.float32)


def yarn_inv_freq(dim: int, theta: float, yarn=None) -> jnp.ndarray:
    """``[dim / 2]`` float32 rotary frequencies ``theta^(-2j/dim)``; under
    YaRN (``yarn``: factor, original positions, beta_fast, beta_slow, ...)
    the fast dims keep theirs, the slow ones are divided by ``factor``, with
    a linear ramp between the correction dims of ``beta_fast`` and
    ``beta_slow``."""
    j = jnp.arange(0, dim, 2, dtype=jnp.float32)
    freq = theta ** (-j / dim)
    if yarn is None:
        return freq
    factor, original, beta_fast, beta_slow = yarn[:4]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    keep = 1.0 - ramp
    return freq / factor * (1.0 - keep) + freq * keep


def rotary_inv_freq(config: LatentMoEConfig) -> jnp.ndarray:
    """``yarn_inv_freq`` of the config's rotary dims."""
    return yarn_inv_freq(config.d_rope, config.rope_theta, config.yarn)


def rotate(x, positions, config: LatentMoEConfig):
    """Rotary embedding on ``x`` [B, S, ..., d_rope] at ``positions`` ([S]
    or [B, S]), pairs interleaved (dims ``2j`` and ``2j+1``), in float32."""
    ang = positions.astype(jnp.float32)[..., None] * rotary_inv_freq(config)
    if ang.ndim == 2:
        ang = ang[None]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., 0::2], x32[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def lora_rescale(d_model: int, rank: int) -> float:
    """What a low-rank latent leaves its norm multiplied by where a config
    asks for it (``mla_scale_q_lora`` / ``mla_scale_kv_lora``):
    ``sqrt(d_model / rank)``."""
    return math.sqrt(d_model / rank)


def lane_rows(n: int) -> int:
    """``n`` elements in whole lane rows of 128."""
    return -(-n // 128) * 128


def head_major(wkv_b, config, dims=None):
    """A stack of ``wkv_b`` ``[n, kv_rank, H, d_nope + d_v]`` as the
    up-projected chunk kernel reads it: ``[n, H, kv_rank, lane_rows(d_nope)
    + d_v]`` in the compute dtype, a head's key part in whole lane rows
    (zero columns past ``d_nope``, which meet zero lanes of the un-absorbed
    queries: a width that is whole already is padded by nothing).  ``dims``:
    the widths of the layer's kind where a config has more than one
    (default: the config's own).  A family's ``step`` makes it ONCE, outside
    its layer scan (a loop over chunks hoists it whole), and hands a layer's
    share to the layer's parameters (``with_up``)."""
    d_nope = (dims or config).d_nope
    w = jnp.swapaxes(wkv_b, 1, 2).astype(config.dtype)
    pad = lane_rows(d_nope) - d_nope
    if not pad:
        return w
    return jnp.concatenate(
        [w[..., :d_nope], jnp.zeros(w.shape[:-1] + (pad,), w.dtype),
         w[..., d_nope:]], -1)


#: where ``with_up`` keeps ``(head-major stack, layer)`` in a layer's
#: parameters: not a matrix of the layer, so no key of any stack
_UP = "wkv_b_heads"


def with_up(p, heads, layer):
    """Layer ``layer``'s parameters ``p`` with the head-major stack
    ``heads`` its ``wkv_b`` lies in: what lets ``latent_project`` and
    ``latent_output`` take the up-projected form for a pass it pays in."""
    return {**p, _UP: (heads, layer)}


def chunk_form(config, S: int) -> str:
    """The form a served pass of ``S`` positions takes at this config's
    widths, by name: ``"up_projected"`` where the positions pay for a key's
    up-projection (``decode_attention.latent_up_projects``, at the widths
    the head-major copy has: ``lane_rows(d_nope)``), else ``"absorbed"``.  A
    family's ``Family.chunk_form``: the batcher records it with an
    admission's work.  ``config``: a config of this family, or the widths of
    one kind of layer under the same names (``sparse_latent_moe.Dims``)."""
    from ..ops.pallas.decode_attention import latent_up_projects
    return "up_projected" if latent_up_projects(
        S, config.n_head, config.cache_row[0], config.kv_rank,
        lane_rows(config.d_nope), config.d_rope, config.d_v) else "absorbed"


def unabsorbed(q_n, q_r):
    """A pass's UN-ABSORBED queries ``[q_n | 0 | R(q_r)]`` from their two
    parts [B, S, H, .], the key part in whole lane rows as ``head_major``
    lays a head's out."""
    pad = lane_rows(q_n.shape[-1]) - q_n.shape[-1]
    return jnp.concatenate(
        [q_n] + ([jnp.zeros(q_n.shape[:-1] + (pad,), q_n.dtype)] if pad
                 else []) + [q_r], -1)


def up_projection(p, config, S: int):
    """``decode_attention.LatentUp`` for a pass of ``S`` positions through
    the layer whose parameters are ``p``, or None where the pass keeps the
    absorbed form: no head-major stack at hand (``apply``), or too few
    positions (``chunk_form``).  ``config``: as ``chunk_form``'s."""
    from ..ops.pallas.decode_attention import LatentUp
    if _UP not in p or chunk_form(config, S) == "absorbed":
        return None
    return LatentUp(*p[_UP], lane_rows(config.d_nope))


def latent_project(x, p, config: LatentMoEConfig, positions,
                   q_scale: float = 1.0, kv_scale: float = 1.0):
    """One layer's attention inputs from ``x`` [B, S, d]: the absorbed
    queries ``[q' | R(q_r)]`` [B, S, H, row] and the token's cache row
    ``[c | R(k_r)]`` [B, S, row], both zero past ``row_elements``.
    ``q_scale`` / ``kv_scale``: what the query latent and the key-value
    latent leave their norms multiplied by (``lora_rescale``), in float32
    before the one rounding; the cached ``c`` is the scaled one, so either
    form reads it as it lies (it scales keys AND values).

    A pass that takes the up-projected form (``up_projection``) gets its
    queries UN-ABSORBED, ``([q_n | R(q_r)] [B, S, H, d_nope + d_rope],
    LatentUp)`` (``unabsorbed``), for ``cached_attention(latent_up=)``."""
    cdt = config.dtype
    H, r = config.n_head, config.kv_rank

    def latent(t, gain, scale):
        if scale == 1.0:
            return rms_norm(t, gain, config.eps)
        return (rms_norm(t, gain, config.eps, jnp.float32) * scale
                ).astype(cdt)
    h = rms_norm(x, p["ln1"], config.eps, cdt)
    if config.q_rank is None:
        q = jnp.einsum("bsd,dhe->bshe", h, p["wq"].astype(cdt))
    else:
        c_q = latent(jnp.einsum("bsd,dr->bsr", h, p["wq_a"].astype(cdt)),
                     p["q_norm"], q_scale)
        q = jnp.einsum("bsr,rhe->bshe", c_q, p["wq_b"].astype(cdt))
    turn = (lambda t: rotate(t, positions, config)) if config.rope \
        else (lambda t: t)
    q_n, q_r = q[..., :config.d_nope], q[..., config.d_nope:]
    kv = jnp.einsum("bsd,dr->bsr", h, p["wkv_a"].astype(cdt))
    c = latent(kv[..., :r], p["kv_norm"], kv_scale)
    k_r = turn(kv[..., r:])
    pad = config.cache_row[0] - config.row_elements
    row = jnp.concatenate(
        [c, k_r] + ([jnp.zeros(c.shape[:2] + (pad,), cdt)] if pad else []),
        -1)
    up = up_projection(p, config, x.shape[1])
    if up is not None:
        return (unabsorbed(q_n, turn(q_r)), up), row
    # absorb the key up-projection into the query
    q_abs = jnp.einsum("bshe,rhe->bshr", q_n,
                       p["wkv_b"][..., :config.d_nope].astype(cdt))
    queries = jnp.concatenate(
        [q_abs, turn(q_r)]
        + ([jnp.zeros(q_abs.shape[:3] + (pad,), cdt)] if pad else []), -1)
    return queries, row


@jax.named_scope("attn_out")
def latent_output(x, weighed, p, config: LatentMoEConfig):
    """``x + W_o concat_h(W_kvb[v] (sum_s p c))``: ``weighed`` [B, S, H,
    kv_rank] is each head's probability-weighted sum of latent rows; from a
    pass in the up-projected form (``up_projection``) it is the heads'
    outputs [B, S, H, d_v] already."""
    cdt = config.dtype
    v = weighed.astype(cdt)
    if up_projection(p, config, x.shape[1]) is None:
        v = jnp.einsum("bshr,rhe->bshe", v,
                       p["wkv_b"][..., config.d_nope:].astype(cdt))
    return x + jnp.einsum("bshe,hed->bsd", v, p["wo"].astype(cdt),
                          preferred_element_type=jnp.float32)


def dense_ffn(x, p, config: LatentMoEConfig):
    h = rms_norm(x, p["ln2"], config.eps, config.dtype)
    with jax.named_scope("dense_ffn"):
        return x + swiglu(h, p["w_gu"], p["w_down"], config.dtype)


def expert_ffn(x, p, config: LatentMoEConfig, experts=None, layer=None):
    """The expert layer's FFN half.  ``p`` is one layer's parameters;
    ``experts`` (with ``layer``, a scan's index) the routed experts'
    matrices as whole stacks ``{"w_gu", "w_down"}`` ``[layers, n_held,
    ...]`` for the grouped product to read in place, where ``p`` does not
    carry one layer of them.  Returns ``(x, counts)`` with ``counts``
    ``[n_pair_counts(n_held)]`` int32: pairs held here, pairs routed, held
    experts that took at least one pair (each streams its matrices once),
    pairs per held expert, pages of pairs run beyond the first, pairs on
    zero-compute experts (none here: 0)."""
    B, S, d = x.shape
    h32 = rms_norm(x, p["ln2"], config.eps, jnp.float32)
    h = h32.astype(config.dtype)
    flat = h.reshape(B * S, d)
    with jax.named_scope("moe_router"):
        # the router reads the norm's own float32 output, not its rounding
        routing = route(h32.reshape(B * S, d), p["router"], p["router_bias"],
                        config.experts_per_token, config.routed_scale,
                        config.norm_topk)
    routed, per_expert = held_experts_ffn(
        flat, routing, experts if experts is not None else p, config.held,
        config.n_experts, layer=layer if experts is not None else None)
    with jax.named_scope("moe_shared"):
        shared = swiglu(h, p["ws_gu"], p["ws_down"], config.dtype)
    counts = pair_counts(per_expert, B * S * config.experts_per_token)
    return x + routed.reshape(B, S, d).astype(jnp.float32) + shared, counts


def embed(params: PyTree, tokens, config: LatentMoEConfig):
    """The residual stream is a sum over layers and is kept in float32;
    every product's inputs are ``config.dtype``."""
    return params["wte"][tokens].astype(jnp.float32)


def lm_logits(params: PyTree, x, config: LatentMoEConfig):
    h = rms_norm(x, params["lnf"], config.eps, config.dtype)
    with jax.named_scope("head"):
        return jnp.einsum("...d,vd->...v", h,
                          params["lm_head"].astype(config.dtype),
                          preferred_element_type=jnp.float32)


# -------------------------------------------------------------------- init

def attention_init(key, config: LatentMoEConfig, n: int, std, out_std):
    """One stack of ``n`` layers' norms and latent-attention matrices (a
    family that mixes these layers with others draws them here too)."""
    d, H = config.d_model, config.n_head
    pdt = config.param_dtype
    k = jax.random.split(key, 5)

    def normal(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(pdt)
    e = config.d_nope + config.d_rope
    queries = {"wq": normal(k[1], (n, d, H, e), std)} \
        if config.q_rank is None else {
            "wq_a": normal(k[0], (n, d, config.q_rank), std),
            "q_norm": jnp.ones((n, config.q_rank), pdt),
            "wq_b": normal(k[1], (n, config.q_rank, H, e), std)}
    return {
        "ln1": jnp.ones((n, d), pdt), "ln2": jnp.ones((n, d), pdt),
        **queries,
        "wkv_a": normal(k[2], (n, d, config.kv_rank + config.d_rope), std),
        "kv_norm": jnp.ones((n, config.kv_rank), pdt),
        "wkv_b": normal(k[3], (n, config.kv_rank, H,
                               config.d_nope + config.d_v), std),
        "wo": normal(k[4], (n, H, config.d_v, d), out_std),
    }


def init(config: LatentMoEConfig, rng: jax.Array, std: float = 0.02,
         routed_out_std: Optional[float] = None) -> PyTree:
    """Seeded weights.  Matrices into a layer draw ``std``, matrices back to
    the residual stream ``std / sqrt(2 L)``; the routed experts' own
    ``routed_out_std`` (default: as the others)."""
    d, v = config.d_model, config.padded_vocab
    pdt = config.param_dtype
    n_d, n_m, E = config.first_k_dense, config.n_moe_layers, len(config.held)
    out_std = std / math.sqrt(2 * config.n_layer)
    if routed_out_std is None:
        routed_out_std = out_std
    k = jax.random.split(rng, 12)

    def normal(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(pdt)
    dense = attention_init(k[0], config, n_d, std, out_std)
    dense["w_gu"] = normal(k[1], (n_d, d, 2 * config.d_ff), std)
    dense["w_down"] = normal(k[2], (n_d, config.d_ff, d), out_std)
    moe = attention_init(k[3], config, n_m, std, out_std)
    f_s = config.d_expert * config.n_shared_experts
    moe.update({
        "router": normal(k[4], (n_m, d, config.n_experts), std),
        # small and not zero, so that the bias is exercised
        "router_bias": normal(k[5], (n_m, config.n_experts), 0.01),
        "w_gu": normal(k[6], (n_m, E, d, 2 * config.d_expert), std),
        "w_down": normal(k[7], (n_m, E, config.d_expert, d), routed_out_std),
        "ws_gu": normal(k[8], (n_m, d, 2 * f_s), std),
        "ws_down": normal(k[9], (n_m, f_s, d), out_std),
    })
    return {"wte": normal(k[10], (v, d), std), "dense_blocks": dense,
            "moe_blocks": moe, "lnf": jnp.ones((d,), pdt),
            "lm_head": normal(k[11], (v, d), std)}


def attention_axes(config) -> PyTree:
    """The logical axes of one stack of ``attention_init``'s."""
    queries = {"wq": (LAYERS, EMBED, HEADS, KV)} \
        if config.q_rank is None else {
            "wq_a": (LAYERS, EMBED, None), "q_norm": (LAYERS, None),
            "wq_b": (LAYERS, None, HEADS, KV)}
    return {
        "ln1": (LAYERS, EMBED), "ln2": (LAYERS, EMBED), **queries,
        "wkv_a": (LAYERS, EMBED, None), "kv_norm": (LAYERS, None),
        "wkv_b": (LAYERS, None, HEADS, KV), "wo": (LAYERS, HEADS, KV, EMBED),
    }


def logical_axes(config: LatentMoEConfig) -> PyTree:
    attn = attention_axes(config)
    return {
        "wte": (VOCAB, EMBED), "lnf": (EMBED,), "lm_head": (VOCAB, EMBED),
        "dense_blocks": {**attn, "w_gu": (LAYERS, EMBED, MLP),
                         "w_down": (LAYERS, MLP, EMBED)},
        "moe_blocks": {**attn, "router": (LAYERS, EMBED, None),
                       "router_bias": (LAYERS, None),
                       "w_gu": (LAYERS, EXPERT, EMBED, MLP),
                       "w_down": (LAYERS, EXPERT, MLP, EMBED),
                       "ws_gu": (LAYERS, EMBED, MLP),
                       "ws_down": (LAYERS, MLP, EMBED)},
    }


# ------------------------------------------------------------------- apply

def _causal_latent_attention(queries, row, config: LatentMoEConfig):
    """Dense absorbed attention of a whole sequence over its own rows."""
    S = row.shape[1]
    s = jnp.einsum("bqhr,bkr->bhqk", queries, row,
                   preferred_element_type=jnp.float32) * config.softmax_scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool))[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(row.dtype)
    return jnp.einsum("bhqk,bkr->bqhr", p, row[..., :config.kv_rank])


def apply(params: PyTree, tokens, config: LatentMoEConfig):
    """Full-sequence logits ``[B, S, padded_vocab]`` float32, no cache."""
    positions = jnp.arange(tokens.shape[1])
    x = embed(params, tokens, config)

    def attend(x, p):
        queries, row = latent_project(x, p, config, positions)
        return latent_output(
            x, _causal_latent_attention(queries, row, config), p, config)

    def dense_layer(x, p):
        return dense_ffn(attend(x, p), p, config), None

    def moe_layer(x, p):
        return expert_ffn(attend(x, p), p, config)[0], None

    x, _ = lax.scan(dense_layer, x, params["dense_blocks"])
    x, _ = lax.scan(moe_layer, x, params["moe_blocks"])
    return lm_logits(params, x, config)
