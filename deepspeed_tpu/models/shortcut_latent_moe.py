"""A shortcut-connected double layer: two latent attentions, two dense
feed-forwards, and one expert layer whose result joins two sublayers later
(the LongCat-Flash generation's block).

What differs from ``models/latent_moe.py``'s block, mechanism by mechanism:

- **one kind of layer, two of everything but the experts.**  A layer is
  ``for i in (0, 1): x += MLA_i(N(x)); h_i = N(x); x += FFN_i(h_i)``, and
  the expert layer reads ``h_0``: ``s = MoE(h_0)`` is kept aside while the
  first dense FFN, the second attention and the second dense FFN run, and
  joins the stream after the SECOND dense FFN (``x += s``:
  ``SHORTCUT_JOINS_AFTER``).  In a deployment that is what hides the
  experts' exchange behind the dense branch; on one chip it is the order of
  the sums.  No leading dense layers, no shared expert: one scanned stack;
- **latent attention** as ``latent_moe``'s (absorbed form, one ``[c |
  R(k_r)]`` row a token a sublayer, plain rotary frequencies), with both
  low-rank latents leaving their norms multiplied by ``sqrt(d_model /
  rank)`` (``latent_moe.lora_rescale``; ``q_scale`` / ``kv_scale``); the
  cached ``c`` is the scaled one.  A layer owns TWO
  rows of the cache: sublayer ``j`` of layer ``i`` is cache layer ``2 i +
  j`` (``cache_layers``);
- **the gate** (``moe/held_experts.py::route_softmax_all``): a float32
  softmax over ALL the router's outputs, ``n_experts`` matrices and
  ``n_zero_experts`` **zero-compute experts** (identity), the
  ``experts_per_token`` largest of ``p + bias`` chosen, weights
  ``routed_scale * p`` with no renormalisation.  A pair on a zero-compute
  expert is multiplied by nothing: its weight times the layer's own input
  ``h_0`` is added (``held_experts_ffn(n_zero=)``), by the token's chip, in
  full whatever share of the matrices is held.

Parameters are one stack ``blocks``, every leaf ``[layers, ...]``: ``attn0``
/ ``attn1`` (two sets of ``latent_moe.attention_init``'s a layer), ``dense0``
/ ``dense1`` (``w_gu`` / ``w_down``) and ``moe`` (router, selection bias,
the held experts' matrices).  A sublayer's matrices are stacks of their own
and not ``[layers, 2, ...]``: the layer scan's slice of such a stack is
copied out whole every step (864 MB a layer at the published widths).
``held_experts`` names the experts this process holds (expert
parallelism's share; default all).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..moe.held_experts import (held_experts_ffn, pair_counts,
                                route_softmax_all)
from . import latent_moe
from .latent_moe import latent_output, lora_rescale, rms_norm, swiglu
from .partitioning import EMBED, EXPERT, LAYERS, MLP, VOCAB

PyTree = Any

#: the sublayer (0 or 1) after whose dense FFN the expert branch joins the
#: stream.  The published block joins after the second; a control plants 0
#: (``benchmarks/chip/reference/longcat_flash_control.py``)
SHORTCUT_JOINS_AFTER = 1


@dataclasses.dataclass(frozen=True)
class ShortcutLatentMoEConfig:
    #: the module whose ``FAMILY`` ``models.cache_family`` serves this
    #: config through
    cache_family = "shortcut_latent_moe_inference"
    #: what ``latent_moe``'s pieces read beside the fields: plain rotary
    #: frequencies, every latent row rotated
    yarn = None
    rope = True

    vocab_size: int = 1024
    max_seq_len: int = 1024
    n_layer: int = 2                # double layers
    n_head: int = 4
    d_model: int = 64
    d_ff: int = 128                 # each dense SwiGLU's width
    d_expert: int = 32              # one expert's SwiGLU width
    q_rank: int = 48
    kv_rank: int = 32
    d_nope: int = 16
    d_rope: int = 8
    d_v: int = 16
    n_experts: int = 16             # experts with matrices, deployment-wide
    n_zero_experts: int = 8         # the router's further outputs: identity
    experts_per_token: int = 4
    #: global ids of the experts held here (None: all ``n_experts``)
    held_experts: Optional[Tuple[int, ...]] = None
    routed_scale: float = 1.0
    eps: float = 1e-5
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    vocab_round_to: int = 128

    def __post_init__(self):
        assert self.d_rope % 2 == 0 and self.n_layer >= 1
        assert self.experts_per_token <= self.router_width
        held = self.held
        assert len(set(held)) == len(held) and all(
            0 <= e < self.n_experts for e in held), held

    @property
    def held(self) -> Tuple[int, ...]:
        return tuple(range(self.n_experts)) if self.held_experts is None \
            else tuple(self.held_experts)

    @property
    def router_width(self) -> int:
        return self.n_experts + self.n_zero_experts

    @property
    def padded_vocab(self) -> int:
        r = self.vocab_round_to
        return ((self.vocab_size + r - 1) // r) * r

    @property
    def q_scale(self) -> float:
        return lora_rescale(self.d_model, self.q_rank)

    @property
    def kv_scale(self) -> float:
        return lora_rescale(self.d_model, self.kv_rank)

    # ---- latent attention, as ``latent_moe`` reads it
    @property
    def row_elements(self) -> int:
        return self.kv_rank + self.d_rope

    @property
    def softmax_scale(self) -> float:
        return 1.0 / math.sqrt(self.d_nope + self.d_rope)

    # ---- what the one cache family asks of a config
    @property
    def cache_row(self) -> Tuple[int, ...]:
        """One bank, the latent row in whole lane rows."""
        return (-(-self.row_elements // 128) * 128,)

    @property
    def cache_layers(self) -> int:
        """Rows a token owns in the bank: two attentions a layer."""
        return 2 * self.n_layer


# ------------------------------------------------------------------ pieces

def project(x, p, config: ShortcutLatentMoEConfig, positions):
    """``latent_moe.latent_project`` with this family's two rescales."""
    return latent_moe.latent_project(x, p, config, positions,
                                     config.q_scale, config.kv_scale)


def expert_branch(h32, p, config: ShortcutLatentMoEConfig, experts=None,
                  layer=None):
    """``MoE(h_0)``: ``h32`` [B, S, d] is the norm's float32 output (the
    router reads it, not its rounding); ``p`` one layer's ``moe``
    parameters; ``experts`` (with ``layer``, a scan's index) the held
    experts' matrices as whole stacks ``{"w_gu", "w_down"}`` ``[layers,
    n_held, ...]`` for the grouped product to read in place, where ``p``
    does not carry one layer of them.  Returns ``(s [B, S, d] float32,
    counts)``: the held experts' part plus the zero-compute experts'
    ``weight * h_0``, and ``held_experts.pair_counts``' vector."""
    B, S, d = h32.shape
    k = config.experts_per_token
    with jax.named_scope("moe_router"):
        routing = route_softmax_all(
            h32.reshape(B * S, d), p["router"], p["router_bias"], k,
            config.routed_scale)
    out, per_expert = held_experts_ffn(
        h32.astype(config.dtype).reshape(B * S, d), routing,
        experts if experts is not None else p, config.held,
        config.n_experts, layer=layer if experts is not None else None,
        n_zero=config.n_zero_experts)
    return (out.reshape(B, S, d).astype(jnp.float32),
            pair_counts(per_expert, B * S * k))


def double_layer(x, p, config: ShortcutLatentMoEConfig, attend,
                 experts=None, layer=None):
    """One layer.  ``p``: its slice of ``blocks`` (less the held experts'
    matrices where ``experts`` brings their stacks); ``attend(x, p_attn, j)
    -> [B, S, H, kv_rank]``: sublayer ``j``'s probability-weighted latent
    rows.  Returns ``(x, counts)``."""
    cdt = config.dtype
    s = counts = None
    for j in (0, 1):
        pa, pd = p[f"attn{j}"], p[f"dense{j}"]
        with jax.named_scope(f"attn{j}"):
            x = latent_output(x, attend(x, pa, j), pa, config)
        h32 = rms_norm(x, pa["ln2"], config.eps, jnp.float32)
        if j == 0:
            # the shortcut: computed from h_0, kept aside
            with jax.named_scope("shortcut"):
                s, counts = expert_branch(h32, p["moe"], config, experts,
                                          layer)
        with jax.named_scope("dense_ffn"):
            x = x + swiglu(h32.astype(cdt), pd["w_gu"], pd["w_down"], cdt)
        if j == SHORTCUT_JOINS_AFTER:
            x = x + s
    return x, counts


embed = latent_moe.embed
lm_logits = latent_moe.lm_logits


# -------------------------------------------------------------------- init

def init(config: ShortcutLatentMoEConfig, rng: jax.Array, std: float = 0.02,
         router_std: Optional[float] = None,
         routed_out_std: Optional[float] = None,
         router_bias_std: Optional[float] = None,
         up_std: Optional[float] = None,
         embed_std: Optional[float] = None) -> PyTree:
    """Seeded weights.  Matrices into a layer draw ``std``, matrices back to
    the residual stream ``std / sqrt(2 * sublayers)`` (two sublayers a
    layer); the router ``router_std`` and the routed experts' way back
    ``routed_out_std`` (default: as the others); the selection bias
    ``router_bias_std`` (default: a hundredth of a uniform gate's
    probability, small and not zero so that the bias is exercised); the two
    up-projections out of the latents (``wq_b``, ``wkv_b``) ``up_std`` and
    the embedding ``embed_std`` (defaults: ``std``)."""
    d, v = config.d_model, config.padded_vocab
    pdt = config.param_dtype
    L, E, W = config.n_layer, len(config.held), config.router_width
    out_std = std / math.sqrt(2 * config.cache_layers)
    k = jax.random.split(rng, 13)

    def normal(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(pdt)
    def attention(key):
        p = latent_moe.attention_init(key, config, L, std, out_std)
        if up_std is not None:
            for name in ("wq_b", "wkv_b"):
                p[name] = (p[name].astype(jnp.float32) * (up_std / std)
                           ).astype(pdt)
        return p
    blocks = {
        "attn0": attention(k[9]), "attn1": attention(k[10]),
        **{f"dense{j}": {
            "w_gu": normal(k[1 + j], (L, d, 2 * config.d_ff), std),
            "w_down": normal(k[11 + j], (L, config.d_ff, d), out_std)}
           for j in (0, 1)},
        "moe": {
            "router": normal(k[3], (L, d, W),
                             std if router_std is None else router_std),
            "router_bias": normal(k[4], (L, W), 0.01 / W if router_bias_std
                                  is None else router_bias_std),
            "w_gu": normal(k[5], (L, E, d, 2 * config.d_expert), std),
            "w_down": normal(k[6], (L, E, config.d_expert, d),
                             out_std if routed_out_std is None
                             else routed_out_std)}}
    return {"wte": normal(k[7], (v, d),
                          std if embed_std is None else embed_std),
            "blocks": blocks,
            "lnf": jnp.ones((d,), pdt),
            "lm_head": normal(k[8], (v, d), std)}


def logical_axes(config: ShortcutLatentMoEConfig) -> PyTree:
    dense = {"w_gu": (LAYERS, EMBED, MLP), "w_down": (LAYERS, MLP, EMBED)}
    return {
        "wte": (VOCAB, EMBED), "lnf": (EMBED,), "lm_head": (VOCAB, EMBED),
        "blocks": {
            "attn0": latent_moe.attention_axes(config),
            "attn1": latent_moe.attention_axes(config),
            "dense0": dense, "dense1": dict(dense),
            "moe": {"router": (LAYERS, EMBED, None),
                    "router_bias": (LAYERS, None),
                    "w_gu": (LAYERS, EXPERT, EMBED, MLP),
                    "w_down": (LAYERS, EXPERT, MLP, EMBED)}}}


# ------------------------------------------------------------------- apply

def apply(params: PyTree, tokens, config: ShortcutLatentMoEConfig):
    """Full-sequence logits ``[B, S, padded_vocab]`` float32, no cache."""
    positions = jnp.arange(tokens.shape[1])
    x = embed(params, tokens, config)

    def attend(x, pa, j):
        queries, row = project(x, pa, config, positions)
        return latent_moe._causal_latent_attention(queries, row, config)

    def layer(x, p):
        return double_layer(x, p, config, attend)[0], None

    x, _ = lax.scan(layer, x, params["blocks"])
    return lm_logits(params, x, config)
