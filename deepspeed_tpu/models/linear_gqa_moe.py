"""A linear-attention / grouped-attention decoder with routed experts (the
Solar-Open2 generation's block): most layers mix tokens through a gated
delta-rule state whose transition may have NEGATIVE eigenvalues, one layer
in a few through grouped softmax attention without any positional encoding
under an output gate, and every layer ends in the expert layer.

What differs from the tree's other blocks, mechanism by mechanism:

- **per-layer kinds** from ONE tuple of layer numbers (``gqa_layers``,
  0-indexed as published: the full layer LEADS its period); every other
  layer is a KDA layer and no layer is dense.  Parameters are a list of
  RUNS in depth order, a run a UNIT of labels repeated
  (``hybrid_ssm_moe.layer_units``), one stack per position of the unit;
- the **KDA mixer** is ``linear_latent_moe``'s (its own functions, called
  there: ``kda_inputs`` / ``kda_scan_inputs`` / ``kda_output``, the state
  ``[d_k, heads * d_v]`` float32 a layer a slot) with ``beta = 2 sigmoid(W_b h)``
  (``kda_beta_scale``): ``I - beta k k^T`` then reflects what the state reads
  for ``k`` where ``beta`` passes 1, and its eigenvalue along ``k`` reaches
  -1;
- **grouped attention** as ``hybrid_ssm_moe``'s (``n_head`` query heads on
  ``n_kv_head`` key-value heads, no bias, no rotation, no position, its
  ``_causal_attention`` and ``attention_output``), with a query projection
  TWICE as wide: per head ``[q | z] = W_q h``, and ``a <- a * sigmoid(z)``
  element by element before ``W_o``;
- the **FFN**: ``latent_moe``'s expert layer (``moe/held_experts.py``:
  sigmoid scores, a selection bias, the chosen scores normalised, one
  shared SwiGLU) in every layer.

The residual stream, norms, softmax, gates, decays and the state are
float32; every product's inputs are ``config.dtype``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.pallas import delta_rule, ssm
from . import hybrid_ssm_moe, latent_moe, linear_latent_moe
from .hybrid_ssm_moe import layer_units, run_parts
from .latent_moe import rms_norm
from .linear_latent_moe import EXPERT_AXES, KDA, KDA_AXES
from .partitioning import EMBED, LAYERS, VOCAB

PyTree = Any

GQA = "gqa"


@dataclasses.dataclass(frozen=True)
class LinearGQAMoEConfig:
    #: the module whose ``FAMILY`` ``models.cache_family`` serves this
    #: config through
    cache_family = "linear_gqa_moe_inference"
    #: what ``hybrid_ssm_moe``'s attention functions read of a config and
    #: this family has one value of
    residual_multiplier = 1.0

    vocab_size: int = 1024
    max_seq_len: int = 1024
    n_layer: int = 4
    #: the grouped-attention layers, 0-indexed, those below ``n_layer``;
    #: every other layer is a KDA layer
    gqa_layers: Tuple[int, ...] = (0,)
    d_model: int = 64
    d_expert: int = 32              # one expert's SwiGLU width
    # the KDA mixer: ``linear_latent_moe``'s names, read by its functions
    kda_heads: int = 2
    kda_head_dim: int = 32          # d_k = d_v
    conv_kernel: int = 4
    kda_chunk: int = 64
    #: the factor on ``sigmoid(W_b h)``: ``beta`` in (0, 2)
    kda_beta_scale: float = 2.0
    # grouped attention: ``hybrid_ssm_moe``'s names, read by its functions
    n_head: int = 4
    n_kv_head: int = 2
    head_dim: int = 16
    # the expert layer: ``latent_moe``'s names, read by its functions
    n_experts: int = 16             # the router's width: every expert there is
    experts_per_token: int = 2
    n_shared_experts: int = 1
    #: global ids of the experts held here (None: all of them)
    held_experts: Optional[Tuple[int, ...]] = None
    routed_scale: float = 1.0
    norm_topk: bool = True
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    vocab_round_to: int = 128

    def __post_init__(self):
        assert self.n_head % self.n_kv_head == 0
        assert len(set(self.gqa_layers)) == len(self.gqa_layers) and all(
            0 <= i < self.n_layer for i in self.gqa_layers), self.gqa_layers
        held = self.held
        assert len(set(held)) == len(held) and all(
            0 <= e < self.n_experts for e in held), held

    @property
    def held(self) -> Tuple[int, ...]:
        return tuple(range(self.n_experts)) if self.held_experts is None \
            else tuple(self.held_experts)

    @property
    def padded_vocab(self) -> int:
        r = self.vocab_round_to
        return ((self.vocab_size + r - 1) // r) * r

    @property
    def labels(self) -> Tuple[str, ...]:
        """Every layer's mixer in depth order."""
        return tuple(GQA if i in self.gqa_layers else KDA
                     for i in range(self.n_layer))

    @property
    def units(self):
        """``hybrid_ssm_moe.layer_units`` of the labels: ``(unit, firsts,
        n)``."""
        return layer_units(self.labels)

    def count(self, kind: str) -> int:
        return self.labels.count(kind)

    @property
    def d_kda(self) -> int:
        """A token's ``q``, ``k`` or ``v`` over all KDA heads."""
        return self.kda_heads * self.kda_head_dim

    @property
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)

    # ---- what the one cache family asks of a config
    @property
    def cache_row(self) -> Tuple[int, ...]:
        """A grouped layer's cached token: K and V of the key-value heads,
        never repeated out to the query heads."""
        return (self.n_kv_head * self.head_dim,) * 2

    @property
    def cache_layers(self) -> int:
        """Layers that own a row in the banks: the grouped layers."""
        return self.count(GQA)

    @property
    def cache_state(self):
        """Per-slot state, ``linear_latent_moe``'s: the KDA layers' ``S`` as
        ``[d_k, heads * d_v]`` float32 and the last ``conv_kernel - 1``
        pre-activation inputs of the convolution over ``q | k | v``."""
        n = self.count(KDA)
        if not n:
            return None
        return ((n, (self.kda_head_dim, self.d_kda), jnp.float32),
                (n, (self.conv_kernel - 1, 3 * self.d_kda), self.dtype))


# ------------------------------------------------------------------ pieces

def attention_project(x, p, config: LinearGQAMoEConfig, positions=None):
    """``(q, z)``, queries and their gates [B, S, n_head, D] each (per head
    ``[q | z] = W_q norm_1(x)``), and the cached row's K and V [B, S,
    n_kv_head, D]: no bias and no rotation; ``positions`` are the cache
    family's hook's and go unread."""
    cdt = config.dtype
    h = rms_norm(x, p["ln1"], config.eps, cdt)
    D = config.head_dim
    # ``wq`` is stored [heads * 2 D, d_model]: stored the other way up the
    # compiler re-lays all of it in every tick (134 MB at the published
    # widths; compiler, PR 64)
    qz = jnp.einsum("bsd,fd->bsf", h, p["wq"].astype(cdt)).reshape(
        x.shape[:2] + (config.n_head, 2, D))
    split = lambda t: t.reshape(t.shape[:2] + (config.n_kv_head, D))
    k = jnp.einsum("bsd,df->bsf", h, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,df->bsf", h, p["wv"].astype(cdt))
    return (qz[..., 0, :], qz[..., 1, :]), (split(k), split(v))


@jax.named_scope("attn_gate")
def gated(attn, z):
    """``attn * sigmoid(z)``, element by element in float32."""
    return attn.astype(jnp.float32) * jax.nn.sigmoid(z.astype(jnp.float32))


#: ``x + W_o attn``, ``attn`` [B, S, n_head, D] already gated
attention_output = hybrid_ssm_moe.attention_output


def ffn(x, p, config: LinearGQAMoEConfig, label=None, experts=None,
        layer=None):
    """A layer's second half whatever its label, ``latent_moe.expert_ffn``:
    ``(x, pair counts)``."""
    with jax.named_scope("expert_ffn"):
        return latent_moe.expert_ffn(x, p, config, experts=experts,
                                     layer=layer)


embed = latent_moe.embed
lm_logits = latent_moe.lm_logits


# -------------------------------------------------------------------- init

def init(config: LinearGQAMoEConfig, rng: jax.Array, std: float = 0.02,
         routed_out_std: Optional[float] = None,
         embed_std: Optional[float] = None) -> PyTree:
    """Seeded weights.  Matrices into a layer draw ``std``, matrices back to
    the residual stream ``std / sqrt(2 L)`` (the routed experts'
    down-projection ``routed_out_std`` where one is given), the embedding
    ``embed_std`` (default ``std``), norm weights one; a KDA layer's ranges
    are ``linear_latent_moe.init``'s."""
    d, v = config.d_model, config.padded_vocab
    pdt = config.param_dtype
    H, Hkv, D = config.n_head, config.n_kv_head, config.head_dim
    out_std = std / math.sqrt(2 * config.n_layer)
    routed_std = out_std if routed_out_std is None else routed_out_std
    normal = lambda key, shape, s: linear_latent_moe._normal(key, shape, s,
                                                             pdt)

    def gqa_init(key, n):
        k = jax.random.split(key, 4)
        return {"ln1": jnp.ones((n, d), pdt), "ln2": jnp.ones((n, d), pdt),
                # per head [q | z]: a head's query beside its gate, a ROW an
                # output channel
                "wq": normal(k[0], (n, H * 2 * D, d), std),
                "wk": normal(k[1], (n, d, Hkv * D), std),
                "wv": normal(k[2], (n, d, Hkv * D), std),
                "wo": normal(k[3], (n, H * D, d), out_std)}

    def part_init(key, label, n):
        km, kf = jax.random.split(key)
        mixer = gqa_init(km, n) if label == GQA else \
            linear_latent_moe.kda_init(km, config, n, std, out_std)
        return {**mixer, **linear_latent_moe.expert_init(
            kf, config, n, std, out_std, routed_std)}

    def run_init(key, unit, n):
        if len(unit) == 1:
            return part_init(key, unit[0], n)
        return [part_init(k, label, n)
                for k, label in zip(jax.random.split(key, len(unit)), unit)]

    keys = jax.random.split(rng, len(config.units) + 2)
    return {"wte": normal(keys[0], (v, d),
                          std if embed_std is None else embed_std),
            "runs": [run_init(key, unit, n) for key, (unit, _, n)
                     in zip(keys[2:], config.units)],
            "lnf": jnp.ones((d,), pdt),
            "lm_head": normal(keys[1], (v, d), std)}


def logical_axes(config: LinearGQAMoEConfig) -> PyTree:
    # the gate meets the attention's output channel by channel and the
    # matrices are stored flat: the grouped mixer is sliced by nothing here
    gqa = {"ln1": (LAYERS, EMBED), "ln2": (LAYERS, EMBED),
           "wq": (LAYERS, None, EMBED), "wk": (LAYERS, EMBED, None),
           "wv": (LAYERS, EMBED, None), "wo": (LAYERS, None, EMBED)}

    def part_axes(label):
        return {**(gqa if label == GQA else KDA_AXES), **EXPERT_AXES}

    def run_axes(unit):
        return part_axes(unit[0]) if len(unit) == 1 else \
            [part_axes(label) for label in unit]

    return {"wte": (VOCAB, EMBED), "lnf": (EMBED,),
            "lm_head": (VOCAB, EMBED),
            "runs": [run_axes(unit) for unit, _, _ in config.units]}


# ------------------------------------------------------------------- apply

def apply(params: PyTree, tokens, config: LinearGQAMoEConfig):
    """Full-sequence logits ``[B, S, padded_vocab]`` float32, no cache: the
    KDA layers scan from a zero state."""
    B, S = tokens.shape
    positions = jnp.arange(S)
    x = embed(params, tokens, config)
    zero_tail = jnp.zeros((B, config.conv_kernel - 1, 3 * config.d_kda),
                          config.dtype)
    zero_state = jnp.zeros((1, B, config.kda_head_dim, config.d_kda),
                           jnp.float32)

    def layer(x, p, label):
        if label == KDA:
            qkv, g, beta, gate = linear_latent_moe.kda_inputs(x, p, config)
            u_act, _ = ssm.causal_conv(qkv, zero_tail, p["conv_w"], None)
            q, k, v = linear_latent_moe.kda_scan_inputs(u_act, config)
            o, _ = delta_rule.kda_chunk_scan(zero_state, 0, q, k, v, g, beta,
                                             chunk=config.kda_chunk)
            x = linear_latent_moe.kda_output(x, o, gate, p, config)
        else:
            (q, z), (k, v) = attention_project(x, p, config, positions)
            a = hybrid_ssm_moe._causal_attention(q, k, v, config)
            x = attention_output(x, gated(a, z), p, config)
        return ffn(x, p, config)[0]

    for (unit, _, _), run in zip(config.units, params["runs"]):
        def repetition(x, parts, unit=unit):
            for label, p in zip(unit, parts):
                x = layer(x, p, label)
            return x, None

        x, _ = lax.scan(repetition, x, tuple(run_parts(run)))
    return lm_logits(params, x, config)
