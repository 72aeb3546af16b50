"""A decoder whose mixer is, in most layers, a gated short convolution and
nothing else, with grouped rotary attention now and then, leading dense
layers and an expert layer that holds every expert (the LFM2-MoE
generation's block).

What differs from the tree's other blocks, mechanism by mechanism:

- **per-layer kinds** (``layer_types``): ``"conv"`` or ``"attention"``; the
  first ``n_dense`` layers end in a dense SwiGLU, the others in the expert
  layer.  Parameters are a list of RUNS in depth order, a run a UNIT of
  labels repeated (``hybrid_ssm_moe.layer_units``; a label is a layer's
  mixer, and ``+dense`` where its FFN is the dense one, so the leading dense
  layers are a run of their own and a run's FFN is one kind, statically),
  one stack per position of the unit;
- the **gated short convolution**: ``[B | C | u] = W_in h``; ``s = B * u``;
  a causal depthwise convolution of ``conv_kernel`` taps over ``s`` with no
  bias and NO activation (``ops/pallas/ssm.py::causal_conv``, the function
  the state-space and the delta-rule families convolve with); ``W_out (C *
  y)``.  What a conversation keeps of such a layer is the last ``conv_kernel
  - 1`` values of ``s``, whatever its length;
- **attention** as ``window_moe``'s (its own functions): ``n_head`` query
  heads on ``n_kv_head`` key-value heads, q and k of every head
  RMS-normalised with a learned weight and then rotated over the whole
  head, halves paired, by ONE plain table (``yarn`` None), matrices
  head-major, keys cached rotated;
- the **FFN**: ``latent_moe``'s dense SwiGLU in the leading layers; in the
  others the expert layer (``moe/held_experts.py``) under the sigmoid gate
  (``route``: the bias moves the choice and never the weight, the chosen
  scores normalised and scaled), SwiGLU experts, no shared expert;
- the head is the embedding (``hybrid_ssm_moe.embed`` / ``lm_logits`` with
  its multipliers at one).

The residual stream, norms, softmax, the router and the convolution's sum
are float32; every product's inputs, the gates and the kept tail are
``config.dtype``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..moe.held_experts import held_experts_ffn, pair_counts, route
from ..ops.pallas import ssm
from . import hybrid_ssm_moe, latent_moe, window_moe
from .hybrid_ssm_moe import DENSE, layer_units, mixer_of, run_parts
from .latent_moe import rms_norm
from .partitioning import EMBED, EXPERT, HEADS, LAYERS, MLP, VOCAB

PyTree = Any

CONV, ATTENTION = "conv", "attention"
#: the routed experts' two stacks among a layer's parameters
ROUTED = ("w_gu", "w_down")


@dataclasses.dataclass(frozen=True)
class ConvMoEConfig:
    #: the module whose ``FAMILY`` ``models.cache_family`` serves this
    #: config through
    cache_family = "conv_moe_inference"
    #: what ``window_moe``'s and ``hybrid_ssm_moe``'s functions read of a
    #: config and this family has one value of: q and k normalised a head,
    #: one plain rotary table, a tied head, no multipliers
    qk_norm = True
    yarn = None
    tie_head = True
    embedding_multiplier = 1.0
    logits_scaling = 1.0

    vocab_size: int = 1024
    max_seq_len: int = 1024
    layer_types: Tuple[str, ...] = (CONV, CONV, ATTENTION, CONV, CONV, CONV)
    #: the leading layers whose FFN is the dense SwiGLU
    n_dense: int = 2
    d_model: int = 64
    conv_kernel: int = 3
    n_head: int = 4
    n_kv_head: int = 2
    head_dim: int = 16
    rope_theta: float = 1e6
    d_ff: int = 128                 # the leading dense layers' SwiGLU width
    n_experts: int = 16             # the router's width: every expert there is
    experts_per_token: int = 4
    d_expert: int = 32              # one expert's SwiGLU width
    routed_scale: float = 1.0
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    vocab_round_to: int = 128

    def __post_init__(self):
        assert all(t in (CONV, ATTENTION) for t in self.layer_types), \
            self.layer_types
        assert 0 <= self.n_dense <= self.n_layer
        assert self.n_head % self.n_kv_head == 0 and self.head_dim % 2 == 0
        assert self.conv_kernel >= 2

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def held(self) -> Tuple[int, ...]:
        """Every expert is held here: what the expert layer and the
        counters read of any family's config."""
        return tuple(range(self.n_experts))

    @property
    def padded_vocab(self) -> int:
        r = self.vocab_round_to
        return ((self.vocab_size + r - 1) // r) * r

    @property
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)

    @property
    def labels(self) -> Tuple[str, ...]:
        """Every layer's label in depth order."""
        return tuple(t + (DENSE if i < self.n_dense else "")
                     for i, t in enumerate(self.layer_types))

    @property
    def units(self):
        """``hybrid_ssm_moe.layer_units`` of the labels, ``firsts`` counted
        by MIXER (a dense layer's tail is layer 0 of the same stack as the
        expert layers'): ``(unit, firsts, n)``."""
        return layer_units(self.labels, kind=mixer_of)

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    # ---- what the one cache family asks of a config
    @property
    def cache_row(self) -> Tuple[int, ...]:
        """An attention layer's cached token: K (rotated) and V of the
        key-value heads, never repeated out to the query heads."""
        return (self.n_kv_head * self.head_dim,) * 2

    @property
    def cache_layers(self) -> int:
        """Layers that own a row in the banks: the attention layers."""
        return self.count(ATTENTION)

    @property
    def cache_state(self):
        """Per-slot state, ``(layers, shape, dtype)``: ONE array, the
        convolution layers' last ``conv_kernel - 1`` values of ``s = B *
        u``."""
        n = self.count(CONV)
        if not n:
            return None
        return ((n, (self.conv_kernel - 1, self.d_model), self.dtype),)


# ------------------------------------------------------------------ pieces

def conv_inputs(x, p, config: ConvMoEConfig):
    """``[B | C | u] = W_in norm_1(x)``, [B, S, d] each in
    ``config.dtype``."""
    cdt = config.dtype
    h = rms_norm(x, p["ln1"], config.eps, cdt)
    with jax.named_scope("conv_in_proj"):
        bcu = jnp.einsum("bsd,df->bsf", h, p["w_in"].astype(cdt))
    return jnp.split(bcu, 3, axis=-1)


@jax.named_scope("conv_mixer")
def conv_mix(gate_b, gate_c, u, tail, p, config: ConvMoEConfig, valid=None):
    """The gates, the taps and the tail: ``(C * conv(B * u)) [B, S, d]`` in
    ``config.dtype`` and the tail after the call's last real position
    (``valid``: ``causal_conv``'s)."""
    cdt = config.dtype
    s = (gate_b.astype(jnp.float32) * u.astype(jnp.float32)).astype(cdt)
    y, tail = ssm.causal_conv(s, tail, p["conv_w"], None, valid,
                              activation=None)
    return (gate_c.astype(jnp.float32) * y).astype(cdt), tail


@jax.named_scope("conv_out_proj")
def conv_output(x, gated, p, config: ConvMoEConfig):
    """``x + W_out gated``."""
    return x + jnp.einsum("bsf,fd->bsd", gated,
                          p["w_out"].astype(config.dtype),
                          preferred_element_type=jnp.float32)


def attention_project(x, p, config: ConvMoEConfig, positions):
    """``window_moe.attention_project`` with this family's one table."""
    return window_moe.attention_project(x, p, config, positions,
                                        window_moe.FULL)


attention_output = window_moe.attention_output


def expert_ffn(x, p, config: ConvMoEConfig, experts=None, layer=None):
    """The expert layer: ``x + routed(norm_2(x))`` under the sigmoid gate.
    ``experts`` / ``layer`` and the counts ``[n_pair_counts(n_held)]`` are
    ``latent_moe.expert_ffn``'s."""
    B, S, d = x.shape
    k = config.experts_per_token
    with jax.named_scope("moe_router"):
        h32 = rms_norm(x, p["ln2"], config.eps, jnp.float32)
        flat = h32.astype(config.dtype).reshape(B * S, d)
        # the router reads the norm's own float32 output, not its rounding
        routing = route(h32.reshape(B * S, d), p["router"],
                        p["router_bias"], k, config.routed_scale)
    routed, per_expert = held_experts_ffn(
        flat, routing, experts if experts is not None else p, config.held,
        config.n_experts, layer=layer if experts is not None else None)
    return (x + routed.reshape(B, S, d).astype(jnp.float32),
            pair_counts(per_expert, B * S * k))


def ffn(x, p, config: ConvMoEConfig, label: str, experts=None, layer=None):
    """A layer's second half by its label: ``(x, pair counts or None)``."""
    if label.endswith(DENSE):
        return latent_moe.dense_ffn(x, p, config), None
    return expert_ffn(x, p, config, experts=experts, layer=layer)


embed = hybrid_ssm_moe.embed
lm_logits = hybrid_ssm_moe.lm_logits


# -------------------------------------------------------------------- init

def init(config: ConvMoEConfig, rng: jax.Array, std: float = 0.02,
         routed_out_std: Optional[float] = None,
         embed_std: Optional[float] = None) -> PyTree:
    """Seeded weights.  Matrices into a layer draw ``std``, matrices back to
    the residual stream ``std / sqrt(2 L)`` (the routed experts'
    down-projection ``routed_out_std`` where one is given), the embedding
    ``embed_std`` (default ``std``), norm weights one.  A convolution's taps
    draw ``1 / sqrt(taps)``, so that ``y`` keeps ``s``'s scale; the
    selection bias 0.01, small and not zero."""
    d, v = config.d_model, config.padded_vocab
    pdt = config.param_dtype
    E, f = config.n_experts, config.d_expert
    H, Hkv, D = config.n_head, config.n_kv_head, config.head_dim
    taps = config.conv_kernel
    out_std = std / math.sqrt(2 * config.n_layer)
    routed_std = out_std if routed_out_std is None else routed_out_std

    def normal(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(pdt)

    def ffn_init(key, label, n):
        k = jax.random.split(key, 4)
        if label.endswith(DENSE):
            return {"w_gu": normal(k[0], (n, d, 2 * config.d_ff), std),
                    "w_down": normal(k[1], (n, config.d_ff, d), out_std)}
        return {"router": normal(k[2], (n, d, config.n_experts), std),
                "router_bias": normal(k[3], (n, config.n_experts), 0.01),
                "w_gu": normal(k[0], (n, E, d, 2 * f), std),
                "w_down": normal(k[1], (n, E, f, d), routed_std)}

    def mixer_init(key, label, n):
        k = jax.random.split(key, 4)
        norms = {"ln1": jnp.ones((n, d), pdt), "ln2": jnp.ones((n, d), pdt)}
        if label.startswith(CONV):
            return {**norms, "w_in": normal(k[0], (n, d, 3 * d), std),
                    "conv_w": normal(k[1], (n, taps, d),
                                     1.0 / math.sqrt(taps)),
                    "w_out": normal(k[2], (n, d, d), out_std)}
        return {**norms, "wq": normal(k[0], (n, H, D, d), std),
                "wk": normal(k[1], (n, Hkv, D, d), std),
                "wv": normal(k[2], (n, Hkv, D, d), std),
                "wo": normal(k[3], (n, H, D, d), out_std),
                "q_norm": jnp.ones((n, D), pdt),
                "k_norm": jnp.ones((n, D), pdt)}

    def part_init(key, label, n):
        km, kf = jax.random.split(key)
        return {**mixer_init(km, label, n), **ffn_init(kf, label, n)}

    def run_init(key, unit, n):
        if len(unit) == 1:
            return part_init(key, unit[0], n)
        return [part_init(k, label, n)
                for k, label in zip(jax.random.split(key, len(unit)), unit)]

    keys = jax.random.split(rng, len(config.units) + 1)
    return {"wte": normal(keys[0], (v, d),
                          std if embed_std is None else embed_std),
            "runs": [run_init(key, unit, n) for key, (unit, _, n)
                     in zip(keys[1:], config.units)],
            "lnf": jnp.ones((d,), pdt)}


def logical_axes(config: ConvMoEConfig) -> PyTree:
    def part_axes(label):
        p = {"ln1": (LAYERS, EMBED), "ln2": (LAYERS, EMBED)}
        if label.startswith(CONV):
            # the gates meet the convolution channel by channel: the mixer
            # is sliced by nothing
            p.update({"w_in": (LAYERS, EMBED, None),
                      "conv_w": (LAYERS, None, None),
                      "w_out": (LAYERS, None, EMBED)})
        else:
            p.update({"wq": (LAYERS, HEADS, None, EMBED),
                      "wk": (LAYERS, HEADS, None, EMBED),
                      "wv": (LAYERS, HEADS, None, EMBED),
                      "wo": (LAYERS, HEADS, None, EMBED),
                      "q_norm": (LAYERS, None), "k_norm": (LAYERS, None)})
        if label.endswith(DENSE):
            return {**p, "w_gu": (LAYERS, EMBED, MLP),
                    "w_down": (LAYERS, MLP, EMBED)}
        return {**p, "router": (LAYERS, EMBED, None),
                "router_bias": (LAYERS, None),
                "w_gu": (LAYERS, EXPERT, EMBED, MLP),
                "w_down": (LAYERS, EXPERT, MLP, EMBED)}

    def run_axes(unit):
        return part_axes(unit[0]) if len(unit) == 1 else \
            [part_axes(label) for label in unit]

    return {"wte": (VOCAB, EMBED), "lnf": (EMBED,),
            "runs": [run_axes(unit) for unit, _, _ in config.units]}


# ------------------------------------------------------------------- apply

def apply(params: PyTree, tokens, config: ConvMoEConfig):
    """Full-sequence logits ``[B, S, padded_vocab]`` float32, no cache: the
    convolutions start from a zero tail."""
    B, S = tokens.shape
    positions = jnp.arange(S)
    x = embed(params, tokens, config)
    zero_tail = jnp.zeros((B, config.conv_kernel - 1, config.d_model),
                          config.dtype)

    def layer(x, p, label):
        if label.startswith(CONV):
            gate_b, gate_c, u = conv_inputs(x, p, config)
            gated, _ = conv_mix(gate_b, gate_c, u, zero_tail, p, config)
            x = conv_output(x, gated, p, config)
        else:
            q, (k, v) = attention_project(x, p, config, positions)
            x = attention_output(x, window_moe._causal_attention(
                q, k, v, config, window_moe.FULL), p, config)
        return ffn(x, p, config, label)[0]

    for (unit, _, _), run in zip(config.units, params["runs"]):
        def repetition(x, parts, unit=unit):
            for label, p in zip(unit, parts):
                x = layer(x, p, label)
            return x, None

        x, _ = lax.scan(repetition, x, tuple(run_parts(run)))
    return lm_logits(params, x, config)
