"""A linear-attention / latent-attention decoder with routed experts (the
Kimi-Linear generation's block): most layers mix tokens through a gated
delta-rule state (Kimi Delta Attention, KDA), one layer in a few through
latent attention without any positional encoding, and every layer but the
leading dense ones ends in the expert layer.

What differs from the tree's other blocks, mechanism by mechanism:

- **per-layer kinds** from two tuples of layer numbers (``kda_layers``,
  ``full_attn_layers``, 1-indexed as published).  Parameters are a list of
  RUNS in depth order, a run a UNIT of labels repeated
  (``hybrid_ssm_moe.layer_units``), one stack per position of the unit;
- the **KDA mixer**: ``q~ | k~ | v~ = W_qkv h``, one causal depthwise
  convolution of ``conv_kernel`` taps over all three (no bias) and SiLU; per
  head ``q = l2norm(q~) d_k^-1/2``, ``k = l2norm(k~)``; a log-decay PER KEY
  CHANNEL ``g = -exp(A_log) softplus(W_fb (W_fa h) + dt_bias)``; ``beta =
  sigmoid(W_b h)`` a head; the state ``S`` ``[d_k, d_v]`` float32 a head,
  ``S' = exp(g)[:, None] S``, ``u = beta (v - S'^T k)``, ``S = S' + k u^T``,
  ``o = S^T q`` (``ops/pallas/delta_rule.py``: a step a token, or the chunked
  form); ``o <- norm_head(o) * sigmoid(W_gb (W_ga h) + b_g)``; ``W_o``;
- **latent attention** as ``latent_moe``'s (its own functions), with no
  query bottleneck (``q_rank`` None) and no rotation at all (``rope``
  False): the row ``[c | k_r]`` is cached as projected;
- the **FFN**: ``latent_moe``'s dense SwiGLU in the first ``first_k_dense``
  layers, its expert layer (``moe/held_experts.py``) in the others.

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
from . import latent_moe
from .hybrid_ssm_moe import DENSE, layer_units, mixer_of, run_parts
from .latent_moe import rms_norm
from .partitioning import EMBED, EXPERT, LAYERS, MLP, VOCAB

PyTree = Any

KDA, LATENT = "kda", "latent"
#: a layer's label in ``LinearLatentMoEConfig.labels``: its mixer, and
#: ``+dense`` (``hybrid_ssm_moe.DENSE``) where its FFN is the dense one
#: the routed experts' two stacks among a layer's parameters
ROUTED = ("w_gu", "w_down")
#: the l2 norm's epsilon on ``q`` and ``k``
L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class LinearLatentMoEConfig:
    #: the module whose ``FAMILY`` ``models.cache_family`` serves this
    #: config through
    cache_family = "linear_latent_moe_inference"
    #: what ``latent_moe``'s functions read of a config and this family has
    #: one value of: no query bottleneck, no rotation
    q_rank = None
    rope = False

    vocab_size: int = 1024
    max_seq_len: int = 1024
    n_layer: int = 4
    #: the layers of each kind, 1-indexed, those up to ``n_layer``
    kda_layers: Tuple[int, ...] = (1, 2, 3)
    full_attn_layers: Tuple[int, ...] = (4,)
    d_model: int = 64
    d_ff: int = 256                 # the leading dense layers' SwiGLU width
    d_expert: int = 32              # one expert's SwiGLU width
    # the KDA mixer
    kda_heads: int = 2
    kda_head_dim: int = 32          # d_k = d_v
    conv_kernel: int = 4
    kda_chunk: int = 64
    #: the factor on ``beta = sigmoid(W_b h)``: 1 keeps every eigenvalue of
    #: the state's transition in [0, 1]; 2 (``linear_gqa_moe``) lets them
    #: reach -1
    kda_beta_scale: float = 1.0
    # latent attention: ``latent_moe``'s names, read by its functions
    n_head: int = 4
    kv_rank: int = 32
    d_nope: int = 16
    d_rope: int = 8
    d_v: int = 16
    # the expert layer
    n_experts: int = 16             # the router's width: every expert there is
    experts_per_token: int = 2
    n_shared_experts: int = 1
    first_k_dense: int = 1
    #: global ids of the experts held here (None: all of them)
    held_experts: Optional[Tuple[int, ...]] = None
    routed_scale: float = 1.0
    norm_topk: bool = True
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    vocab_round_to: int = 128

    def __post_init__(self):
        assert 0 <= self.first_k_dense <= self.n_layer
        kda, full = set(self.kda_layers), set(self.full_attn_layers)
        assert not kda & full and kda | full == set(
            range(1, self.n_layer + 1)), (self.kda_layers,
                                          self.full_attn_layers)
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
        """Every layer's label in depth order."""
        return tuple(
            (KDA if i + 1 in self.kda_layers else LATENT)
            + (DENSE if i < self.first_k_dense else "")
            for i in range(self.n_layer))

    @property
    def units(self):
        """``hybrid_ssm_moe.layer_units`` of the labels, with ``firsts``
        counted by MIXER (a dense layer's state is layer 0 of the same
        stack as the expert layers'): ``(unit, firsts, n)``."""
        return layer_units(self.labels, kind=mixer_of)

    def count(self, kind: str) -> int:
        return len(self.kda_layers if kind == KDA else self.full_attn_layers)

    @property
    def d_kda(self) -> int:
        """A token's ``q``, ``k`` or ``v`` over all heads."""
        return self.kda_heads * self.kda_head_dim

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
        """Layers that own a row in the bank: the latent layers."""
        return self.count(LATENT)

    @property
    def cache_state(self):
        """Per-slot state, ``(layers, shape, dtype)`` each: the KDA layers'
        ``S`` as ``[d_k, heads * d_v]`` float32 (``ops/pallas/delta_rule.py``
        says why that way up) and the last ``conv_kernel - 1``
        pre-activation inputs of the convolution over ``q | k | v``."""
        n = self.count(KDA)
        if not n:
            return None
        return ((n, (self.kda_head_dim, self.d_kda), jnp.float32),
                (n, (self.conv_kernel - 1, 3 * self.d_kda), self.dtype))


# ------------------------------------------------------------------ pieces

def kda_inputs(x, p, config: LinearLatentMoEConfig):
    """From ``x`` [B, S, d]: the convolution's pre-activation input ``q~ |
    k~ | v~`` [B, S, 3 d_kda] in ``config.dtype``; the log-decay ``g`` [B, S,
    H, d_k] (<= 0), ``beta`` [B, S, H] (``kda_beta_scale sigmoid(W_b h)``)
    and the output gate [B, S, d_kda], float32."""
    cdt = config.dtype
    H, K = config.kda_heads, config.kda_head_dim
    h = rms_norm(x, p["ln1"], config.eps, cdt)
    with jax.named_scope("kda_in_proj"):
        qkv = jnp.einsum("bsd,df->bsf", h, p["w_qkv"].astype(cdt))
    with jax.named_scope("kda_gate"):
        low = lambda a, b: jnp.einsum(
            "bsr,rf->bsf", jnp.einsum("bsd,dr->bsr", h, p[a].astype(cdt)),
            p[b].astype(cdt), preferred_element_type=jnp.float32)
        dt = jax.nn.softplus(low("w_fa", "w_fb")
                             + p["dt_bias"].astype(jnp.float32))
        g = -jnp.exp(p["A_log"].astype(jnp.float32))[:, None] \
            * dt.reshape(dt.shape[:2] + (H, K))
        beta = jax.nn.sigmoid(jnp.einsum(
            "bsd,dh->bsh", h, p["w_b"].astype(cdt),
            preferred_element_type=jnp.float32))
        if config.kda_beta_scale != 1.0:
            beta = config.kda_beta_scale * beta
        gate = jax.nn.sigmoid(low("w_ga", "w_gb")
                              + p["b_g"].astype(jnp.float32))
    return qkv, g, beta, gate


def kda_scan_inputs(u_act, config: LinearLatentMoEConfig):
    """From the convolution's output [B, S, 3 d_kda] float32: ``q``, ``k``,
    ``v`` [B, S, H, d_k], the first two of unit length a head, the query
    over ``sqrt(d_k)``."""
    H, K = config.kda_heads, config.kda_head_dim
    q, k, v = (t.reshape(t.shape[:2] + (H, K))
               for t in jnp.split(u_act.astype(jnp.float32), 3, axis=-1))
    unit = lambda t: t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True)
                                   + L2_EPS)
    return unit(q) * K ** -0.5, unit(k), v


@jax.named_scope("kda_output")
def kda_output(x, o, gate, p, config: LinearLatentMoEConfig):
    """``x + W_o (norm_head(o) * gate)``: ``o`` [B, S, d_kda] float32, the
    norm over each head's channels with one scale shared by the heads."""
    cdt = config.dtype
    B, S, _ = o.shape
    o = rms_norm(o.reshape(B, S, config.kda_heads, config.kda_head_dim),
                 p["norm_o"], config.eps, jnp.float32).reshape(B, S, -1)
    return x + jnp.einsum("bsf,fd->bsd", (o * gate).astype(cdt),
                          p["w_o"].astype(cdt),
                          preferred_element_type=jnp.float32)


def ffn(x, p, config: LinearLatentMoEConfig, label: str, experts=None,
        layer=None):
    """A layer's second half by its label: ``(x, pair counts or None)``."""
    if label.endswith(DENSE):
        return latent_moe.dense_ffn(x, p, config), None
    with jax.named_scope("expert_ffn"):
        return latent_moe.expert_ffn(x, p, config, experts=experts,
                                     layer=layer)


embed = latent_moe.embed
lm_logits = latent_moe.lm_logits


# -------------------------------------------------------------------- init

def _normal(key, shape, s, pdt):
    return (jax.random.normal(key, shape, jnp.float32) * s).astype(pdt)


def kda_init(key, config, n: int, std, out_std):
    """``n`` stacked KDA layers' mixer and both norms (the ranges are
    :func:`init`'s), for any config with this family's KDA fields."""
    d, pdt = config.d_model, config.param_dtype
    normal = lambda key, shape, s: _normal(key, shape, s, pdt)
    k = jax.random.split(key, 10)
    H, K, F = config.kda_heads, config.kda_head_dim, config.d_kda
    r = K       # between the decay's and the gate's two matrices: a head's
    taps = config.conv_kernel
    dt = jnp.exp(jax.random.uniform(k[8], (n, F), jnp.float32)
                 * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
    return {
        "ln1": jnp.ones((n, d), pdt), "ln2": jnp.ones((n, d), pdt),
        "w_qkv": normal(k[0], (n, d, 3 * F), std),
        "conv_w": (jax.random.uniform(k[1], (n, taps, 3 * F),
                                      jnp.float32, -1.0, 1.0)
                   / math.sqrt(taps)).astype(pdt),
        "w_fa": normal(k[2], (n, d, r), std),
        "w_fb": normal(k[3], (n, r, F), std),
        # the inverse of softplus, so that softplus(dt_bias) = dt
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt),
        "A_log": jnp.log(jax.random.uniform(
            k[9], (n, H), jnp.float32, 1.0, 16.0)).astype(pdt),
        "w_b": normal(k[4], (n, d, H), std),
        "w_ga": normal(k[5], (n, d, r), std),
        "w_gb": normal(k[6], (n, r, F), std),
        "b_g": normal(jax.random.fold_in(key, 21), (n, F), std),
        "norm_o": jnp.ones((n, K), pdt),
        "w_o": normal(k[7], (n, F, d), out_std)}


def expert_init(key, config, n: int, std, out_std, routed_std):
    """``n`` stacked expert layers' FFN halves: the router with its
    selection bias, the held experts and the shared one."""
    d, pdt = config.d_model, config.param_dtype
    normal = lambda key, shape, s: _normal(key, shape, s, pdt)
    k = jax.random.split(key, 7)
    E = len(config.held)
    f, fs = config.d_expert, config.d_expert * config.n_shared_experts
    return {"router": normal(k[2], (n, d, config.n_experts), std),
            # small and not zero, so that the bias is exercised
            "router_bias": normal(k[3], (n, config.n_experts), 0.01),
            "w_gu": normal(k[0], (n, E, d, 2 * f), std),
            "w_down": normal(k[1], (n, E, f, d), routed_std),
            "ws_gu": normal(k[4], (n, d, 2 * fs), std),
            "ws_down": normal(k[5], (n, fs, d), out_std)}


def init(config: LinearLatentMoEConfig, rng: jax.Array, std: float = 0.02,
         routed_out_std: Optional[float] = None,
         embed_std: Optional[float] = None) -> PyTree:
    """Seeded weights.  Matrices into a layer draw ``std``, matrices back to
    the residual stream ``std / sqrt(2 L)`` (the routed experts'
    down-projection ``routed_out_std`` where one is given), the embedding
    ``embed_std`` (default ``std``).  A KDA layer's ``A_log`` is the log of
    ``A`` uniform in [1, 16] a head, its ``dt_bias`` the inverse softplus of
    ``dt`` log-uniform in [0.001, 0.1] a channel, its convolution's taps
    uniform in +-1/sqrt(taps), its output gate's bias normal ``std``."""
    d, v = config.d_model, config.padded_vocab
    pdt = config.param_dtype
    out_std = std / math.sqrt(2 * config.n_layer)
    routed_std = out_std if routed_out_std is None else routed_out_std
    normal = lambda key, shape, s: _normal(key, shape, s, pdt)

    def ffn_init(key, label, n):
        if label.endswith(DENSE):
            k = jax.random.split(key, 7)
            return {"w_gu": normal(k[0], (n, d, 2 * config.d_ff), std),
                    "w_down": normal(k[1], (n, config.d_ff, d), out_std)}
        return expert_init(key, config, n, std, out_std, routed_std)

    def part_init(key, label, n):
        km, kf = jax.random.split(key)
        mixer = kda_init(km, config, n, std, out_std) \
            if label.startswith(KDA) else \
            latent_moe.attention_init(km, config, n, std, out_std)
        return {**mixer, **ffn_init(kf, label, n)}

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


#: a KDA layer's mixer and both norms: the head norm and the gate run over a
#: token's heads together, so the mixer is not sliced by head
KDA_AXES = {"ln1": (LAYERS, EMBED), "ln2": (LAYERS, EMBED),
            "w_qkv": (LAYERS, EMBED, None), "conv_w": (LAYERS, None, None),
            "w_fa": (LAYERS, EMBED, None), "w_fb": (LAYERS, None, None),
            "dt_bias": (LAYERS, None), "A_log": (LAYERS, None),
            "w_b": (LAYERS, EMBED, None),
            "w_ga": (LAYERS, EMBED, None), "w_gb": (LAYERS, None, None),
            "b_g": (LAYERS, None), "norm_o": (LAYERS, None),
            "w_o": (LAYERS, None, EMBED)}
#: an expert layer's FFN half
EXPERT_AXES = {"router": (LAYERS, EMBED, None), "router_bias": (LAYERS, None),
               "w_gu": (LAYERS, EXPERT, EMBED, MLP),
               "w_down": (LAYERS, EXPERT, MLP, EMBED),
               "ws_gu": (LAYERS, EMBED, MLP), "ws_down": (LAYERS, MLP, EMBED)}


def logical_axes(config: LinearLatentMoEConfig) -> PyTree:
    def part_axes(label):
        p = dict(KDA_AXES) if label.startswith(KDA) else \
            latent_moe.attention_axes(config)
        if label.endswith(DENSE):
            return {**p, "w_gu": (LAYERS, EMBED, MLP),
                    "w_down": (LAYERS, MLP, EMBED)}
        return {**p, **EXPERT_AXES}

    def run_axes(unit):
        return part_axes(unit[0]) if len(unit) == 1 else \
            [part_axes(label) for label in unit]

    return {"wte": (VOCAB, EMBED), "lnf": (EMBED,),
            "lm_head": (VOCAB, EMBED),
            "runs": [run_axes(unit) for unit, _, _ in config.units]}


# ------------------------------------------------------------------- apply

def apply(params: PyTree, tokens, config: LinearLatentMoEConfig):
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
        if label.startswith(KDA):
            qkv, g, beta, gate = kda_inputs(x, p, config)
            u_act, _ = ssm.causal_conv(qkv, zero_tail, p["conv_w"], None)
            q, k, v = kda_scan_inputs(u_act, config)
            o, _ = delta_rule.kda_chunk_scan(zero_state, 0, q, k, v, g, beta,
                                             chunk=config.kda_chunk)
            x = kda_output(x, o, gate, p, config)
        else:
            queries, row = latent_moe.latent_project(x, p, config, positions)
            x = latent_moe.latent_output(
                x, latent_moe._causal_latent_attention(queries, row, config),
                p, config)
        return ffn(x, p, config, label)[0]

    for (unit, _, _), run in zip(config.units, params["runs"]):
        def repetition(x, parts, unit=unit):
            for label, p in zip(unit, parts):
                x = layer(x, p, label)
            return x, None

        x, _ = lax.scan(repetition, x, tuple(run_parts(run)))
    return lm_logits(params, x, config)
