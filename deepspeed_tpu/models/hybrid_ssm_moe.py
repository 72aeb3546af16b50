"""A hybrid state-space / attention decoder with routed experts in every
layer (the Granite-4.0-H generation's block: mostly Mamba-2 mixers, an
attention layer now and then, each followed by many small routed experts
beside a shared gated MLP).

What differs from the tree's other blocks, mechanism by mechanism:

- **per-layer mixer kinds** (``layer_types``): a layer's mixer is a
  state-space one (``"mamba"``) or attention (``"attention"``).  Parameters
  are a list of RUNS, one stack per run of consecutive layers of one kind,
  in depth order; nothing assumes a period or a pattern;
- the **state-space mixer** (Mamba-2, one group): ``[z | u | dt] = W_in h``;
  a causal depthwise convolution of width ``conv_kernel`` and SiLU on ``u``;
  ``[v | B | C] = u``; per head ``dt = softplus(dt + dt_bias)``, ``a =
  -exp(A_log)``, state ``H_t = exp(dt_t a) H_{t-1} + dt_t v_t B_t^T``, ``y_t
  = H_t C_t + D v_t``; ``y = norm_g(y * silu(z))`` over all channels;
  ``W_out y``.  The scan runs chunked (``ops/pallas/ssm.py``);
- **attention** with grouped key-value heads, **no positional encoding**
  and a fixed score scale (``attn_scale``), no bias;
- the **expert layer** (``moe/held_experts.py``): a float32 router over all
  ``n_experts``, the ``experts_per_token`` largest logits chosen and
  weighed by a softmax over the chosen, the pairs that land on the experts
  held here multiplied grouped, and a shared gated MLP beside them;
- **multipliers**: the embedding times ``embedding_multiplier``, every
  residual branch times ``residual_multiplier``, the logits over
  ``logits_scaling``; the head is the embedding;
- RMSNorm and the gated MLP are ``latent_moe``'s own functions (``rms_norm``,
  ``swiglu``); the residual stream, a sum over layers, stays
  float32; the state ``H``, ``dt``, the decays, norms, softmax and router
  logits are float32, every product's inputs ``config.dtype``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..moe.held_experts import (held_experts_ffn, pair_counts,
                                route_softmax)
from ..ops.pallas import ssm
from .latent_moe import rms_norm, swiglu as gated_mlp
from .partitioning import EMBED, EXPERT, HEADS, LAYERS, MLP, VOCAB

PyTree = Any

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class HybridSSMMoEConfig:
    #: the module ``models.cache_family`` serves this config through
    cache_family = "hybrid_ssm_moe_inference"

    vocab_size: int = 1024
    max_seq_len: int = 1024
    layer_types: Tuple[str, ...] = (MAMBA, MAMBA, ATTENTION, MAMBA)
    d_model: int = 64
    # the state-space mixer
    ssm_heads: int = 4
    ssm_head_dim: int = 32
    ssm_state: int = 16
    conv_kernel: int = 4
    ssm_chunk: int = 256
    # attention
    n_head: int = 4
    n_kv_head: int = 2
    head_dim: int = 16
    attn_scale: float = 1.0 / 16
    # the expert layer
    n_experts: int = 16             # the router's width: every expert there is
    experts_per_token: int = 4
    d_expert: int = 32              # one routed expert's width
    d_shared: int = 64              # the shared gated MLP's width
    #: global ids of the experts held here (None: all of them)
    held_experts: Optional[Tuple[int, ...]] = None
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    vocab_round_to: int = 128

    def __post_init__(self):
        assert all(t in (MAMBA, ATTENTION) for t in self.layer_types), \
            self.layer_types
        assert self.n_head % self.n_kv_head == 0
        held = self.held
        assert len(set(held)) == len(held) and all(
            0 <= e < self.n_experts for e in held), held

    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def held(self) -> Tuple[int, ...]:
        return tuple(range(self.n_experts)) if self.held_experts is None \
            else tuple(self.held_experts)

    @property
    def padded_vocab(self) -> int:
        r = self.vocab_round_to
        return ((self.vocab_size + r - 1) // r) * r

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def d_conv(self) -> int:
        """Channels through the convolution: ``[v | B | C]``."""
        return self.d_inner + 2 * self.ssm_state

    @property
    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """``(kind, first layer among its kind, layers)`` for each run of
        consecutive layers of one kind, in depth order."""
        out: List[Tuple[str, int, int]] = []
        seen = {MAMBA: 0, ATTENTION: 0}
        for kind in self.layer_types:
            if out and out[-1][0] == kind:
                out[-1] = (kind, out[-1][1], out[-1][2] + 1)
            else:
                out.append((kind, seen[kind], 1))
            seen[kind] += 1
        return tuple(out)

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    # ---- what the one cache family asks of a config
    @property
    def cache_row(self) -> Tuple[int, ...]:
        """An attention layer's cached token: K and V of the key-value
        heads (never repeated out to the query heads)."""
        return (self.n_kv_head * self.head_dim,) * 2

    @property
    def cache_layers(self) -> int:
        """Layers that own a row in the banks: the attention layers."""
        return self.count(ATTENTION)

    @property
    def cache_state(self):
        """Per-slot state, ``(layers, shape, dtype)`` each: the state-space
        layers' running sum ``H`` as ``[d_state, heads * head_dim]`` float32
        (``ops/pallas/ssm.py`` says why that way up) and the last
        ``conv_kernel - 1`` pre-activation inputs of the convolution."""
        n = self.count(MAMBA)
        if not n:
            return None
        return ((n, (self.ssm_state, self.d_inner), jnp.float32),
                (n, (self.conv_kernel - 1, self.d_conv), self.dtype))


# ------------------------------------------------------------------ pieces

def ssm_inputs(x, p, config: HybridSSMMoEConfig):
    """``[z | u | dt] = W_in norm_1(x)``: the gate ``z`` [B, S, d_inner] and
    the convolution's pre-activation input ``u`` [B, S, d_conv] in
    ``config.dtype``, ``dt`` [B, S, heads] float32 before its bias.  The
    published matrix is kept as two, ``w_in`` (``z | u``) and ``w_dt``: one
    product that returned float32 for ``dt``'s sake wrote the other 16,640
    columns out in float32 and again in ``config.dtype``."""
    cdt = config.dtype
    h = rms_norm(x, p["ln1"], config.eps, cdt)
    with jax.named_scope("ssm_in_proj"):
        zu = jnp.einsum("bsd,df->bsf", h, p["w_in"].astype(cdt))
        dt = jnp.einsum("bsd,dh->bsh", h, p["w_dt"].astype(cdt),
                        preferred_element_type=jnp.float32)
    di = config.d_inner
    return zu[..., :di], zu[..., di:], dt


def ssm_scan_inputs(u_act, dt, p, config: HybridSSMMoEConfig):
    """From the convolution's output ``u_act`` [B, S, d_conv] float32 and
    the raw ``dt``: ``(v [B, S, heads, head_dim], dt [B, S, heads] float32
    after bias and softplus, a [heads] float32, B, C [B, S, d_state])``."""
    cdt = config.dtype
    di, N = config.d_inner, config.ssm_state
    u_act = u_act.astype(cdt)
    v = u_act[..., :di].reshape(
        u_act.shape[:2] + (config.ssm_heads, config.ssm_head_dim))
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    return v, dt, a, u_act[..., di:di + N], u_act[..., di + N:]


def ssm_output(x, y, v, z, p, config: HybridSSMMoEConfig):
    """``x + r W_out norm_g((y + D v) * silu(z))``: ``y`` [B, S, d_inner]
    float32 is ``H_t C_t``."""
    cdt = config.dtype
    P = config.ssm_head_dim
    d_wide = jnp.repeat(p["D"].astype(jnp.float32), P)
    y = y + d_wide * v.reshape(y.shape).astype(jnp.float32)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)), p["norm_g"],
                 config.eps, cdt)
    with jax.named_scope("ssm_out_proj"):
        out = jnp.einsum("bsf,fd->bsd", y, p["w_out"].astype(cdt),
                         preferred_element_type=jnp.float32)
    return x + config.residual_multiplier * out


def attention_project(x, p, config: HybridSSMMoEConfig):
    """Queries [B, S, n_head, D] and the cached row's K and V [B, S,
    n_kv_head, D] of ``norm_1(x)``: no bias, no rotation, no position."""
    cdt = config.dtype
    h = rms_norm(x, p["ln1"], config.eps, cdt)
    D = config.head_dim
    split = lambda t, n: t.reshape(t.shape[:2] + (n, D))
    q = jnp.einsum("bsd,df->bsf", h, p["wq"].astype(cdt))
    k = jnp.einsum("bsd,df->bsf", h, p["wk"].astype(cdt))
    v = jnp.einsum("bsd,df->bsf", h, p["wv"].astype(cdt))
    return split(q, config.n_head), (split(k, config.n_kv_head),
                                     split(v, config.n_kv_head))


@jax.named_scope("attn_out")
def attention_output(x, attn, p, config: HybridSSMMoEConfig):
    """``x + r W_o attn``: ``attn`` [B, S, n_head, D]."""
    cdt = config.dtype
    out = jnp.einsum("bsf,fd->bsd",
                     attn.reshape(attn.shape[:2] + (-1,)).astype(cdt),
                     p["wo"].astype(cdt), preferred_element_type=jnp.float32)
    return x + config.residual_multiplier * out


def expert_ffn(x, p, config: HybridSSMMoEConfig, experts=None, layer=None):
    """The layer's second half: ``x + r (routed(h) + shared(h))``, ``h =
    norm_2(x)``.  ``experts`` / ``layer`` and the counts ``[3 + n_held]``
    are ``latent_moe.expert_ffn``'s: pairs held here, pairs routed, held
    experts that took a pair, pairs per held expert."""
    B, S, d = x.shape
    h32 = rms_norm(x, p["ln2"], config.eps, jnp.float32)
    h = h32.astype(config.dtype)
    flat = h.reshape(B * S, d)
    with jax.named_scope("moe_router"):
        # the router reads the norm's own float32 output, not its rounding
        routing = route_softmax(h32.reshape(B * S, d), p["router"],
                                config.experts_per_token)
    routed, per_expert = held_experts_ffn(
        flat, routing, experts if experts is not None else p, config.held,
        config.n_experts, layer=layer if experts is not None else None)
    with jax.named_scope("moe_shared"):
        shared = gated_mlp(h, p["ws_gu"], p["ws_down"], config.dtype)
    counts = pair_counts(per_expert, B * S * config.experts_per_token)
    out = routed.reshape(B, S, d).astype(jnp.float32) + shared
    return x + config.residual_multiplier * out, counts


def embed(params: PyTree, tokens, config: HybridSSMMoEConfig):
    return config.embedding_multiplier \
        * params["wte"][tokens].astype(jnp.float32)


def lm_logits(params: PyTree, x, config: HybridSSMMoEConfig):
    """The tied head over the rows held here, over ``logits_scaling``."""
    h = rms_norm(x, params["lnf"], config.eps, config.dtype)
    with jax.named_scope("head"):
        return jnp.einsum("...d,vd->...v", h,
                          params["wte"].astype(config.dtype),
                          preferred_element_type=jnp.float32) \
            / config.logits_scaling


# -------------------------------------------------------------------- init

def init(config: HybridSSMMoEConfig, rng: jax.Array,
         std: float = 0.02) -> PyTree:
    """Seeded weights.  Matrices into a layer draw ``std``, matrices back to
    the residual stream ``std / sqrt(2 L)``.  The state-space layers' ``dt``
    bias, ``A_log`` and ``D`` as the published initialisation draws them:
    ``dt`` log-uniform in [0.001, 0.1] through the inverse softplus, ``A``
    uniform in [1, 16], ``D`` one."""
    d, v = config.d_model, config.padded_vocab
    pdt = config.param_dtype
    E = len(config.held)
    out_std = std / math.sqrt(2 * config.n_layer)

    def normal(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(pdt)

    def run_init(key, kind, n):
        k = jax.random.split(key, 12)
        p = {"ln1": jnp.ones((n, d), pdt), "ln2": jnp.ones((n, d), pdt),
             "router": normal(k[0], (n, d, config.n_experts), std),
             "w_gu": normal(k[1], (n, E, d, 2 * config.d_expert), std),
             "w_down": normal(k[2], (n, E, config.d_expert, d), out_std),
             "ws_gu": normal(k[3], (n, d, 2 * config.d_shared), std),
             "ws_down": normal(k[4], (n, config.d_shared, d), out_std)}
        if kind == ATTENTION:
            hq = config.n_head * config.head_dim
            hkv = config.n_kv_head * config.head_dim
            p.update({"wq": normal(k[5], (n, d, hq), std),
                      "wk": normal(k[6], (n, d, hkv), std),
                      "wv": normal(k[7], (n, d, hkv), std),
                      "wo": normal(k[8], (n, hq, d), out_std)})
            return p
        H, di, dc = config.ssm_heads, config.d_inner, config.d_conv
        K = config.conv_kernel
        dt = jnp.exp(jax.random.uniform(k[9], (n, H), jnp.float32)
                     * (math.log(0.1) - math.log(0.001)) + math.log(0.001))
        p.update({
            "w_in": normal(k[5], (n, d, di + dc), std),
            "w_dt": normal(k[11], (n, d, H), std),
            "conv_w": (jax.random.uniform(k[6], (n, K, dc), jnp.float32,
                                          -1.0, 1.0) / math.sqrt(K)
                       ).astype(pdt),
            "conv_b": normal(k[7], (n, dc), std),
            # the inverse of softplus, so that softplus(dt_bias) = dt
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt),
            "A_log": jnp.log(jax.random.uniform(
                k[10], (n, H), jnp.float32, 1.0, 16.0)).astype(pdt),
            "D": jnp.ones((n, H), pdt),
            "norm_g": jnp.ones((n, di), pdt),
            "w_out": normal(k[8], (n, di, d), out_std)})
        return p

    keys = jax.random.split(rng, len(config.runs) + 1)
    return {"wte": normal(keys[0], (v, d), std),
            "runs": [run_init(key, kind, n)
                     for key, (kind, _, n) in zip(keys[1:], config.runs)],
            "lnf": jnp.ones((d,), pdt)}


def logical_axes(config: HybridSSMMoEConfig) -> PyTree:
    def run_axes(kind):
        p = {"ln1": (LAYERS, EMBED), "ln2": (LAYERS, EMBED),
             "router": (LAYERS, EMBED, None),
             "w_gu": (LAYERS, EXPERT, EMBED, MLP),
             "w_down": (LAYERS, EXPERT, MLP, EMBED),
             "ws_gu": (LAYERS, EMBED, MLP), "ws_down": (LAYERS, MLP, EMBED)}
        if kind == ATTENTION:
            p.update({"wq": (LAYERS, EMBED, HEADS),
                      "wk": (LAYERS, EMBED, HEADS),
                      "wv": (LAYERS, EMBED, HEADS),
                      "wo": (LAYERS, HEADS, EMBED)})
        else:
            # the gated norm runs over all channels of a token: the mixer
            # is not sliced by head
            p.update({"w_in": (LAYERS, EMBED, None),
                      "w_dt": (LAYERS, EMBED, None),
                      "conv_w": (LAYERS, None, None),
                      "conv_b": (LAYERS, None), "dt_bias": (LAYERS, None),
                      "A_log": (LAYERS, None), "D": (LAYERS, None),
                      "norm_g": (LAYERS, None),
                      "w_out": (LAYERS, None, EMBED)})
        return p
    return {"wte": (VOCAB, EMBED), "lnf": (EMBED,),
            "runs": [run_axes(kind) for kind, _, _ in config.runs]}


# ------------------------------------------------------------------- apply

def _causal_attention(q, k, v, config: HybridSSMMoEConfig):
    """Dense grouped-head attention of a whole sequence over itself."""
    B, S, H, D = q.shape
    G = H // config.n_kv_head
    qg = q.reshape(B, S, config.n_kv_head, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * config.attn_scale
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v).reshape(B, S, H, D)


def apply(params: PyTree, tokens, config: HybridSSMMoEConfig):
    """Full-sequence logits ``[B, S, padded_vocab]`` float32, no cache: the
    state-space layers scan from a zero state."""
    B = tokens.shape[0]
    x = embed(params, tokens, config)
    zero_tail = jnp.zeros((B, config.conv_kernel - 1, config.d_conv),
                          config.dtype)
    zero_state = jnp.zeros((1, B, config.ssm_state, config.d_inner),
                           jnp.float32)

    def mamba_layer(x, p):
        z, u, dt = ssm_inputs(x, p, config)
        u_act, _ = ssm.causal_conv(u, zero_tail, p["conv_w"], p["conv_b"])
        v, dt, a, Bm, Cm = ssm_scan_inputs(u_act, dt, p, config)
        y, _ = ssm.ssd_chunk_scan(zero_state, 0, v, dt, a, Bm, Cm,
                                  chunk=config.ssm_chunk)
        x = ssm_output(x, y, v, z, p, config)
        return expert_ffn(x, p, config)[0], None

    def attention_layer(x, p):
        q, (k, v) = attention_project(x, p, config)
        x = attention_output(x, _causal_attention(q, k, v, config), p, config)
        return expert_ffn(x, p, config)[0], None

    for (kind, _, _), stack in zip(config.runs, params["runs"]):
        x, _ = lax.scan(mamba_layer if kind == MAMBA else attention_layer,
                        x, stack)
    return lm_logits(params, x, config)
