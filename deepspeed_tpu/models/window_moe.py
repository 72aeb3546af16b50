"""A decoder whose attention layers are of two kinds, window and full, with
rotary grouped heads and an expert layer in every block.

What differs from the tree's other blocks, mechanism by mechanism:

- **per-layer kinds** (``layer_types``): ``"window"`` layers see the last
  ``window`` tokens (``0 <= i - j < window``), ``"full"`` layers the whole
  conversation.  Parameters are a list of RUNS in depth order, a run a UNIT
  of kinds repeated (``hybrid_ssm_moe.layer_units``: three window layers and
  a full one, seven times, is one run of one scan), one stack per position
  of the unit, so each position knows its kind statically;
- **two pools** (``models/window_moe_inference.py``): a full layer caches
  whole rows, a window layer a ring of ``window`` cells a slot;
- **grouped heads that rotate**: ``n_head`` query heads on ``n_kv_head``
  key-value heads; with ``qk_norm`` q and k of every head are RMS-normalised
  over the head's elements with a learned weight; then both rotate over all
  of the head (halves paired: element ``i`` with ``i + D/2``) by one of TWO
  tables: plain frequencies on window layers, YaRN's on full ones (``yarn``:
  factor, original positions, beta_fast, beta_slow, attention_factor; the
  frequencies are ``latent_moe.yarn_inv_freq``'s, cosines and sines times
  ``attention_factor``).  Keys are cached rotated.  The four attention
  matrices are stored HEAD-MAJOR, ``[layers, heads, D, d_model]``, and
  multiplied as such (``bsd,hed->bshe``; ``bshe,hed->bsd``): stored ``[d,
  heads * D]`` the compiler re-laid ``W_q`` model-dimension-minor for the
  head-split queries its consumers want, 19 MB a layer of every tick and
  four stacks of 132 MB held through every admission (compiler, PR 48);
- the **expert layer** (``moe/held_experts.py``): a float32 router over all
  ``n_experts``, the ``experts_per_token`` largest logits, weights a softmax
  over the chosen (``route_softmax``), SwiGLU experts, no shared expert;
- RMSNorm and the residual stream as ``latent_moe``'s (``rms_norm``; the
  stream, a sum over layers, float32; softmax, norms and router float32,
  every product's inputs ``config.dtype``); an untied head.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..moe.held_experts import held_experts_ffn, pair_counts, route_softmax
from .hybrid_ssm_moe import layer_units, run_parts
from .latent_moe import rms_norm, yarn_inv_freq
from .partitioning import EMBED, EXPERT, HEADS, LAYERS, MLP, VOCAB

PyTree = Any

WINDOW, FULL = "window", "full"
#: the routed experts' two stacks among a layer's parameters
ROUTED = ("w_gu", "w_down")


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    #: the module whose ``FAMILY`` ``models.cache_family`` serves this
    #: config through
    cache_family = "window_moe_inference"

    vocab_size: int = 1024
    max_seq_len: int = 1024
    layer_types: Tuple[str, ...] = (WINDOW, WINDOW, FULL) * 2
    d_model: int = 64
    n_head: int = 4
    n_kv_head: int = 2
    head_dim: int = 16
    #: a window layer's query at ``i`` sees the keys at ``i - window < j <= i``
    window: int = 16
    #: q and k of every head RMS-normalised with a learned weight
    qk_norm: bool = True
    rope_theta: float = 10000.0
    #: the full layers' table: (factor, original positions, beta_fast,
    #: beta_slow, attention_factor), or None for the window layers' plain one
    yarn: Optional[Tuple[float, int, float, float, float]] = None
    n_experts: int = 16             # the router's width: every expert there is
    experts_per_token: int = 4
    d_expert: int = 32
    #: global ids of the experts held here (None: all of them)
    held_experts: Optional[Tuple[int, ...]] = None
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    vocab_round_to: int = 128

    def __post_init__(self):
        assert all(t in (WINDOW, FULL) for t in self.layer_types), \
            self.layer_types
        assert self.n_head % self.n_kv_head == 0 and self.head_dim % 2 == 0
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
    def attn_scale(self) -> float:
        return 1.0 / math.sqrt(self.head_dim)

    @property
    def units(self):
        """The runs, ``(unit, firsts, n)`` each: ``layer_units``."""
        return layer_units(self.layer_types)

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    # ---- what the one cache family asks of a config
    @property
    def cache_row(self) -> Tuple[int, ...]:
        """A cached token of either kind of layer: K and V of the key-value
        heads (never repeated out to the query heads)."""
        return (self.n_kv_head * self.head_dim,) * 2

    @property
    def cache_layers(self) -> int:
        """Layers that own whole rows in the banks: the full layers."""
        return self.count(FULL)

    @property
    def cache_ring(self) -> Optional[Tuple[int, int]]:
        """The second pool: the window layers, a ring of ``window`` cells a
        slot each."""
        n = self.count(WINDOW)
        return (n, self.window) if n else None


# ------------------------------------------------------------------ pieces

def rotate(x, positions, config: WindowMoEConfig, kind: str):
    """Rotary embedding of ``x`` [B, S, heads, D] at ``positions`` ([S] or
    [B, S]) over the whole head, halves paired, in float32: by the plain
    table on a window layer, by YaRN's on a full one (its cosines and sines
    times ``attention_factor``)."""
    yarn = config.yarn if kind == FULL else None
    D = x.shape[-1]
    ang = positions.astype(jnp.float32)[..., None] \
        * yarn_inv_freq(D, config.rope_theta, yarn)
    if ang.ndim == 2:
        ang = ang[None]
    ang = ang[:, :, None, :]
    factor = 1.0 if yarn is None else yarn[4]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    # element ``i`` meets ``i + D/2`` as a roll of the whole head by half
    # (``x cos + roll(x) [-sin | sin]``); two half-heads cut out and glued
    # back compile to a tick of the same length (PERF.md 6, PR 48: it was
    # the matrices' layout, not this, that re-laid ``W_q``)
    x32 = x.astype(jnp.float32)
    return (x32 * jnp.concatenate([cos, cos], -1)
            + jnp.roll(x32, D // 2, axis=-1)
            * jnp.concatenate([-sin, sin], -1)).astype(x.dtype)


def attention_project(x, p, config: WindowMoEConfig, positions, kind: str):
    """Queries [B, S, n_head, D] and the cached row's K and V [B, S,
    n_kv_head, D] of ``norm_1(x)``: no bias, q and k normalised per head
    (``qk_norm``) and rotated by ``kind``'s table."""
    cdt = config.dtype
    h = rms_norm(x, p["ln1"], config.eps, cdt)
    q, k, v = (jnp.einsum("bsd,hed->bshe", h, p[w].astype(cdt))
               for w in ("wq", "wk", "wv"))
    if config.qk_norm:
        q = rms_norm(q, p["q_norm"], config.eps)
        k = rms_norm(k, p["k_norm"], config.eps)
    return (rotate(q, positions, config, kind),
            (rotate(k, positions, config, kind), v))


@jax.named_scope("attn_out")
def attention_output(x, attn, p, config: WindowMoEConfig):
    """``x + W_o attn``: ``attn`` [B, S, n_head, D]."""
    cdt = config.dtype
    return x + jnp.einsum("bshe,hed->bsd", attn.astype(cdt),
                          p["wo"].astype(cdt),
                          preferred_element_type=jnp.float32)


def expert_ffn(x, p, config: WindowMoEConfig, experts=None, layer=None):
    """The expert layer: ``x + routed(norm_2(x))``.  ``experts`` / ``layer``
    and the counts ``[n_pair_counts(n_held)]`` are
    ``latent_moe.expert_ffn``'s: pairs held here, pairs routed, held experts
    that took a pair, pairs per held expert, pages of pairs run beyond the
    first."""
    B, S, d = x.shape
    k = config.experts_per_token
    with jax.named_scope("moe_router"):
        h32 = rms_norm(x, p["ln2"], config.eps, jnp.float32)
        flat = h32.astype(config.dtype).reshape(B * S, d)
        # the router reads the norm's own float32 output, not its rounding
        routing = route_softmax(h32.reshape(B * S, d), p["router"], k)
    routed, per_expert = held_experts_ffn(
        flat, routing, experts if experts is not None else p, config.held,
        config.n_experts, layer=layer if experts is not None else None)
    return (x + routed.reshape(B, S, d).astype(jnp.float32),
            pair_counts(per_expert, B * S * k))


def embed(params: PyTree, tokens, config: WindowMoEConfig):
    return params["wte"][tokens].astype(jnp.float32)


def lm_logits(params: PyTree, x, config: WindowMoEConfig):
    """The untied head over the rows of the vocabulary held here."""
    with jax.named_scope("head"):
        h = rms_norm(x, params["lnf"], config.eps, config.dtype)
        return jnp.einsum("...d,vd->...v", h,
                          params["head"].astype(config.dtype),
                          preferred_element_type=jnp.float32)


# -------------------------------------------------------------------- init

def init(config: WindowMoEConfig, rng: jax.Array, std: float = 0.02,
         routed_out_std: Optional[float] = None,
         embed_std: Optional[float] = None) -> PyTree:
    """Seeded weights.  Matrices into a layer draw ``std``, matrices back to
    the residual stream ``std / sqrt(2 L)`` (the routed experts'
    down-projection ``routed_out_std`` where one is given), the embedding
    ``embed_std`` (default ``std``), norm weights one."""
    d, v = config.d_model, config.padded_vocab
    pdt = config.param_dtype
    E, f = len(config.held), config.d_expert
    H, Hkv, D = config.n_head, config.n_kv_head, config.head_dim
    out_std = std / math.sqrt(2 * config.n_layer)
    routed_std = out_std if routed_out_std is None else routed_out_std

    def normal(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(pdt)

    def part_init(key, n):
        k = jax.random.split(key, 7)
        p = {"ln1": jnp.ones((n, d), pdt), "ln2": jnp.ones((n, d), pdt),
             "wq": normal(k[0], (n, H, D, d), std),
             "wk": normal(k[1], (n, Hkv, D, d), std),
             "wv": normal(k[2], (n, Hkv, D, d), std),
             "wo": normal(k[3], (n, H, D, d), out_std),
             "router": normal(k[4], (n, d, config.n_experts), std),
             "w_gu": normal(k[5], (n, E, d, 2 * f), std),
             "w_down": normal(k[6], (n, E, f, d), routed_std)}
        if config.qk_norm:
            p["q_norm"] = jnp.ones((n, config.head_dim), pdt)
            p["k_norm"] = jnp.ones((n, config.head_dim), pdt)
        return p

    def run_init(key, unit, n):
        if len(unit) == 1:
            return part_init(key, n)
        return [part_init(k, n) for k in jax.random.split(key, len(unit))]

    keys = jax.random.split(rng, len(config.units) + 2)
    return {"wte": normal(keys[0], (v, d),
                          std if embed_std is None else embed_std),
            "head": normal(keys[1], (v, d), std),
            "runs": [run_init(key, unit, n) for key, (unit, _, n)
                     in zip(keys[2:], config.units)],
            "lnf": jnp.ones((d,), pdt)}


def logical_axes(config: WindowMoEConfig) -> PyTree:
    part = {"ln1": (LAYERS, EMBED), "ln2": (LAYERS, EMBED),
            "wq": (LAYERS, HEADS, None, EMBED),
            "wk": (LAYERS, HEADS, None, EMBED),
            "wv": (LAYERS, HEADS, None, EMBED),
            "wo": (LAYERS, HEADS, None, EMBED),
            "router": (LAYERS, EMBED, None),
            "w_gu": (LAYERS, EXPERT, EMBED, MLP),
            "w_down": (LAYERS, EXPERT, MLP, EMBED)}
    if config.qk_norm:
        part.update(q_norm=(LAYERS, None), k_norm=(LAYERS, None))
    return {"wte": (VOCAB, EMBED), "head": (VOCAB, EMBED), "lnf": (EMBED,),
            "runs": [dict(part) if len(unit) == 1
                     else [dict(part) for _ in unit]
                     for unit, _, _ in config.units]}


# ------------------------------------------------------------------- apply

def _causal_attention(q, k, v, config: WindowMoEConfig, kind: str):
    """Dense grouped-head attention of a whole sequence over itself, a
    window layer's banded."""
    B, S, H, D = q.shape
    G = H // config.n_kv_head
    qg = q.reshape(B, S, config.n_kv_head, G, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * config.attn_scale
    dist = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    seen = dist >= 0
    if kind == WINDOW:
        seen = seen & (dist < config.window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v).reshape(B, S, H, D)


def apply(params: PyTree, tokens, config: WindowMoEConfig):
    """Full-sequence logits ``[B, S, padded_vocab]`` float32, no cache."""
    positions = jnp.arange(tokens.shape[1])
    x = embed(params, tokens, config)

    for (unit, _, _), run in zip(config.units, params["runs"]):
        def repetition(x, parts, unit=unit):
            for kind, p in zip(unit, parts):
                q, (k, v) = attention_project(x, p, config, positions, kind)
                x = attention_output(
                    x, _causal_attention(q, k, v, config, kind), p, config)
                x = expert_ffn(x, p, config)[0]
            return x, None

        x, _ = lax.scan(repetition, x, tuple(run_parts(run)))
    return lm_logits(params, x, config)
