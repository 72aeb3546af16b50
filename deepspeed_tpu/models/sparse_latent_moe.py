"""A latent-attention, routed-expert decoder whose layers are of two kinds:
full layers that attend to a LEARNED SELECTION of the conversation, and
window layers that keep a short latent ring with widths of their own.

What differs from ``models/latent_moe.py``'s block, mechanism by mechanism:

- **per-layer kinds** (``layer_types``): parameters are a list of RUNS in
  depth order, a run a UNIT of kinds repeated (``hybrid_ssm_moe.layer_units``
  over kind and FFN form: a dense first layer is a run of its own, a full
  layer and three window layers twice one run of one scan), one stack per
  position of the unit, so each position knows its kind statically;
- **two sets of widths** (``Dims``): a full layer's latent attention
  (``n_head``, ``q_rank``, ``kv_rank``, ``d_nope``, ``d_rope``, ``d_v``,
  ``rope_theta``) and a window layer's (the ``w_`` fields); both
  ``latent_moe``'s one algorithm in the form a pass's shape picks
  (absorbed: a tick, a few tokens, ``apply``; up-projected: a served
  chunk, each kind by its own widths, ``latent_moe.up_projection``), with
  plain rotary frequencies, pairs interleaved.  With ``lora_rescale`` the
  two low-rank latents leave their norms multiplied by ``sqrt(d_model /
  rank)``;
- **the index** (full layers): ``index_heads`` small heads score every
  earlier token, ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s]) *
  index_heads^-1/2 * index_dim^-1/2`` with ``q_I = W_qI c_q``, ``k_I =
  LayerNorm(W_kI h)`` (one key a token, the first ``d_rope`` elements of
  both rotated, halves paired) and ``w = W_w h``; a query attends to the
  ``min(t + 1, index_topk)`` tokens of largest ``I[t, .]`` and no other,
  exactly, ties to the lower position (``ops/pallas/decode_attention.py``:
  ``index_scores``, ``topk_bias``).  The index keys are a second bank of
  the full layers' cache;
- **the window**: a window layer's query at ``t`` sees ``t - window < s <=
  t`` (``window`` counts the query);
- **the gate**: every head's output (after the value up-projection, before
  ``W_o``) is multiplied by ``sigmoid(W_g h)``, one scalar a head;
- the **FFN**: ``latent_moe``'s own (a dense SwiGLU in the first
  ``first_k_dense`` layers, then ``moe/held_experts.py``'s sigmoid router,
  held experts and a shared one), RMSNorm and the float32 residual stream
  as there; an untied head.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import latent_moe
from .hybrid_ssm_moe import layer_units, run_parts
from .latent_moe import lora_rescale, rms_norm
from .partitioning import EMBED, EXPERT, HEADS, KV, LAYERS, MLP, VOCAB

PyTree = Any

FULL, WINDOW = "full", "window"
#: a layer's label in ``SparseLatentMoEConfig.labels``: its attention kind,
#: and ``+dense`` where its FFN is the dense one
DENSE = "+dense"
#: the routed experts' two stacks among a layer's parameters
ROUTED = ("w_gu", "w_down")
#: the index's LayerNorm's epsilon (the published indexer's default)
INDEX_NORM_EPS = 1e-6


class Dims(NamedTuple):
    """One kind of layer's latent attention."""
    n_head: int
    q_rank: int
    kv_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    rope_theta: float

    @property
    def row_elements(self) -> int:
        """What the mathematics keeps of a cached token in a layer."""
        return self.kv_rank + self.d_rope

    @property
    def lanes(self) -> int:
        """``row_elements`` in whole lane rows: how the row is stored."""
        return latent_moe.lane_rows(self.row_elements)

    @property
    def cache_row(self) -> Tuple[int, ...]:
        """``(lanes,)``: the name ``latent_moe.chunk_form`` reads a
        config's stored row under."""
        return (self.lanes,)

    @property
    def softmax_scale(self) -> float:
        return 1.0 / math.sqrt(self.d_nope + self.d_rope)


@dataclasses.dataclass(frozen=True)
class SparseLatentMoEConfig:
    #: the module whose ``FAMILY`` ``models.cache_family`` serves this
    #: config through
    cache_family = "sparse_latent_moe_inference"

    vocab_size: int = 1024
    max_seq_len: int = 1024
    layer_types: Tuple[str, ...] = (FULL, FULL, WINDOW, WINDOW)
    d_model: int = 64
    d_ff: int = 256                 # the leading dense layers' SwiGLU width
    d_expert: int = 32
    # a full layer's latent attention
    n_head: int = 4
    q_rank: int = 48
    kv_rank: int = 32
    d_nope: int = 16
    d_rope: int = 8
    d_v: int = 16
    rope_theta: float = 10000.0
    # its index
    index_heads: int = 4
    index_dim: int = 16
    index_topk: int = 8
    # a window layer's latent attention
    w_n_head: int = 2
    w_q_rank: int = 48
    w_kv_rank: int = 40
    w_d_nope: int = 24
    w_d_rope: int = 8
    w_d_v: int = 16
    w_rope_theta: float = 10000.0
    #: a window layer's query at ``t`` sees ``t - window < s <= t``
    window: int = 5
    #: the low-rank latents leave their norms times ``sqrt(d_model / rank)``
    lora_rescale: bool = True
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
        assert all(t in (FULL, WINDOW) for t in self.layer_types), \
            self.layer_types
        assert 0 <= self.first_k_dense <= self.n_layer
        assert self.d_rope % 2 == 0 and self.w_d_rope % 2 == 0
        assert self.d_rope <= self.index_dim, "the index rotates d_rope dims"
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

    def dims(self, kind: str) -> Dims:
        if kind == FULL:
            return Dims(self.n_head, self.q_rank, self.kv_rank, self.d_nope,
                        self.d_rope, self.d_v, self.rope_theta)
        return Dims(self.w_n_head, self.w_q_rank, self.w_kv_rank,
                    self.w_d_nope, self.w_d_rope, self.w_d_v,
                    self.w_rope_theta)

    def rescale(self, rank: int) -> float:
        return lora_rescale(self.d_model, rank) if self.lora_rescale else 1.0

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.layer_types)

    @property
    def labels(self) -> Tuple[str, ...]:
        """Each layer's kind, ``+dense`` on the leading dense layers."""
        return tuple(t + DENSE * (i < self.first_k_dense)
                     for i, t in enumerate(self.layer_types))

    @property
    def runs(self):
        """The runs in depth order, ``(unit, firsts, n)`` each: ``unit`` the
        labels of one repetition, ``n`` the repetitions, ``firsts[j]`` the
        index of position ``j``'s first layer in the pool of its attention
        kind (full layers own the banks, window layers the rings), so that
        repetition ``i`` of position ``j`` is layer ``firsts[j] + i *
        (layers of that kind in the unit)`` of its pool."""
        out, seen = [], {FULL: 0, WINDOW: 0}
        for unit, _, n in layer_units(self.labels):
            kinds = [label.split("+")[0] for label in unit]
            out.append((unit, tuple(seen[k] + kinds[:j].count(k)
                                    for j, k in enumerate(kinds)), n))
            for k in kinds:
                seen[k] += n
        return tuple(out)

    # ---- what the one cache family asks of a config
    @property
    def cache_row(self) -> Tuple[int, ...]:
        """A full layer's cached token: the latent row ``[c | R(k_r)]`` in
        whole lane rows, and the index key."""
        return (self.dims(FULL).lanes, self.index_dim)

    @property
    def cache_sweep_row(self) -> Tuple[int, ...]:
        """Of those banks, what the single-token sweep streams by blocks:
        the latent row (the index reads the other bank whole)."""
        return self.cache_row[:1]

    @staticmethod
    def cache_second_sweep_block(max_len: int) -> Optional[int]:
        """The block of the index's sweep over its bank of keys."""
        from ..ops.pallas.decode_attention import index_block_k
        return index_block_k(max_len)

    @property
    def cache_layers(self) -> int:
        return self.count(FULL)

    @property
    def ring_cells(self) -> int:
        """Cells of a window layer's ring: the window in whole blocks of the
        latent sweep (128 tokens), a small one in whole sublanes; the cells
        past the window are masked, to the token."""
        tile = 128 if self.window >= 128 else 8
        return -(-self.window // tile) * tile

    @property
    def cache_ring(self) -> Optional[Tuple[int, int]]:
        n = self.count(WINDOW)
        return (n, self.ring_cells) if n else None

    @property
    def cache_ring_row(self) -> Tuple[int, ...]:
        """A ring's cell: a window layer's latent row, another width than
        the banks'."""
        return (self.dims(WINDOW).lanes,)


# ------------------------------------------------------------------ pieces

def rotate(x, positions, theta: float, interleaved: bool = True):
    """Rotary embedding of ``x`` [B, S, ..., D] at ``positions`` ([S] or
    [B, S]) by plain frequencies ``theta^(-2j/D)``, in float32: pairs
    interleaved (dims ``2j`` and ``2j+1``; the latent attention's) or halves
    paired (``j`` with ``j + D/2``; the index's)."""
    D = x.shape[-1]
    freq = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = positions.astype(jnp.float32)[..., None] * freq
    if ang.ndim == 2:
        ang = ang[None]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + ang.shape[-1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = x32[..., 0::2], x32[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        return out.reshape(x.shape).astype(x.dtype)
    x1, x2 = x32[..., :D // 2], x32[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def layer_norm(x, scale, bias, eps):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    return ((x32 - mu) * lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


class Index(NamedTuple):
    """A full layer's index queries: ``q`` [B, S, index_heads, index_dim]
    and the heads' weights ``w`` [B, S, index_heads] float32, the score's
    two scales folded in."""
    q: jnp.ndarray
    w: jnp.ndarray


class Queries(NamedTuple):
    """What a layer's attention is asked with: ``q`` the latent attention's
    queries [B, S, H, .] (absorbed; un-absorbed where ``up`` is given);
    ``index``: a full layer's :class:`Index`, None on a window layer;
    ``up``: the layer's ``decode_attention.LatentUp`` where the pass takes
    the up-projected form (``latent_moe.up_projection``), else None."""
    q: jnp.ndarray
    index: Optional[Index] = None
    up: Any = None


def chunk_form(config: "SparseLatentMoEConfig", S: int) -> str:
    """``latent_moe.chunk_form`` for a stack of two kinds of layer, each
    with widths of its own: a span holds one name, so ``"up_projected"``
    only where the latent chunk call of EVERY kind the stack has takes that
    form at ``S`` positions, else ``"absorbed"``."""
    forms = {latent_moe.chunk_form(config.dims(kind), S)
             for kind in set(config.layer_types)}
    return forms.pop() if len(forms) == 1 else "absorbed"


def attention_project(x, p, config: SparseLatentMoEConfig, positions,
                      kind: str):
    """One layer's attention inputs from ``x`` [B, S, d]: the absorbed
    queries ``[q' | R(q_r)]`` [B, S, H, lanes] and the token's cache row
    ``[c | R(k_r)]`` [B, S, lanes], both zero past ``row_elements``; on a
    full layer also the index's queries (:class:`Index`) and the token's
    index key [B, S, index_dim]: ``Queries, (row[, key])``.  A pass that
    takes the up-projected form (``p`` brings its head-major stack,
    ``latent_moe.with_up``, and its positions pay) gets its queries
    UN-ABSORBED (``latent_moe.unabsorbed``) beside the layer's ``LatentUp``.
    """
    cdt = config.dtype
    dm = config.dims(kind)
    r = dm.kv_rank
    h = rms_norm(x, p["ln1"], config.eps, cdt)
    c_q = rms_norm(jnp.einsum("bsd,dr->bsr", h, p["wq_a"].astype(cdt)),
                   p["q_norm"], config.eps, jnp.float32)
    c_q = (c_q * config.rescale(dm.q_rank)).astype(cdt)
    q = jnp.einsum("bsr,rhe->bshe", c_q, p["wq_b"].astype(cdt))
    q_n, q_r = q[..., :dm.d_nope], q[..., dm.d_nope:]
    kv = jnp.einsum("bsd,dr->bsr", h, p["wkv_a"].astype(cdt))
    c = rms_norm(kv[..., :r], p["kv_norm"], config.eps, jnp.float32)
    c = (c * config.rescale(r)).astype(cdt)
    k_r = rotate(kv[..., r:], positions, dm.rope_theta)
    q_r = rotate(q_r, positions, dm.rope_theta)
    pad = dm.lanes - dm.row_elements
    up = latent_moe.up_projection(p, dm, x.shape[1])
    if up is not None:
        queries = latent_moe.unabsorbed(q_n, q_r)
    else:
        # absorb the key up-projection into the query
        q_abs = jnp.einsum("bshe,rhe->bshr", q_n,
                           p["wkv_b"][..., :dm.d_nope].astype(cdt))
        queries = jnp.concatenate(
            [q_abs, q_r]
            + ([jnp.zeros(q_abs.shape[:3] + (pad,), cdt)] if pad else []),
            -1)
    row = jnp.concatenate(
        [c, k_r] + ([jnp.zeros(c.shape[:2] + (pad,), cdt)] if pad else []),
        -1)
    if kind != FULL:
        return Queries(queries, up=up), (row,)
    with jax.named_scope("index_project"):
        dr = config.d_rope
        q_i = jnp.einsum("bsr,rhe->bshe", c_q, p["wi_q"].astype(cdt))
        q_i = jnp.concatenate(
            [rotate(q_i[..., :dr], positions, dm.rope_theta, False),
             q_i[..., dr:]], -1)
        k_i = layer_norm(jnp.einsum("bsd,de->bse", h, p["wi_k"].astype(cdt)),
                         p["wi_k_scale"], p["wi_k_bias"], INDEX_NORM_EPS)
        k_i = jnp.concatenate(
            [rotate(k_i[..., :dr], positions, dm.rope_theta, False),
             k_i[..., dr:]], -1)
        w = jnp.einsum("bsd,dh->bsh", h, p["wi_w"].astype(cdt),
                       preferred_element_type=jnp.float32) \
            * (config.index_heads ** -0.5 * config.index_dim ** -0.5)
    return Queries(queries, Index(q_i, w), up), (row, k_i)


def head_gate(h, w_gate, cdt):
    """``sigmoid(W_g h)`` [B, S, H] float32: one scalar a head."""
    return jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", h, w_gate.astype(cdt),
                                     preferred_element_type=jnp.float32))


@jax.named_scope("attn_out")
def attention_output(x, weighed, p, config: SparseLatentMoEConfig, kind: str):
    """``x + W_o concat_h(g_h * W_kvb[v] (sum_s p c))``: ``weighed`` [B, S,
    H, kv_rank] is each head's probability-weighted sum of latent rows
    (from a pass in the up-projected form, ``latent_moe.up_projection``, the
    heads' outputs [B, S, H, d_v] already), ``g = sigmoid(W_g norm_1(x))``
    one scalar a head."""
    cdt = config.dtype
    dm = config.dims(kind)
    g = head_gate(rms_norm(x, p["ln1"], config.eps, cdt), p["w_gate"], cdt)
    v = weighed.astype(cdt)
    if latent_moe.up_projection(p, dm, x.shape[1]) is None:
        v = jnp.einsum("bshr,rhe->bshe", v,
                       p["wkv_b"][..., dm.d_nope:].astype(cdt),
                       preferred_element_type=jnp.float32)
    v = (v * g[..., None]).astype(cdt)
    return x + jnp.einsum("bshe,hed->bsd", v, p["wo"].astype(cdt),
                          preferred_element_type=jnp.float32)


def ffn(x, p, config: SparseLatentMoEConfig, label: str, experts=None,
        layer=None):
    """A layer's FFN half by its label: ``(x, counts or None)``, the
    ``latent_moe`` functions at this config (the fields they read have its
    names)."""
    if label.endswith(DENSE):
        return latent_moe.dense_ffn(x, p, config), None
    return latent_moe.expert_ffn(x, p, config, experts=experts, layer=layer)


def embed(params: PyTree, tokens, config: SparseLatentMoEConfig):
    return params["wte"][tokens].astype(jnp.float32)


def lm_logits(params: PyTree, x, config: SparseLatentMoEConfig):
    """The untied head over the rows of the vocabulary held here."""
    h = rms_norm(x, params["lnf"], config.eps, config.dtype)
    with jax.named_scope("head"):
        return jnp.einsum("...d,vd->...v", h,
                          params["head"].astype(config.dtype),
                          preferred_element_type=jnp.float32)


# -------------------------------------------------------------------- init

def init(config: SparseLatentMoEConfig, rng: jax.Array, std: float = 0.02,
         routed_out_std: Optional[float] = None,
         embed_std: Optional[float] = None,
         up_std: Optional[float] = None,
         full_out_scale: float = 1.0) -> PyTree:
    """Seeded weights.  Matrices into a layer draw ``std``, matrices back to
    the residual stream ``std / sqrt(2 L)`` (the routed experts'
    down-projection ``routed_out_std`` where one is given; a full layer's
    ``W_o`` times ``full_out_scale``), the embedding ``embed_std`` and the
    two up-projections out of the latents (``W_qb``, ``W_kvb``) ``up_std``
    (each default ``std``), norm weights one, biases zero."""
    d, v = config.d_model, config.padded_vocab
    pdt = config.param_dtype
    E, f = len(config.held), config.d_expert
    out_std = std / math.sqrt(2 * config.n_layer)
    routed_std = out_std if routed_out_std is None else routed_out_std
    up_std = std if up_std is None else up_std

    def normal(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(pdt)

    def part_init(key, n, label):
        kind = label.split("+")[0]
        dm = config.dims(kind)
        k = jax.random.split(key, 16)
        p = {"ln1": jnp.ones((n, d), pdt), "ln2": jnp.ones((n, d), pdt),
             "wq_a": normal(k[0], (n, d, dm.q_rank), std),
             "q_norm": jnp.ones((n, dm.q_rank), pdt),
             "wq_b": normal(k[1], (n, dm.q_rank, dm.n_head,
                                   dm.d_nope + dm.d_rope), up_std),
             "wkv_a": normal(k[2], (n, d, dm.kv_rank + dm.d_rope), std),
             "kv_norm": jnp.ones((n, dm.kv_rank), pdt),
             "wkv_b": normal(k[3], (n, dm.kv_rank, dm.n_head,
                                    dm.d_nope + dm.d_v), up_std),
             "wo": normal(k[4], (n, dm.n_head, dm.d_v, d), out_std * (
                 full_out_scale if kind == FULL else 1.0)),
             "w_gate": normal(k[5], (n, d, dm.n_head), std)}
        if kind == FULL:
            Hi, Di = config.index_heads, config.index_dim
            p.update(wi_q=normal(k[6], (n, dm.q_rank, Hi, Di), std),
                     wi_k=normal(k[7], (n, d, Di), std),
                     wi_k_scale=jnp.ones((n, Di), pdt),
                     wi_k_bias=jnp.zeros((n, Di), pdt),
                     wi_w=normal(k[8], (n, d, Hi), std))
        if label.endswith(DENSE):
            p.update(w_gu=normal(k[9], (n, d, 2 * config.d_ff), std),
                     w_down=normal(k[10], (n, config.d_ff, d), out_std))
            return p
        f_s = f * config.n_shared_experts
        p.update(router=normal(k[9], (n, d, config.n_experts), std),
                 # small and not zero, so that the bias is exercised
                 router_bias=normal(k[10], (n, config.n_experts), 0.01),
                 w_gu=normal(k[11], (n, E, d, 2 * f), std),
                 w_down=normal(k[12], (n, E, f, d), routed_std),
                 ws_gu=normal(k[13], (n, d, 2 * f_s), std),
                 ws_down=normal(k[14], (n, f_s, d), out_std))
        return p

    def run_init(key, unit, n):
        if len(unit) == 1:
            return part_init(key, n, unit[0])
        return [part_init(k, n, label)
                for k, label in zip(jax.random.split(key, len(unit)), unit)]

    keys = jax.random.split(rng, len(config.runs) + 2)
    return {"wte": normal(keys[0], (v, d),
                          std if embed_std is None else embed_std),
            "head": normal(keys[1], (v, d), std),
            "runs": [run_init(key, unit, n) for key, (unit, _, n)
                     in zip(keys[2:], config.runs)],
            "lnf": jnp.ones((d,), pdt)}


def logical_axes(config: SparseLatentMoEConfig) -> PyTree:
    def part(label):
        p = {"ln1": (LAYERS, EMBED), "ln2": (LAYERS, EMBED),
             "wq_a": (LAYERS, EMBED, None), "q_norm": (LAYERS, None),
             "wq_b": (LAYERS, None, HEADS, KV),
             "wkv_a": (LAYERS, EMBED, None), "kv_norm": (LAYERS, None),
             "wkv_b": (LAYERS, None, HEADS, KV),
             "wo": (LAYERS, HEADS, KV, EMBED),
             "w_gate": (LAYERS, EMBED, HEADS)}
        if label.startswith(FULL):
            p.update(wi_q=(LAYERS, None, None, None),
                     wi_k=(LAYERS, EMBED, None), wi_k_scale=(LAYERS, None),
                     wi_k_bias=(LAYERS, None), wi_w=(LAYERS, EMBED, None))
        if label.endswith(DENSE):
            p.update(w_gu=(LAYERS, EMBED, MLP), w_down=(LAYERS, MLP, EMBED))
        else:
            p.update(router=(LAYERS, EMBED, None),
                     router_bias=(LAYERS, None),
                     w_gu=(LAYERS, EXPERT, EMBED, MLP),
                     w_down=(LAYERS, EXPERT, MLP, EMBED),
                     ws_gu=(LAYERS, EMBED, MLP),
                     ws_down=(LAYERS, MLP, EMBED))
        return p

    return {"wte": (VOCAB, EMBED), "head": (VOCAB, EMBED), "lnf": (EMBED,),
            "runs": [part(unit[0]) if len(unit) == 1
                     else [part(label) for label in unit]
                     for unit, _, _ in config.runs]}


# ------------------------------------------------------------------- apply

def _causal_attention(q, fresh, config: SparseLatentMoEConfig, kind: str):
    """Dense absorbed attention of a whole sequence over its own rows: a
    window layer's banded, a full layer's over each query's selection."""
    from ..ops.pallas.decode_attention import index_scores, topk_bias
    S = fresh[0].shape[1]
    dist = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
    q, index = q.q, q.index
    if kind == FULL:
        row, k_i = fresh
        scores = index_scores(index.q, index.w, k_i)
        bias = topk_bias(scores, jnp.arange(S)[None], config.index_topk)
    else:
        row, = fresh
        bias = jnp.where((dist >= 0) & (dist < config.window), 0.0,
                         -jnp.inf)[None]
    dm = config.dims(kind)
    s = jnp.einsum("bqhr,bkr->bhqk", q, row,
                   preferred_element_type=jnp.float32) * dm.softmax_scale
    p = jax.nn.softmax(s + bias[:, None], axis=-1).astype(row.dtype)
    return jnp.einsum("bhqk,bkr->bqhr", p, row[..., :dm.kv_rank])


def apply(params: PyTree, tokens, config: SparseLatentMoEConfig):
    """Full-sequence logits ``[B, S, padded_vocab]`` float32, no cache."""
    positions = jnp.arange(tokens.shape[1])
    x = embed(params, tokens, config)

    for (unit, _, _), run in zip(config.runs, params["runs"]):
        def repetition(x, parts, unit=unit):
            for label, p in zip(unit, parts):
                kind = label.split("+")[0]
                q, fresh = attention_project(x, p, config, positions, kind)
                x = attention_output(
                    x, _causal_attention(q, fresh, config, kind), p, config,
                    kind)
                x = ffn(x, p, config, label)[0]
            return x, None

        x, _ = lax.scan(repetition, x, tuple(run_parts(run)))
    return lm_logits(params, x, config)
