"""A hybrid state-space / attention decoder with routed experts: mostly
Mamba-2 mixers, an attention layer now and then, many small routed experts
beside a shared MLP.  Two published blocks are configurations of it: the
Granite-4.0-H generation's, where EVERY layer is a mixer followed by the
expert layer (two norms), and the Nemotron-H generation's, where a layer is
ONE part (a mixer or the expert layer alone, one norm).

What differs from the tree's other blocks, mechanism by mechanism:

- **per-layer kinds** (``layer_types``): ``"mamba"``, ``"attention"`` or,
  where ``mixer_ffn`` is off, ``"experts"``.  Parameters are a list of RUNS
  in depth order, a run being a UNIT of kinds repeated (``config.units``:
  ``mamba x 5``, or ``(mamba, experts) x 3`` where the kinds alternate), one
  stack per position of the unit, so a run is one scan however the kinds
  interleave; nothing assumes a period or a pattern;
- the **state-space mixer** (Mamba-2): ``[z | u | dt] = W_in h``; a causal
  depthwise convolution of width ``conv_kernel`` and SiLU on ``u``; ``[v | B
  | C] = u`` with ``B`` and ``C`` ``ssm_groups`` columns each, head ``h``
  reading its group's; per head ``dt = softplus(dt + dt_bias)``, ``a =
  -exp(A_log)``, state ``H_t = exp(dt_t a) H_{t-1} + dt_t v_t B_t^T``, ``y_t
  = H_t C_t + D v_t``; ``y = norm_g(y * silu(z))``, the mean square taken
  over each group's channels apart (one group: over all); ``W_out y``.  The
  scan runs chunked (``ops/pallas/ssm.py``);
- **attention** with grouped key-value heads, **no positional encoding**
  and a fixed score scale (``attn_scale``), no bias;
- the **expert layer** (``moe/held_experts.py``): a float32 router over all
  ``n_experts`` and one of its two gates (``gate``: ``"softmax"`` of the
  chosen logits, or the ``"sigmoid"`` gate with its selection bias and
  ``routed_scale``), the pairs that land on the experts held here
  multiplied grouped, and a shared MLP beside them; an expert is
  ``expert_form`` ``"swiglu"`` (three matrices, gate beside up) or
  ``"relu2"`` (two: ``W_down relu(W_up h)^2``);
- **multipliers**: the embedding times ``embedding_multiplier``, every
  residual branch times ``residual_multiplier``, the logits over
  ``logits_scaling``; the head is the embedding, or with ``tie_head`` off a
  matrix of its own;
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

from ..moe.held_experts import (RELU2, SWIGLU, held_experts_ffn, pair_counts,
                                route, route_softmax)
from ..ops.pallas import ssm
from .latent_moe import rms_norm, swiglu as gated_mlp
from .partitioning import EMBED, EXPERT, HEADS, LAYERS, MLP, VOCAB

PyTree = Any

MAMBA, ATTENTION, EXPERTS = "mamba", "attention", "experts"
#: layers in the largest unit a run may repeat: a scan's body holds the
#: unit's layers inline, each compiled once, so a small unit that repeats is
#: worth more than a long one that repeats twice
MAX_UNIT = 4


#: the suffix of a layer's label where its FFN is the dense one, in the
#: families whose labels say more than the mixer (``kda+dense``)
DENSE = "+dense"


def mixer_of(label: str) -> str:
    """A label's mixer: what precedes ``+dense``."""
    return label.split("+")[0]


def layer_units(types: Tuple[str, ...], max_unit: int = MAX_UNIT,
                kind=lambda label: label):
    """``types`` (a stack's kinds in depth order) as runs: ``(unit, firsts,
    n)`` each, see ``HybridSSMMoEConfig.units``.  ``kind``: what a label's
    layers are counted by in ``firsts`` where a label says more than its
    mixer (``kda+dense`` and ``kda`` index ONE state stack)."""
    out: List[Tuple[Tuple[str, ...], Tuple[int, ...], int]] = []
    seen = dict.fromkeys(map(kind, types), 0)
    i = 0
    while i < len(types):
        u, n = 1, 1
        for length in range(1, max_unit + 1):
            unit, times = types[i:i + length], 1
            while types[i + times * length:
                        i + (times + 1) * length] == unit:
                times += 1
            if (times > 1 or length == 1) and times * length > u * n:
                u, n = length, times
        unit = tuple(types[i:i + u])
        kinds = [kind(label) for label in unit]
        out.append((unit, tuple(seen[k] + kinds[:j].count(k)
                                for j, k in enumerate(kinds)), n))
        for k in kinds:
            seen[k] += n
        i += u * n
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class HybridSSMMoEConfig:
    #: the module whose ``FAMILY`` ``models.cache_family`` serves this
    #: config through
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
    #: groups of ``B`` and ``C`` (and of the gated norm): consecutive heads
    ssm_groups: int = 1
    # attention
    n_head: int = 4
    n_kv_head: int = 2
    head_dim: int = 16
    attn_scale: float = 1.0 / 16
    # the expert layer
    n_experts: int = 16             # the router's width: every expert there is
    experts_per_token: int = 4
    d_expert: int = 32              # one routed expert's width
    d_shared: int = 64              # the shared MLP's width
    #: global ids of the experts held here (None: all of them)
    held_experts: Optional[Tuple[int, ...]] = None
    #: True: every layer is a mixer AND the expert layer (norms ``ln1`` and
    #: ``ln2``); False: a layer is one part with one norm, and the expert
    #: layers stand in ``layer_types`` as ``"experts"``
    mixer_ffn: bool = True
    expert_form: str = SWIGLU       # or RELU2: up, relu squared, down
    gate: str = "softmax"           # or "sigmoid": ``held_experts.route``
    routed_scale: float = 1.0       # the sigmoid gate's
    tie_head: bool = True
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    vocab_round_to: int = 128

    def __post_init__(self):
        kinds = (MAMBA, ATTENTION) + (() if self.mixer_ffn else (EXPERTS,))
        assert all(t in kinds for t in self.layer_types), self.layer_types
        assert self.n_head % self.n_kv_head == 0
        assert self.ssm_heads % self.ssm_groups == 0
        assert self.expert_form in (SWIGLU, RELU2), self.expert_form
        assert self.gate in ("softmax", "sigmoid"), self.gate
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
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def d_expert_stored(self) -> int:
        """A routed expert's width as STORED: a two-matrix expert's is
        rounded up to whole 128-lane rows and the rest is zero (an
        activation of 0 adds nothing), so that the grouped matmul's tiles
        divide it (1856 = 14.5 rows is stored as 1920); the packed
        gate-and-up stack is stored as it is."""
        if self.expert_form == SWIGLU:
            return self.d_expert
        return -(-self.d_expert // 128) * 128

    @property
    def units(self) -> Tuple[Tuple[Tuple[str, ...], Tuple[int, ...], int], ...]:
        """The runs, in depth order: ``(unit, firsts, n)``, a unit of at
        most ``MAX_UNIT`` kinds repeated ``n`` times; ``firsts[j]`` is the
        index, among the layers of its kind, of position ``j``'s layer in
        the first repetition (repetition ``i``'s: ``firsts[j] + i *
        unit.count(unit[j])``).  From each depth the unit that covers the
        most layers is taken, a unit of several kinds only where it repeats,
        so ``MMMMMAMMMM`` reads ``M x 5, A, M x 4`` and ``MEMEM*EMEMEM*E``
        reads ``(ME) x 2, M, *, (EM) x 3, *, E``."""
        return layer_units(self.layer_types)

    @property
    def runs(self) -> Tuple[Tuple[str, int, int], ...]:
        """``units`` of a config whose every unit is one kind: ``(kind,
        first layer among its kind, layers)`` for each run of consecutive
        layers of that kind."""
        assert all(len(unit) == 1 for unit, _, _ in self.units)
        return tuple((unit[0], firsts[0], n)
                     for unit, firsts, n in self.units)

    @property
    def routed_keys(self) -> Tuple[str, str]:
        """The routed experts' two stacks among a layer's parameters."""
        return ("w_gu" if self.expert_form == SWIGLU else "w_up", "w_down")

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

def run_parts(run) -> list:
    """A run's parameters as one dict per position of its unit: a unit of
    one kind is stored as that dict itself (the layout the one-kind runs
    have always had), a unit of several as a list of them."""
    return [run] if isinstance(run, dict) else list(run)


def has_ffn(kind: str, config: HybridSSMMoEConfig) -> bool:
    """Whether a layer of ``kind`` holds the expert layer."""
    return kind == EXPERTS or config.mixer_ffn


def ssm_inputs(x, p, config: HybridSSMMoEConfig):
    """``[z | u | dt] = W_in norm_1(x)``: the gate ``z`` [B, S, d_inner] and
    the convolution's pre-activation input ``u`` [B, S, d_conv] in
    ``config.dtype``, ``dt`` [B, S, heads] float32 before its bias.  The
    published matrix is kept as two, ``w_in`` (``z | u``) and ``w_dt``: one
    product that returned float32 for ``dt``'s sake wrote the other 16,640
    columns out in float32 and again in ``config.dtype``."""
    cdt = config.dtype
    with jax.named_scope("ssm_in_proj"):
        h = rms_norm(x, p["ln1"], config.eps, cdt)
        zu = jnp.einsum("bsd,df->bsf", h, p["w_in"].astype(cdt))
        dt = jnp.einsum("bsd,dh->bsh", h, p["w_dt"].astype(cdt),
                        preferred_element_type=jnp.float32)
    di = config.d_inner
    return zu[..., :di], zu[..., di:], dt


def ssm_scan_inputs(u_act, dt, p, config: HybridSSMMoEConfig):
    """From the convolution's output ``u_act`` [B, S, d_conv] float32 and
    the raw ``dt``: ``(v [B, S, heads, head_dim], dt [B, S, heads] float32
    after bias and softplus, a [heads] float32, B, C [B, S, groups *
    d_state])``, group by group."""
    cdt = config.dtype
    di, N = config.d_inner, config.ssm_groups * config.ssm_state
    u_act = u_act.astype(cdt)
    v = u_act[..., :di].reshape(
        u_act.shape[:2] + (config.ssm_heads, config.ssm_head_dim))
    dt = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    return v, dt, a, u_act[..., di:di + N], u_act[..., di + N:]


def ssm_output(x, y, v, z, p, config: HybridSSMMoEConfig):
    """``x + r W_out norm_g((y + D v) * silu(z))``: ``y`` [B, S, d_inner]
    float32 is ``H_t C_t``; the norm's mean square is each group's own."""
    cdt = config.dtype
    P, G = config.ssm_head_dim, config.ssm_groups
    with jax.named_scope("ssm_gated_norm"):
        d_wide = jnp.repeat(p["D"].astype(jnp.float32), P)
        y = y + d_wide * v.reshape(y.shape).astype(jnp.float32)
        y = y * jax.nn.silu(z.astype(jnp.float32))
        # a group's channels on an axis of their own: the norm's last
        y = rms_norm(y.reshape(y.shape[:-1] + (G, -1)),
                     p["norm_g"].reshape(G, -1), config.eps,
                     cdt).reshape(z.shape)
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


def relu2_mlp(h, w_up, w_down, cdt):
    """``W_d relu(W_u h)^2``: products in ``cdt``, the result float32."""
    up = jnp.einsum("...d,df->...f", h, w_up.astype(cdt))
    act = jnp.square(jax.nn.relu(up.astype(jnp.float32))).astype(cdt)
    return jnp.einsum("...f,fd->...d", act, w_down.astype(cdt),
                      preferred_element_type=jnp.float32)


def expert_ffn(x, p, config: HybridSSMMoEConfig, experts=None, layer=None):
    """The expert layer: ``x + r (routed(h) + shared(h))``, ``h =
    norm_2(x)``.  ``experts`` / ``layer`` and the counts
    ``[n_pair_counts(n_held)]`` are ``latent_moe.expert_ffn``'s: pairs held
    here, pairs routed, held experts that took a pair, pairs per held
    expert, pages of pairs run beyond the first."""
    B, S, d = x.shape
    k = config.experts_per_token
    with jax.named_scope("moe_router"):
        h32 = rms_norm(x, p["ln2"], config.eps, jnp.float32)
        h = h32.astype(config.dtype)
        flat = h.reshape(B * S, d)
        # the router reads the norm's own float32 output, not its rounding
        if config.gate == "sigmoid":
            routing = route(h32.reshape(B * S, d), p["router"],
                            p["router_bias"], k, config.routed_scale)
        else:
            routing = route_softmax(h32.reshape(B * S, d), p["router"], k)
    routed, per_expert = held_experts_ffn(
        flat, routing, experts if experts is not None else p, config.held,
        config.n_experts, layer=layer if experts is not None else None,
        form=config.expert_form)
    with jax.named_scope("moe_shared"):
        if config.expert_form == SWIGLU:
            shared = gated_mlp(h, p["ws_gu"], p["ws_down"], config.dtype)
        else:
            shared = relu2_mlp(h, p["ws_up"], p["ws_down"], config.dtype)
        out = routed.reshape(B, S, d).astype(jnp.float32) + shared
        x = x + config.residual_multiplier * out
    return x, pair_counts(per_expert, B * S * k)


def embed(params: PyTree, tokens, config: HybridSSMMoEConfig):
    return config.embedding_multiplier \
        * params["wte"][tokens].astype(jnp.float32)


def lm_logits(params: PyTree, x, config: HybridSSMMoEConfig):
    """The head (the embedding where it is tied) over the rows held here,
    over ``logits_scaling``."""
    with jax.named_scope("head"):
        h = rms_norm(x, params["lnf"], config.eps, config.dtype)
        w = params["wte" if config.tie_head else "head"]
        return jnp.einsum("...d,vd->...v", h, w.astype(config.dtype),
                          preferred_element_type=jnp.float32) \
            / config.logits_scaling


# -------------------------------------------------------------------- init

def init(config: HybridSSMMoEConfig, rng: jax.Array, std: float = 0.02,
         routed_out_std: Optional[float] = None,
         embed_std: Optional[float] = None) -> PyTree:
    """Seeded weights.  Matrices into a layer draw ``std``, matrices back to
    the residual stream ``std / sqrt(2 L)`` (the routed experts'
    down-projection ``routed_out_std`` where one is given), the embedding
    ``embed_std`` (default ``std``).  The state-space layers' ``dt``
    bias, ``A_log`` and ``D`` as the published initialisation draws them:
    ``dt`` log-uniform in [0.001, 0.1] through the inverse softplus, ``A``
    uniform in [1, 16], ``D`` one."""
    d, v = config.d_model, config.padded_vocab
    pdt = config.param_dtype
    E = len(config.held)
    out_std = std / math.sqrt(2 * config.n_layer)
    routed_std = out_std if routed_out_std is None else routed_out_std

    def normal(key, shape, s):
        return (jax.random.normal(key, shape, jnp.float32) * s).astype(pdt)

    def ffn_init(key, k, n):
        f, fs = config.d_expert, config.d_expert_stored
        p = {"ln2": jnp.ones((n, d), pdt),
             "router": normal(k[0], (n, d, config.n_experts), std),
             "ws_down": normal(k[4], (n, config.d_shared, d), out_std)}
        if config.gate == "sigmoid":    # moves the selection, never a weight
            p["router_bias"] = normal(jax.random.fold_in(key, 12),
                                      (n, config.n_experts), 0.01)
        if config.expert_form == SWIGLU:
            p.update({"w_gu": normal(k[1], (n, E, d, 2 * f), std),
                      "w_down": normal(k[2], (n, E, f, d), routed_std),
                      "ws_gu": normal(k[3], (n, d, 2 * config.d_shared),
                                      std)})
        else:       # the padding's columns and rows are zero
            real = (jnp.arange(fs) < f).astype(pdt)
            p.update({"w_up": normal(k[1], (n, E, d, fs), std) * real,
                      "w_down": normal(k[2], (n, E, fs, d), routed_std)
                      * real[:, None],
                      "ws_up": normal(k[3], (n, d, config.d_shared), std)})
        return p

    def part_init(key, kind, n):
        k = jax.random.split(key, 12)
        p = ffn_init(key, k, n) if has_ffn(kind, config) else {}
        if kind == EXPERTS:
            return p
        p["ln1"] = jnp.ones((n, d), pdt)
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

    def run_init(key, unit, n):
        if len(unit) == 1:
            return part_init(key, unit[0], n)
        return [part_init(k, kind, n)
                for k, kind in zip(jax.random.split(key, len(unit)), unit)]

    keys = jax.random.split(rng, len(config.units) + 1)
    params = {"wte": normal(keys[0], (v, d),
                            std if embed_std is None else embed_std),
              "runs": [run_init(key, unit, n) for key, (unit, _, n)
                       in zip(keys[1:], config.units)],
              "lnf": jnp.ones((d,), pdt)}
    if not config.tie_head:
        params["head"] = normal(jax.random.fold_in(rng, 1), (v, d), std)
    return params


def logical_axes(config: HybridSSMMoEConfig) -> PyTree:
    def part_axes(kind):
        p = {}
        if has_ffn(kind, config):
            up, shared_up = config.routed_keys[0], \
                "ws_gu" if config.expert_form == SWIGLU else "ws_up"
            p = {"ln2": (LAYERS, EMBED), "router": (LAYERS, EMBED, None),
                 up: (LAYERS, EXPERT, EMBED, MLP),
                 "w_down": (LAYERS, EXPERT, MLP, EMBED),
                 shared_up: (LAYERS, EMBED, MLP),
                 "ws_down": (LAYERS, MLP, EMBED)}
            if config.gate == "sigmoid":
                p["router_bias"] = (LAYERS, None)
        if kind == EXPERTS:
            return p
        p["ln1"] = (LAYERS, EMBED)
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

    def run_axes(unit):
        return part_axes(unit[0]) if len(unit) == 1 else \
            [part_axes(kind) for kind in unit]

    axes = {"wte": (VOCAB, EMBED), "lnf": (EMBED,),
            "runs": [run_axes(unit) for unit, _, _ in config.units]}
    if not config.tie_head:
        axes["head"] = (VOCAB, EMBED)
    return axes


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

    def layer(x, p, kind):
        if kind == MAMBA:
            z, u, dt = ssm_inputs(x, p, config)
            u_act, _ = ssm.causal_conv(u, zero_tail, p["conv_w"],
                                       p["conv_b"])
            v, dt, a, Bm, Cm = ssm_scan_inputs(u_act, dt, p, config)
            y, _ = ssm.ssd_chunk_scan(zero_state, 0, v, dt, a, Bm, Cm,
                                      chunk=config.ssm_chunk,
                                      groups=config.ssm_groups)
            x = ssm_output(x, y, v, z, p, config)
        elif kind == ATTENTION:
            q, (k, v) = attention_project(x, p, config)
            x = attention_output(x, _causal_attention(q, k, v, config), p,
                                 config)
        return expert_ffn(x, p, config)[0] if has_ffn(kind, config) else x

    for (unit, _, _), run in zip(config.units, params["runs"]):
        def repetition(x, parts, unit=unit):
            for kind, p in zip(unit, parts):
                x = layer(x, p, kind)
            return x, None

        x, _ = lax.scan(repetition, x, tuple(run_parts(run)))
    return lm_logits(params, x, config)
