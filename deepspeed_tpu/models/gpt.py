"""GPT-2/3-family causal transformer, TPU-first.

This is the flagship model family used by the benchmark configs
(BASELINE.md: GPT-2 125M/1.3B/13B, GPT-3 6.7B).  Design choices that differ
deliberately from a torch port:

- **scan over layers**: block params are stacked on a leading ``layers`` dim
  and the decoder body is one ``lax.scan`` — compile time is O(1) in depth,
  and the stacked layout is exactly what pipeline partitioning slices.
- **logical axes**: every param carries logical axis names
  (``models/partitioning.py``) so TP/FSDP/MoE shardings are rule-table swaps.
- **bf16 compute, fp32 logits/loss**: matmuls in ``config.dtype`` feed the
  MXU; the loss path upcasts, matching the reference's fp16 master-weight
  discipline without loss-scale fragility on TPU.
- **remat**: ``config.remat`` wraps each block in ``jax.checkpoint`` — the
  counterpart of the reference's activation checkpointing
  (runtime/activation_checkpointing/checkpointing.py:499).
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .partitioning import EMBED, HEADS, KV, LAYERS, MLP, SEQ, VOCAB
from ..utils.logging import logger

PyTree = Any


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    d_ff: Optional[int] = None          # default 4*d_model
    dtype: Any = jnp.bfloat16           # activation/compute dtype
    param_dtype: Any = jnp.float32      # storage dtype of master params
    dropout: float = 0.0
    remat: bool = False
    # jax.checkpoint policy when remat is on: "nothing" recomputes the
    # whole block (min memory); "dots" saves matmul outputs with no batch
    # dims; "attn_out" saves the [B,S,H,D] attention outputs (~48MB/layer)
    # so the downstream block tail needn't recompute them.  The flash
    # kernel's logsumexp residual is tagged ``ds_attn_lse`` inside its
    # custom_vjp and saved beside the output under "dots" and "attn_out",
    # so the backward does not replay the forward kernel under those two.
    remat_policy: str = "nothing"       # nothing | dots | attn_out
    # lax.scan unroll factor for the layer stack (XLA can overlap/fuse
    # across unrolled iterations at the cost of program size)
    scan_unroll: int = 1
    # a cap on the positions of each sequence a chunk of the loss holds.
    # The head and the cross-entropy run over [B, chunk, V] float32 logits
    # at a time under their own backward rule wherever the whole [B, S, V]
    # would not be small (``_loss_layout``: the chunk follows from the
    # bytes); this caps it further.  0: no cap.
    loss_chunk: int = 0
    use_flash_attention: bool = True    # pallas kernel when available
    vocab_round_to: int = 128           # pad vocab to a lane multiple
    sequence_parallel: Optional[str] = None  # None | 'ring' | 'ulysses'
    # activation fake-quant hook set by compression.init_compression
    # (reference basic_layer.py activation quantization)
    act_quant_bits: Optional[int] = None
    act_quant_symmetric: bool = True
    # a SparsityConfig instance routes attention through the block-sparse
    # kernel (reference SparseSelfAttention in BERT-style models)
    sparse_attention: Optional[Any] = None
    # ---- architecture variants (covering the reference's injection-policy
    # breadth: GPT-2/OPT learned positions, BLOOM alibi, NeoX/GPT-J rotary)
    pos_embed: str = "learned"          # learned | rotary | alibi | none
    rotary_pct: float = 1.0             # NeoX rotates only a fraction
    rotary_base: float = 10000.0
    rotary_interleaved: bool = False    # GPT-J pairs dims; NeoX splits halves
    activation: str = "gelu"            # gelu | relu
    parallel_residual: bool = False     # NeoX: x + attn(ln1 x) + mlp(ln2 x)
    # GPT-Neo (reference HFGPTNEOLayerPolicy, replace_policy.py:255): no
    # 1/sqrt(Dh) softmax scaling, and every other layer attends through a
    # banded causal window instead of the full prefix
    attn_softmax_scale: Optional[float] = None  # None → 1/sqrt(head_dim)
    local_attention_window: int = 0     # >0: banded-causal window width
    local_attention_alternating: bool = False   # odd layers local (GPT-Neo)
    tie_word_embeddings: bool = True    # False -> separate lm_head param
    lm_head_bias: bool = False          # GPT-J: untied head carries a bias
    pos_offset: int = 0                 # OPT stores positions offset by 2
    embed_layernorm: bool = False       # BLOOM's word_embeddings_layernorm

    def __post_init__(self):
        assert self.remat_policy in ("nothing", "dots", "attn_out"), \
            f"unknown remat_policy {self.remat_policy!r}"
        # alibi routes attention through its own biased-dense path; make the
        # non-composition with SP/sparse kernels loud rather than silently
        # ignoring the configured parallelism (same policy as the pipeline
        # config's asserts)
        if self.pos_embed == "alibi":
            assert not self.sequence_parallel, \
                "alibi attention does not compose with sequence_parallel yet"
            assert self.sparse_attention is None, \
                "alibi attention does not compose with sparse_attention yet"

    @property
    def ffn_dim(self) -> int:
        return self.d_ff if self.d_ff is not None else 4 * self.d_model

    @property
    def head_dim(self) -> int:
        assert self.d_model % self.n_head == 0
        return self.d_model // self.n_head

    @property
    def padded_vocab(self) -> int:
        r = self.vocab_round_to
        return ((self.vocab_size + r - 1) // r) * r

    def num_params(self) -> int:
        d, v, L = self.d_model, self.padded_vocab, self.n_layer
        per_layer = (4 * d * d + 3 * d) + (2 * d * self.ffn_dim + d + self.ffn_dim) + 4 * d
        return v * d + self.max_seq_len * d + L * per_layer + 2 * d


# canonical size presets (BASELINE.md tracked configs)
GPT2_125M = GPTConfig(n_layer=12, n_head=12, d_model=768)
GPT2_350M = GPTConfig(n_layer=24, n_head=16, d_model=1024)
GPT2_760M = GPTConfig(n_layer=24, n_head=16, d_model=1536)
GPT2_1_3B = GPTConfig(n_layer=24, n_head=32, d_model=2048)
GPT2_2_7B = GPTConfig(n_layer=32, n_head=32, d_model=2560)
GPT3_6_7B = GPTConfig(n_layer=32, n_head=32, d_model=4096, max_seq_len=2048)
GPT2_13B = GPTConfig(n_layer=40, n_head=40, d_model=5120, max_seq_len=2048)

PRESETS = {
    "gpt2-125m": GPT2_125M,
    "gpt2-350m": GPT2_350M,
    "gpt2-760m": GPT2_760M,
    "gpt2-1.3b": GPT2_1_3B,
    "gpt2-2.7b": GPT2_2_7B,
    "gpt3-6.7b": GPT3_6_7B,
    "gpt2-13b": GPT2_13B,
}


# --------------------------------------------------------------------- init

def _normal(rng, shape, std, dtype):
    return (jax.random.normal(rng, shape) * std).astype(dtype)


def init(config: GPTConfig, rng: jax.Array) -> PyTree:
    """Materialize the parameter tree (use under jax.eval_shape for zero.Init)."""
    d, v, L = config.d_model, config.padded_vocab, config.n_layer
    h, hd, f = config.n_head, config.head_dim, config.ffn_dim
    pdt = config.param_dtype
    std = 0.02
    resid_std = std / math.sqrt(2 * L)
    keys = jax.random.split(rng, 8)

    block = {
        "ln1_scale": jnp.ones((L, d), pdt),
        "ln1_bias": jnp.zeros((L, d), pdt),
        "wqkv": _normal(keys[0], (L, d, 3, h, hd), std, pdt),
        "bqkv": jnp.zeros((L, 3, h, hd), pdt),
        "wo": _normal(keys[1], (L, h, hd, d), resid_std, pdt),
        "bo": jnp.zeros((L, d), pdt),
        "ln2_scale": jnp.ones((L, d), pdt),
        "ln2_bias": jnp.zeros((L, d), pdt),
        "wi": _normal(keys[2], (L, d, f), std, pdt),
        "bi": jnp.zeros((L, f), pdt),
        "wo_mlp": _normal(keys[3], (L, f, d), resid_std, pdt),
        "bo_mlp": jnp.zeros((L, d), pdt),
    }
    params = {
        "wte": _normal(keys[4], (v, d), std, pdt),
        "blocks": block,
        "lnf_scale": jnp.ones((d,), pdt),
        "lnf_bias": jnp.zeros((d,), pdt),
    }
    if config.pos_embed == "learned":
        params["wpe"] = _normal(
            keys[5], (config.max_seq_len + config.pos_offset, d), std, pdt)
    if not config.tie_word_embeddings:
        params["lm_head"] = _normal(keys[6], (v, d), std, pdt)
        if config.lm_head_bias:
            params["lm_head_bias"] = jnp.zeros((v,), pdt)
    if config.embed_layernorm:
        params["emb_ln_scale"] = jnp.ones((d,), pdt)
        params["emb_ln_bias"] = jnp.zeros((d,), pdt)
    return params


def logical_axes(config: GPTConfig) -> PyTree:
    """Per-dim logical axis names mirroring ``init``'s tree."""
    axes = {
        "wte": (VOCAB, EMBED),
        "blocks": {
            "ln1_scale": (LAYERS, EMBED),
            "ln1_bias": (LAYERS, EMBED),
            "wqkv": (LAYERS, EMBED, None, HEADS, KV),
            "bqkv": (LAYERS, None, HEADS, KV),
            "wo": (LAYERS, HEADS, KV, EMBED),
            "bo": (LAYERS, EMBED),
            "ln2_scale": (LAYERS, EMBED),
            "ln2_bias": (LAYERS, EMBED),
            "wi": (LAYERS, EMBED, MLP),
            "bi": (LAYERS, MLP),
            "wo_mlp": (LAYERS, MLP, EMBED),
            "bo_mlp": (LAYERS, EMBED),
        },
        "lnf_scale": (EMBED,),
        "lnf_bias": (EMBED,),
    }
    if config.pos_embed == "learned":
        axes["wpe"] = (SEQ, EMBED)
    if not config.tie_word_embeddings:
        axes["lm_head"] = (VOCAB, EMBED)
        if config.lm_head_bias:
            axes["lm_head_bias"] = (VOCAB,)
    if config.embed_layernorm:
        axes["emb_ln_scale"] = (EMBED,)
        axes["emb_ln_bias"] = (EMBED,)
    return axes


# -------------------------------------------------------------------- apply

@jax.named_scope("norm")
def _layer_norm(x, scale, bias, eps=1e-5):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)


def _rotate(x, positions, config: GPTConfig):
    """Rotary position embedding on [B, S, H, D].

    ``rotary_pct`` < 1 rotates only the leading fraction of head dims
    (NeoX); ``rotary_interleaved`` pairs (0,1),(2,3)… dims (GPT-J) instead
    of the NeoX half-split (i, i+rot/2) convention.
    """
    D = x.shape[-1]
    rot = int(D * config.rotary_pct) // 2 * 2
    if rot == 0:
        return x
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    inv = 1.0 / (config.rotary_base **
                 (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    # positions: [S] (shared) or [B, S] (per-row, ragged decode)
    ang = positions.astype(jnp.float32)[..., None] * inv   # [..., S, rot/2]
    if ang.ndim == 2:
        ang = ang[None]                                    # [1, S, rot/2]
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    if config.rotary_interleaved:
        x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
        out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
        out = out.reshape(x_rot.shape)
    else:
        x1, x2 = x_rot[..., :rot // 2], x_rot[..., rot // 2:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
    return jnp.concatenate([out, x_pass], axis=-1)


def alibi_slopes(n_head: int) -> jnp.ndarray:
    """ALiBi per-head slopes (Press et al.): geometric from 2^(-8/n); the
    non-power-of-two tail interleaves slopes of the doubled ladder."""
    def pow2_slopes(n):
        start = 2.0 ** (-8.0 / n)
        return [start ** (i + 1) for i in range(n)]

    floor = 1 << (n_head.bit_length() - 1)  # largest power of two <= n_head
    if floor == n_head:
        slopes = pow2_slopes(n_head)
    else:
        slopes = pow2_slopes(floor)
        slopes += pow2_slopes(2 * floor)[0::2][:n_head - floor]
    return jnp.asarray(slopes, jnp.float32)


def _alibi_attention(q, k, v, config: GPTConfig, q_positions=None):
    """Dense causal attention with the ALiBi bias (BLOOM family).
    q: [B,Sq,H,D] at absolute positions q_positions — [Sq] shared or
    [B,Sq] per-row (ragged decode); default end-aligned."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    q_pos = (jnp.arange(Sq) + (Sk - Sq)) if q_positions is None else q_positions
    q_pos = jnp.atleast_2d(q_pos)                                # [B or 1, Sq]
    k_pos = jnp.arange(Sk)
    # bias = -slope * distance; 0 on the diagonal
    dist = q_pos[:, :, None] - k_pos[None, None, :]              # [B?, Sq, Sk]
    bias = -alibi_slopes(H)[None, :, None, None] * \
        dist[:, None].astype(jnp.float32)
    s = s + bias
    mask = dist >= 0
    s = jnp.where(mask[:, None], s, float("-inf"))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)


def _activation_fn(x, config: GPTConfig):
    if config.activation == "relu":
        return jax.nn.relu(x)
    if config.activation == "quick_gelu":   # CLIP: x * sigmoid(1.702 x)
        return x * jax.nn.sigmoid(1.702 * x)
    if config.activation == "gelu_exact":   # HF 'gelu' = erf form
        return jax.nn.gelu(x, approximate=False)
    return jax.nn.gelu(x, approximate=True)  # HF 'gelu_new' (GPT-2/J/Neo)


def _dropout(x, rate: float, key):
    if key is None or rate <= 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, jnp.zeros_like(x))


def _windowed_attention(q, k, v, config: GPTConfig, window, pos=None):
    """Dense banded-causal attention: key j visible to query i iff
    0 <= i - j < window (GPT-Neo local layers; window may be a traced
    per-layer scalar so the alternating stack stays one `lax.scan`).

    ``pos``: absolute position of the first query — scalar or [B] (ragged
    decode against a padded KV cache); defaults to end-aligned
    ``Sk - Sq`` (training / prefill on unpadded K/V).  One implementation
    serves train, prefill, and cached decode.
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    scale = config.attn_softmax_scale
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    pos_arr = jnp.asarray(Sk - Sq if pos is None else pos)
    steps = jnp.arange(Sq)
    q_pos = pos_arr[:, None] + steps if pos_arr.ndim else pos_arr + steps
    q_pos = jnp.atleast_2d(q_pos)                          # [B or 1, Sq]
    dist = q_pos[:, :, None] - jnp.arange(Sk)[None, None, :]
    mask = (dist >= 0) & (dist < window)
    s = jnp.where(mask[:, None], s, float("-inf"))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)


def layer_window(config: GPTConfig, idx, full):
    """Per-layer attention window (or None): GPT-Neo's alternating
    global/local stack as one traced scalar — the single source of the
    alternation rule for train, prefill, and decode."""
    if config.local_attention_window <= 0:
        return None
    return jnp.where((idx % 2 == 1) | ~jnp.asarray(
        config.local_attention_alternating),
        config.local_attention_window, full)


def _attention(q, k, v, config: GPTConfig, window=None):
    """Causal MHA. q,k,v: [B, S, H, D].  ``window`` (optional traced
    scalar) routes through the banded-causal dense path; in an
    alternating stack the global layers (window >= S) keep the
    memory-linear flash path via ``lax.cond`` — only the truly banded
    layers materialize dense scores.

    Every path's output is name-tagged "ds_attn_out", once, so
    ``remat_policy="attn_out"`` saves it regardless of variant:
    ``flash_attention`` tags its own result (the kernel's, which IS the
    array the output product reads, or its dense fallback's); every other
    path is tagged here.
    """
    with jax.named_scope("attention"):
        return _attention_impl(q, k, v, config, window)


def _tagged(attn):
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(attn, "ds_attn_out")


def _attention_impl(q, k, v, config: GPTConfig, window=None):
    if window is not None:
        # long sequences take the banded flash kernel: O(S·window) FLOPs
        # at O(block) memory, tiles below the band skipped; a traced
        # window >= S degenerates to pure causal, so ONE kernel serves
        # the whole alternating global/local stack (no lax.cond)
        from ..ops.pallas import flash_attention as _fa
        from ..ops.pallas.flash_attention import (FLASH_MIN_SEQ, _pick_block,
                                                  resolve_env_blocks,
                                                  use_pallas)
        Sq, Sk = q.shape[1], k.shape[1]
        # resolve the same env-derived blocks flash_attention will use, so
        # this guard and the kernel's own tiling check can never disagree
        # (a FLASH_BLOCK_Q override must fall back here, not ValueError
        # inside the no-dense-fallback window path)
        _bq, _bk = resolve_env_blocks()
        if (config.use_flash_attention and use_pallas()
                and Sq >= FLASH_MIN_SEQ and Sq <= Sk
                and _pick_block(Sq, _bq) and _pick_block(Sk, _bk)):
            return _fa(q, k, v, causal=True,
                       sm_scale=config.attn_softmax_scale, window=window)
        if config.local_attention_alternating:
            return lax.cond(
                window >= k.shape[1],
                lambda ops: _attention_impl(*ops, config),
                lambda ops: _tagged(_windowed_attention(*ops, config, window)),
                (q, k, v))
        return _tagged(_windowed_attention(q, k, v, config, window))
    if config.pos_embed == "alibi":
        return _tagged(_alibi_attention(q, k, v, config))
    if config.sequence_parallel:
        from ..parallel.mesh import SEQ_AXIS, get_mesh_manager
        mm = get_mesh_manager(optional=True)
        if mm is not None and mm.mesh.shape.get(SEQ_AXIS, 1) > 1:
            from ..parallel.sequence import sp_attention
            return _tagged(sp_attention(q, k, v, impl=config.sequence_parallel,
                                        causal=True, mesh=mm.mesh))
    if config.sparse_attention is not None:
        from ..ops.pallas.block_sparse_attention import block_sparse_attention
        layout = config.sparse_attention.make_layout(q.shape[1])
        return _tagged(block_sparse_attention(
            q, k, v, layout, block=config.sparse_attention.block,
            causal=True))
    from ..ops.pallas import flash_attention, mha_reference
    if config.use_flash_attention:
        # pallas kernel on TPU; internally falls back to the dense
        # reference on other backends or non-tiling/short shapes
        return flash_attention(q, k, v, causal=True,
                               sm_scale=config.attn_softmax_scale)
    return _tagged(mha_reference(q, k, v, causal=True,
                                 sm_scale=config.attn_softmax_scale))


def _wdot(spec, x, w, out_dtype, preferred_element_type=None):
    """Weight-gemm dispatcher shared by every projection site: float (or
    weight-only ``Int8Param``, which dequantizes via ``astype``) weights
    run the einsum in the compute dtype; ``Int8ComputeParam`` routes
    through the true int8×int8→int32 dot with the scale epilogue
    (``ops/int8.py`` — reference pt_binding.cpp int8 gemm serving)."""
    from ..ops.int8 import Int8ComputeParam, int8_einsum
    if isinstance(w, Int8ComputeParam):
        return int8_einsum(spec, x, w,
                           preferred_element_type or out_dtype)
    return jnp.einsum(spec, x, w.astype(out_dtype),
                      preferred_element_type=preferred_element_type)


def _self_attention(x, p, config: GPTConfig, positions=None, window=None):
    """qkv projection + causal attention of one block: [B,S,d] → the
    attention output, ``[B,S,H,Dh]`` or (the packed path) ``[B,S,H*Dh]``;
    :func:`attn_project` reads either.

    Where nothing stands between the product and the kernel (learned or no
    position embedding in the block, no window, no sequence or sparse
    variant, float weights whose heads no mesh axis splits) the product is
    written as rows ``[B,S,3*H*Dh]`` and the flash kernels read q, k, v out
    of it in place: no slice, no transpose, and one array's gradient back
    into the weight product."""
    from ..parallel.mesh import MODEL_AXIS, get_mesh_manager
    mm = get_mesh_manager(optional=True)
    if (window is None and config.use_flash_attention
            and config.pos_embed not in ("rotary", "alibi")
            and not config.sequence_parallel
            and config.sparse_attention is None
            and isinstance(p["wqkv"], jax.Array)
            and isinstance(p["wo"], jax.Array)
            and (mm is None or mm.mesh.shape.get(MODEL_AXIS, 1) == 1)):
        from ..ops.pallas import flash_attention_packed
        cdt = config.dtype
        with jax.named_scope("qkv"):
            h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
            qkv = jnp.einsum(
                "bsd,dk->bsk", h,
                p["wqkv"].astype(cdt).reshape(config.d_model, -1)) \
                + p["bqkv"].astype(cdt).reshape(-1)
        with jax.named_scope("attention"):
            return flash_attention_packed(
                qkv, config.n_head, causal=True,
                sm_scale=config.attn_softmax_scale)
    q, k, v = qkv_proj(x, p, config, positions=positions)
    return _attention(q, k, v, config, window=window)


@jax.named_scope("qkv")
def qkv_proj(x, p, config: GPTConfig, positions=None):
    """LN1 + qkv projection: [B,S,d] → (q, k, v) each [B,S,H,Dh].

    Shared by training (_block) and inference (gpt_inference prefill/decode)
    so the block math has one source of truth.  Rotary embedding (when
    configured) rotates q/k at ``positions`` (default 0..S-1).
    """
    cdt = config.dtype
    h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
    qkv = _wdot("bsd,dthe->bsthe", h, p["wqkv"], cdt) + p["bqkv"].astype(cdt)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if config.pos_embed == "rotary":
        if positions is None:
            positions = jnp.arange(x.shape[1])
        q = _rotate(q, positions, config)
        k = _rotate(k, positions, config)
    return q, k, v


@jax.named_scope("attn_out")
def attn_project(attn, p, config: GPTConfig):
    """Attention output projection W_o·attn + b_o (no residual) — the one
    definition every train/inference/MoE path shares."""
    cdt = config.dtype
    if attn.ndim == 3:      # rows [B,S,H*Dh], as the packed flash path leaves them
        return jnp.einsum("bsk,kd->bsd", attn, p["wo"].astype(cdt).reshape(
            -1, config.d_model)) + p["bo"].astype(cdt)
    return _wdot("bshe,hed->bsd", attn, p["wo"], cdt) + p["bo"].astype(cdt)


def attn_out_residual(x, attn, p, config: GPTConfig, dropout_key=None):
    """Attention output projection + residual: x + W_o·attn."""
    return x + _dropout(attn_project(attn, p, config), config.dropout,
                        dropout_key)


@jax.named_scope("mlp")
def mlp_out(x, p, config: GPTConfig, dropout_key=None):
    """LN2 + MLP (no residual add — parallel-residual models sum it with
    the attention branch instead of chaining)."""
    cdt = config.dtype
    h2 = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
    ff = _wdot("bsd,df->bsf", h2, p["wi"], cdt) + p["bi"].astype(cdt)
    ff = _activation_fn(ff, config)
    if config.act_quant_bits is not None:
        from ..compression.transforms import quantize_activation
        ff = quantize_activation(ff, config.act_quant_bits,
                                 symmetric=config.act_quant_symmetric)
    ff_out = _wdot("bsf,fd->bsd", ff, p["wo_mlp"], cdt) + p["bo_mlp"].astype(cdt)
    return _dropout(ff_out, config.dropout, dropout_key)


def mlp_residual(x, p, config: GPTConfig, dropout_key=None):
    """LN2 + MLP + residual (the dense FFN half-block)."""
    return x + mlp_out(x, p, config, dropout_key)


def block_tail(x, attn, p, config: GPTConfig):
    """Attention output projection + residual + LN2 + MLP + residual."""
    return mlp_residual(attn_out_residual(x, attn, p, config), p, config)


def _attn_residual(x, layer_params, config: GPTConfig, positions=None,
                   dropout_key=None, window=None):
    """Full attention sublayer with residual: x + W_o·attn(qkv(LN1(x))).

    Used by the MoE model (gpt_moe._moe_half_block), whose FFN half is an
    expert layer instead of mlp_residual.
    """
    p = layer_params
    attn = _self_attention(x, p, config, positions=positions, window=window)
    return attn_out_residual(x, attn, p, config, dropout_key)


def _block(x, layer_params, config: GPTConfig, positions=None,
           dropout_key=None, window=None):
    """One transformer block on [B, S, d]."""
    k_attn = k_mlp = None
    if dropout_key is not None:
        k_attn, k_mlp = jax.random.split(dropout_key)
    if config.parallel_residual:
        # NeoX: both sublayers read the SAME input; residual sums them
        p = layer_params
        attn = _self_attention(x, p, config, positions=positions,
                               window=window)
        return x + _dropout(attn_project(attn, p, config),
                            config.dropout, k_attn) \
            + mlp_out(x, p, config, k_mlp)
    h = _attn_residual(x, layer_params, config, positions=positions,
                       dropout_key=k_attn, window=window)
    return mlp_residual(h, layer_params, config, dropout_key=k_mlp)


@jax.named_scope("embed")
def embed(params: PyTree, tokens: jnp.ndarray, config: GPTConfig,
          positions=None) -> jnp.ndarray:
    """Token (+ learned position) embedding with the family's variants.
    ``positions``: [S] shared or [B, S] per-row (ragged decode)."""
    cdt = config.dtype
    x = params["wte"].astype(cdt)[tokens]
    if config.embed_layernorm:
        x = _layer_norm(x, params["emb_ln_scale"], params["emb_ln_bias"])
    if config.pos_embed == "learned":
        if positions is None:
            positions = jnp.arange(tokens.shape[-1])
        pe = params["wpe"].astype(cdt)[positions + config.pos_offset]
        x = x + (pe if pe.ndim == x.ndim else pe[None])
    return x


@jax.named_scope("head")
def _head_logits(params: PyTree, h, config: GPTConfig) -> jnp.ndarray:
    """(Tied or separate) head on final-layernormed hiddens ``h``.

    Inputs stay in the compute dtype so the MXU runs at its bf16 rate; the
    accumulator/output is fp32 (``preferred_element_type``) for a stable
    softmax.  The head of full logits: ``lm_logits`` and the loss's plain
    path route here; the chunked loss (``_chunked_nll``) multiplies a float
    head a chunk at a time to the same types.
    """
    head = params["wte"] if config.tie_word_embeddings else params["lm_head"]
    logits = _wdot("...d,vd->...v", h.astype(config.dtype), head,
                   config.dtype, preferred_element_type=jnp.float32)
    if "lm_head_bias" in params:  # GPT-J's biased untied head
        logits = logits + params["lm_head_bias"].astype(jnp.float32)
    return logits


@jax.named_scope("loss")
def _token_nll(logits, targets):
    """Per-token masked NLL sums: (sum nll, count). targets < 0 are masked
    (the -100 convention)."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    mask = (targets >= 0).astype(jnp.float32)
    return jnp.sum((logz - gold) * mask), jnp.sum(mask)


def lm_logits(params: PyTree, x, config: GPTConfig) -> jnp.ndarray:
    """Final LN + head → fp32 logits."""
    return _head_logits(
        params, _layer_norm(x, params["lnf_scale"], params["lnf_bias"]),
        config)


def backbone(params: PyTree, tokens: jnp.ndarray, config: GPTConfig,
             dropout_rng=None, pld_theta=None) -> jnp.ndarray:
    """Embed + transformer stack: tokens [B, S] → hidden [B, S, d]
    (pre-final-layernorm).

    ``pld_theta`` (engine-injected, train only) enables progressive layer
    drop: layer l keeps with prob 1 - (l+1)/L · (1-θ) — deeper layers drop
    more, the whole stack survives at θ=1 (reference PLD semantics,
    runtime/progressive_layer_drop.py wired at engine.py:1698).
    """
    B, S = tokens.shape
    x = embed(params, tokens, config)
    if dropout_rng is not None and config.dropout > 0:
        emb_key, dropout_rng = jax.random.split(dropout_rng)
        x = _dropout(x, config.dropout, emb_key)

    if config.sequence_parallel:
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ..parallel.mesh import (DATA_AXIS, EXPERT_AXIS, SEQ_AXIS,
                                     get_mesh_manager)
        mm = get_mesh_manager(optional=True)
        if mm is not None and mm.mesh.shape.get(SEQ_AXIS, 1) > 1:
            x = lax.with_sharding_constraint(
                x, NamedSharding(mm.mesh, P((DATA_AXIS, EXPERT_AXIS), SEQ_AXIS, None)))

    block_fn = partial(_block, config=config)
    if config.remat:
        from ..runtime.activation_checkpointing import checkpointing as ckpt
        if ckpt.is_configured():
            # policy-driven remat (partitioned/offloaded checkpoints)
            block_fn = ckpt.wrap(block_fn)
        else:
            if config.remat_policy == "dots":
                # saving matmul outputs alone still re-runs the flash fwd
                # kernel in the backward (lse is a custom_vjp residual,
                # not a dot output) — save the tagged pair as well
                policy = jax.checkpoint_policies.save_from_both_policies(
                    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                    jax.checkpoint_policies.save_only_these_names(
                        "ds_attn_out", "ds_attn_lse"))
            elif config.remat_policy == "attn_out":
                # "ds_attn_lse" rides along (tagged inside the flash
                # custom_vjp's fwd rule): saving o WITHOUT lse would
                # leave the backward re-running the fwd kernel for it
                policy = jax.checkpoint_policies.save_only_these_names(
                    "ds_attn_out", "ds_attn_lse")
            else:
                policy = jax.checkpoint_policies.nothing_saveable
            block_fn = jax.checkpoint(block_fn, policy=policy)

    use_dropout = dropout_rng is not None and config.dropout > 0
    use_pld = pld_theta is not None and dropout_rng is not None
    L = config.n_layer

    def scan_body(carry, xs):
        layer_params, idx = xs
        key = jax.random.fold_in(dropout_rng, idx) if use_dropout else None
        out = block_fn(carry, layer_params, dropout_key=key,
                       window=layer_window(config, idx, S))
        if use_pld:
            p_keep = 1.0 - (idx + 1.0) / L * (1.0 - pld_theta)
            gate_key = jax.random.fold_in(
                jax.random.fold_in(dropout_rng, idx), 7919)
            keep = jax.random.bernoulli(gate_key, p_keep)
            out = jnp.where(keep, out, carry)
        return out, None

    x, _ = lax.scan(scan_body, x,
                    (params["blocks"], jnp.arange(config.n_layer)),
                    unroll=config.scan_unroll)
    return x


def apply(params: PyTree, tokens: jnp.ndarray, config: GPTConfig,
          dropout_rng=None, pld_theta=None) -> jnp.ndarray:
    """Forward pass: tokens [B, S] int32 → logits [B, S, padded_vocab] f32."""
    x = backbone(params, tokens, config, dropout_rng=dropout_rng,
                 pld_theta=pld_theta)
    return lm_logits(params, x, config)


def encode(params: PyTree, tokens: jnp.ndarray, config: GPTConfig
           ) -> jnp.ndarray:
    """Final-layernormed hidden states [B, S, d] — the text-encoder surface
    (CLIP's ``last_hidden_state``; reference HFCLIPLayerPolicy,
    replace_policy.py:205)."""
    x = backbone(params, tokens, config)
    return _layer_norm(x, params["lnf_scale"], params["lnf_bias"])


#: float32 logits a device may hold at once in the loss: a ``[B, c, V]``
#: chunk's bytes on one device stay at or under it.  Where the whole
#: ``[B, S, V]`` fits, the loss is the plain single pass.
_LOGITS_CHUNK_BYTES = 5 << 28

#: the ``[head products the loss holds, sequence chunks]`` lists opened by
#: :func:`tally_head`, innermost last
_head_tallies = []


@contextlib.contextmanager
def tally_head():
    """Yields ``[products, chunks]`` of the loss heads traced inside the
    block: the vocabulary-sized products the traced step holds, forward and
    backward (3 either way: where the compiler is handed whole logits it
    may re-make them, in chunks it cannot), and the chunks of the sequence
    the head walks (1: the plain path).  The train engine opens it around
    the trace of its loss and records the two as counters."""
    tally = [0, 0]
    _head_tallies.append(tally)
    try:
        yield tally
    finally:
        _head_tallies.pop()


def _loss_layout(B, S, V, cap):
    """How the loss walks ``[B, S, V]`` logits: ``(c, shards, whole)``.

    ``c``: positions of each sequence in a chunk, the largest divisor of
    ``S`` whose float32 logits ``[B, c, V]``, divided over the devices of
    the mesh (data shards the batch, model the vocabulary), stay under
    :data:`_LOGITS_CHUNK_BYTES`, and under ``cap`` positions if set
    (``GPTConfig.loss_chunk``); ``S`` itself is one chunk, the plain path.
    ``shards``: the data-parallel shards of the batch, each of which sums
    its own rows' head gradient, reduced ONCE after the loop.  ``whole``:
    the sharding of the head inside the loop (vocabulary over model, whole
    on every data shard: ZeRO-3 gathers it ONCE, before the loop), or None
    without a mesh."""
    from jax.sharding import NamedSharding
    from ..parallel.mesh import get_mesh_manager
    from .partitioning import TP_RULES, spec_for_axes
    mm = get_mesh_manager(optional=True)
    devices = mm.mesh.size if mm is not None else 1
    limit = min(cap or S, S,
                max(int(_LOGITS_CHUNK_BYTES * devices // (B * V * 4)), 1))
    c = next(c for c in range(limit, 0, -1) if S % c == 0)
    if devices == 1:
        return c, 1, None
    shards = mm.dp_world_size if B % mm.dp_world_size == 0 else 1
    return c, shards, NamedSharding(
        mm.mesh, spec_for_axes((VOCAB, EMBED), TP_RULES))


def _nll_chunks(h, w, bias, targets, whole, with_grads):
    """``(total, grads)`` of :func:`_chunked_nll`, one chunk of the scan at
    a time; ``grads`` for a unit cotangent, ``(dh, dw, dbias)`` (``dw`` and
    ``dbias`` float32 sums), or None."""
    if whole is not None:
        w = lax.with_sharding_constraint(w, whole)
    G = h.shape[1]

    def chunk(acc, xs):
        total, dw, db = acc
        hc, tc = xs
        with jax.named_scope("head"):
            logits = jnp.einsum("...d,vd->...v", hc, w,
                                preferred_element_type=jnp.float32)
            if bias is not None:
                logits = logits + bias
        with jax.named_scope("loss"):
            lse = jax.nn.logsumexp(logits, axis=-1)
            live = tc >= 0
            gold = jnp.take_along_axis(
                logits, jnp.maximum(tc, 0)[..., None], axis=-1)[..., 0]
            total = total + jnp.sum(jnp.where(live, lse - gold, 0.0))
            if not with_grads:
                return (total, dw, db), None
            hot = tc[..., None] == lax.broadcasted_iota(
                jnp.int32, logits.shape, logits.ndim - 1)
            dl = jnp.where(live[..., None],
                           jnp.exp(logits - lse[..., None]) - hot, 0.0)
            if db is not None:
                db = db + jnp.sum(dl, axis=(1, 2))
            dl = dl.astype(w.dtype)
        with jax.named_scope("head"):
            dh = jnp.einsum("...v,vd->...d", dl, w)
            dw = dw + jnp.einsum("gbcv,gbcd->gvd", dl, hc,
                                 preferred_element_type=jnp.float32)
        return (total, dw, db), dh

    def zeros(x):
        return jnp.zeros((G,) + x.shape, jnp.float32)

    acc = (jnp.zeros((), jnp.float32),
           zeros(w) if with_grads else None,
           zeros(bias) if with_grads and bias is not None else None)
    (total, dw, db), dh = lax.scan(chunk, acc, (h, targets))
    return total, (dh, dw, db) if with_grads else None


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def _chunked_nll(h, w, bias, targets, whole):
    """Sum of the masked token NLLs of ``h`` ``[n, G, b, c, d]`` (``n``
    chunks of ``c`` positions of ``G`` data shards' ``b`` rows) against the
    head ``w`` ``[V, d]`` (both in the compute dtype; ``bias`` float32 or
    None), a chunk ``[G, b, c, V]`` of float32 logits at a time.

    Its forward rule makes the gradients while it holds a chunk's logits:
    the softmax's gradient ``(p - onehot) * mask`` cast to the compute
    dtype, then the hidden states' gradient and the head's, the latter
    summed in float32 across chunks; the backward rule multiplies them by
    the cotangent.  That is one head product a chunk forward and two
    backward, fixed by this code and not by what the compiler would rather
    re-make.  ``whole``: :func:`_loss_layout`'s."""
    return _nll_chunks(h, w, bias, targets, whole, False)[0]


def _chunked_nll_fwd(h, w, bias, targets, whole):
    return _nll_chunks(h, w, bias, targets, whole, True)


def _chunked_nll_bwd(whole, grads, g):
    dh, dw, db = grads
    with jax.named_scope("head"):
        # a data shard's sum leaves in the compute dtype, as a gradient
        # crosses devices everywhere else in the step
        return ((dh.astype(jnp.float32) * g).astype(dh.dtype),
                jnp.sum((dw * g).astype(dh.dtype), axis=0),
                None if db is None else jnp.sum(db * g, axis=0), None)


_chunked_nll.defvjp(_chunked_nll_fwd, _chunked_nll_bwd)


def _head_nll(params: PyTree, h, targets, config: GPTConfig):
    """Head and cross-entropy of final-layernormed ``h`` ``[B, S, d]``:
    ``(sum of masked NLLs, count)``.  Where the whole float32 logits are
    small, or the head is not a float matrix (int8 weights go through
    ``_wdot``), the plain single pass; else :func:`_chunked_nll` over
    slices of the sequence (every batch row in each, so a device's share of
    a chunk is its own rows)."""
    head = params["wte"] if config.tie_word_embeddings else params["lm_head"]
    B, S, d = h.shape
    c, shards, whole = S, 1, None
    if isinstance(head, jax.Array) and jnp.issubdtype(head.dtype,
                                                      jnp.floating):
        c, shards, whole = _loss_layout(B, S, head.shape[0],
                                        config.loss_chunk)
        if S % min(config.loss_chunk or S, S):
            logger.warning(f"loss_chunk={config.loss_chunk} does not divide "
                           f"seq {S}; using chunk {c}")
    for tally in _head_tallies[-1:]:
        tally[0] += 3
        tally[1] += S // c
    if c == S:
        return _token_nll(_head_logits(params, h, config), targets)

    def chunks(x):      # [B, S, ...] -> [n, shards, B / shards, c, ...]
        x = x.reshape((shards, B // shards, S // c, c) + x.shape[2:])
        return jnp.moveaxis(x, 2, 0)

    with jax.named_scope("head"):
        w = head.astype(config.dtype)   # ONE copy, outside the chunk loop
        hc = chunks(h.astype(config.dtype))
    bias = params.get("lm_head_bias")
    total = _chunked_nll(
        hc, w, None if bias is None else bias.astype(jnp.float32),
        chunks(targets), whole)
    with jax.named_scope("loss"):
        return total, jnp.sum((targets >= 0).astype(jnp.float32))


def loss_fn(params: PyTree, batch: Dict[str, jnp.ndarray], config: GPTConfig) -> jnp.ndarray:
    """Mean next-token cross-entropy. batch: {'tokens': [B,S+1]} or
    input/target.  A ``_train_rng`` key in the batch (engine-injected)
    enables dropout; its absence (eval) disables it."""
    dropout_rng = pld_theta = None
    if "_train_rng" in batch or "_pld_theta" in batch:
        batch = dict(batch)
        dropout_rng = batch.pop("_train_rng", None)
        pld_theta = batch.pop("_pld_theta", None)
    if "input_ids" in batch:
        inputs, targets = batch["input_ids"], batch["labels"]
    else:
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = backbone(params, inputs, config, dropout_rng=dropout_rng,
                 pld_theta=pld_theta)
    h = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    tot, cnt = _head_nll(params, h, targets, config)
    return tot / jnp.maximum(cnt, 1.0)


def flops_per_token(config: GPTConfig) -> float:
    """6N + attention flops per token (for MFU accounting)."""
    d, L, S = config.d_model, config.n_layer, config.max_seq_len
    n_params = (config.padded_vocab * d + S * d + L * (12 * d * d + 13 * d) + 2 * d)
    return 6.0 * n_params + 12.0 * L * d * S  # fwd+bwd matmul + attention term
