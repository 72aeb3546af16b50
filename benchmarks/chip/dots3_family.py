"""The selected-latent / window-latent family (``models/sparse_latent_moe.py``)
for the benchmark: from a ``dots3_note`` ``config.json`` to the program's
config, the weights, and what the family's kernels must compute and read.

``layer_types`` gives each layer its kind (``full_attention``: latent
attention over the ``index_topk`` tokens a learned index picks;
``sliding_attention``: latent attention with the ``swa_`` widths over the
last ``sliding_window_size`` tokens, the query among them); the first
``first_k_dense_replace`` layers hold a dense MLP, every other an expert
layer.  The file's ``n_routed_experts`` counts the experts *held here* (the
chip's share of a deployment, listed in ``reduced``): the first that many of
the deployment's; the router's width is the published count
(``published.n_routed_experts``).

What this module cannot serve it refuses by name (the asserts of ``build``):
a sibling configuration is never served as something it is not.  The towers
and the MTP module of the published model are not in the language model's
``config`` and are not served."""

from __future__ import annotations

import math

KINDS = {"full_attention": "full", "sliding_attention": "window"}


def build(file: dict):
    import jax.numpy as jnp
    from deepspeed_tpu.models.sparse_latent_moe import SparseLatentMoEConfig
    assert file["model_type"] == "dots3_note"
    assert file["scoring_func"] == "sigmoid" \
        and file["topk_method"] == "noaux_tc", "the sigmoid noaux_tc router"
    assert file.get("n_group", 1) == 1 and file.get("topk_group", 1) == 1, \
        "group-limited routing (n_group > 1) is not served"
    assert file["rope_scaling"] is None, "rope_scaling is not served"
    assert not file["tie_word_embeddings"], "the head is a matrix of its own"
    assert not file["attention_bias"], "biases are not served"
    assert file["moe_layer_freq"] == 1, "moe_layer_freq != 1 is not served"
    assert file["hidden_act"] == "silu"
    assert file["attention_gate_type"] == "headwise" \
        and file["swa_attention_gate_type"] == "headwise", \
        "one sigmoid gate a head, on both kinds of layer"
    assert file["num_key_value_heads"] == file["num_attention_heads"] \
        and file["swa_num_key_value_heads"] == file["swa_num_attention_heads"]
    types = file["layer_types"]
    assert len(types) == file["num_hidden_layers"] \
        and set(types) <= set(KINDS), types
    return SparseLatentMoEConfig(
        vocab_size=file["vocab_size"],
        max_seq_len=file["max_position_embeddings"],
        layer_types=tuple(KINDS[t] for t in types),
        d_model=file["hidden_size"], d_ff=file["intermediate_size"],
        d_expert=file["moe_intermediate_size"],
        n_head=file["num_attention_heads"], q_rank=file["q_lora_rank"],
        kv_rank=file["kv_lora_rank"], d_nope=file["qk_nope_head_dim"],
        d_rope=file["qk_rope_head_dim"], d_v=file["v_head_dim"],
        rope_theta=float(file["rope_theta"]),
        index_heads=file["index_n_heads"], index_dim=file["index_head_dim"],
        index_topk=file["index_topk"],
        w_n_head=file["swa_num_attention_heads"],
        w_q_rank=file["swa_q_lora_rank"], w_kv_rank=file["swa_kv_lora_rank"],
        w_d_nope=file["swa_qk_nope_head_dim"],
        w_d_rope=file["swa_qk_rope_head_dim"], w_d_v=file["swa_v_head_dim"],
        w_rope_theta=float(file["swa_rope_theta"]),
        window=file["sliding_window_size"],
        lora_rescale=bool(file["apply_mla_qkv_lora_rescale"]),
        n_experts=file.get("published", {}).get(
            "n_routed_experts", file["n_routed_experts"]),
        experts_per_token=file["num_experts_per_tok"],
        n_shared_experts=file["n_shared_experts"],
        first_k_dense=file["first_k_dense_replace"],
        held_experts=tuple(range(file["n_routed_experts"])),
        routed_scale=file["routed_scaling_factor"],
        norm_topk=file["norm_topk_prob"], eps=file["rms_norm_eps"],
        dtype=jnp.bfloat16)


build.published = lambda c: {
    "hidden_size": c.d_model, "intermediate_size": c.d_ff,
    "moe_intermediate_size": c.d_expert,
    "num_attention_heads": c.n_head, "num_key_value_heads": c.n_head,
    "q_lora_rank": c.q_rank, "kv_lora_rank": c.kv_rank,
    "qk_nope_head_dim": c.d_nope, "qk_rope_head_dim": c.d_rope,
    "v_head_dim": c.d_v, "rope_theta": c.rope_theta,
    "index_n_heads": c.index_heads, "index_head_dim": c.index_dim,
    "index_topk": c.index_topk,
    "swa_num_attention_heads": c.w_n_head,
    "swa_num_key_value_heads": c.w_n_head,
    "swa_q_lora_rank": c.w_q_rank, "swa_kv_lora_rank": c.w_kv_rank,
    "swa_qk_nope_head_dim": c.w_d_nope, "swa_qk_rope_head_dim": c.w_d_rope,
    "swa_v_head_dim": c.w_d_v, "swa_rope_theta": c.w_rope_theta,
    "sliding_window_size": c.window,
    "num_experts_per_tok": c.experts_per_token,
    "n_shared_experts": c.n_shared_experts,
    "first_k_dense_replace": c.first_k_dense,
    "num_hidden_layers": c.n_layer, "n_routed_experts": len(c.held),
    "vocab_size": c.vocab_size, "max_position_embeddings": c.max_seq_len,
    "routed_scaling_factor": c.routed_scale, "rms_norm_eps": c.eps}


#: ``latent_moe_family.ROUTED_DOWN_SCALE``'s, for its reason: with nine
#: layers in place of 46 a held (token, expert) pair at full scale is far
#: more of its position's logits than one expert of a trained deep stack,
#: and a choice of the router that bf16 and float32 make differently would
#: own the comparison
ROUTED_DOWN_SCALE = 1 / 8


#: The up-projections out of the two latents (``W_qb``, ``W_kvb``) draw half
#: the other matrices' 0.02.  ``apply_mla_qkv_lora_rescale`` multiplies a
#: full layer's scores by ``sqrt(5) sqrt(10)``: at 0.02 they spread by about
#: 2, a softmax over some tens of effective keys, and bf16 alone reads 0.022
#: / 0.022 (largest / rms) against ``compare.py``'s 0.03 / 0.018 on a prompt
#: too short to select anything; at 0.014 0.011 / 0.013, at 0.01 0.0089 /
#: 0.0094 (my chip runs, PR 51; PERF.md 6).
UP_STD = 0.01
#: A full layer's ``W_o`` against the other matrices that write to the
#: residual stream.  A TRAINED index picks the tokens its layer's attention
#: weighs, so a neighbour flipped at the 2,048th rank carries no weight; a
#: random index picks at random with respect to those weights, so each of the
#: 8 of 2,048 tokens a query that bf16 and float32 choose differently (0.4%,
#: ``dots3_control.py --in-common``) moves a head's output by a typical
#: token's share.  At 1 that noise alone reads 0.029 rms at 2,600 tokens; it
#: is linear in this scale (0.025 at 1/2 with the up-projections at 0.014),
#: and at 1/8 it reads under 0.005 beside bf16's 0.009 while the selection
#: replaced by the latest 2,048 tokens, which changes a fifth of the set at
#: 2,600 tokens and three quarters at 9,100, still reads over the limits
#: (PERF.md 6, PR 51).
FULL_OUT_SCALE = 1 / 8


def init(cfg, key, dtype=None):
    """Seeded weights in the type served (``assumed.weights`` of the
    configuration file gives the scales)."""
    import dataclasses

    from deepspeed_tpu.models import sparse_latent_moe
    if dtype is not None:
        cfg = dataclasses.replace(cfg, param_dtype=dtype)
    out_std = 0.02 / math.sqrt(2 * cfg.n_layer)
    return sparse_latent_moe.init(
        cfg, key, std=0.02, routed_out_std=out_std * ROUTED_DOWN_SCALE,
        up_std=UP_STD, full_out_scale=FULL_OUT_SCALE)


def index_count(cfg, eligible: float):
    """``(operations, bytes)`` the index and its selection must spend on
    ``eligible`` (query, cached token) pairs of single-token passes: every
    index head scores the token's one key (``2 Hi Di`` = 16,384 operations;
    the ``relu``, the weighted sum over heads and the selection's comparisons
    are not counted), and the key is read once, two bytes an element (256
    B)."""
    return (2.0 * eligible * cfg.index_heads * cfg.index_dim,
            2.0 * eligible * cfg.index_dim)


def _latent_count(dm, tokens: float):
    row = dm.kv_rank + dm.d_rope
    return (2.0 * tokens * dm.n_head * (row + dm.kv_rank), 2.0 * tokens * row)


def sparse_decode_count(cfg, selected: float):
    """``(operations, bytes)`` a full layer's single-token attention must
    spend on the ``selected`` tokens its queries attend to, whatever finds
    and fetches them: every head scores the latent row (576 elements) and
    weighs its 512 values; the row is read once (1,152 B), the lanes it is
    padded to in storage not counted.  A masked sweep moves and multiplies
    every live token and so reads low against this."""
    return _latent_count(cfg.dims("full"), selected)


def window_decode_count(cfg, ring_live: float):
    """``(operations, bytes)`` a window layer's single-token attention must
    spend on the ``ring_live`` cells its queries see (at most the window's
    513 a query): 64 heads on a row of 1,088 elements (2,176 B), 1,024 of
    them weighed."""
    return _latent_count(cfg.dims("window"), ring_live)


def expert_count(cfg, pairs: float, visits: float):
    """``latent_moe_family.expert_count`` at this family's widths: a pair is
    three products of ``d x f`` (5120 x 1536); a visit reads that expert's
    three matrices once."""
    per = 3.0 * cfg.d_model * cfg.d_expert
    return 2.0 * pairs * per, 2.0 * visits * per
