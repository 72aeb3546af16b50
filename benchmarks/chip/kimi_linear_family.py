"""The linear-attention / latent-attention family
(``models/linear_latent_moe.py``) for the benchmark: from a ``kimi_linear``
``config.json`` to the program's config, the weights, and what the family's
kernels must compute and read.

``linear_attn_config`` is kept whole as published (both lists of layers to
the published depth); the builder takes the layers up to
``num_hidden_layers``.  The file's ``num_experts`` counts the experts *held
here* (the chip's share of a deployment, listed in ``reduced``): the first
that many of the deployment's; the router's width is the published count
(``published.num_experts``).

What this module cannot serve it refuses by name (the asserts of ``build``):
a sibling configuration is never served as something it is not."""

from __future__ import annotations

import math


def build(file: dict):
    import jax.numpy as jnp
    from deepspeed_tpu.models.linear_latent_moe import LinearLatentMoEConfig
    assert file["model_type"] == "kimi_linear"
    assert file["q_lora_rank"] is None and file["mla_use_nope"], \
        "a query bottleneck or rotated latent layers (q_lora_rank set, or " \
        "mla_use_nope off): kimi-k2.7-code-ep32's family, not this one"
    assert not file.get("rope_scaling"), \
        "rope_scaling: the latent layers have no rotation to scale"
    assert file["num_expert_group"] == file["topk_group"] == 1, \
        "the gate selects among all experts: no expert groups"
    assert not file.get("num_nextn_predict_layers"), "MTP layers"
    assert not file["tie_word_embeddings"], "the head is a matrix of its own"
    assert file["moe_layer_freq"] == 1, \
        "every layer after the leading dense ones is an expert layer"
    assert file["moe_router_activation_func"] == "sigmoid" \
        and file["hidden_act"] == "silu"
    # stated and used by neither mixer: the latent layers' heads are
    # qk_nope + qk_rope / v_head_dim wide, the KDA layers' their own
    assert file["head_dim"] * file["num_attention_heads"] \
        == file["hidden_size"]
    assert file["num_key_value_heads"] == file["num_attention_heads"]
    lin = file["linear_attn_config"]
    n = file["num_hidden_layers"]
    upto = lambda layers: tuple(i for i in layers if i <= n)
    return LinearLatentMoEConfig(
        vocab_size=file["vocab_size"],
        max_seq_len=file["model_max_length"], n_layer=n,
        kda_layers=upto(lin["kda_layers"]),
        full_attn_layers=upto(lin["full_attn_layers"]),
        d_model=file["hidden_size"], d_ff=file["intermediate_size"],
        d_expert=file["moe_intermediate_size"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"],
        n_head=file["num_attention_heads"], kv_rank=file["kv_lora_rank"],
        d_nope=file["qk_nope_head_dim"], d_rope=file["qk_rope_head_dim"],
        d_v=file["v_head_dim"],
        n_experts=file.get("published", {}).get(
            "num_experts", file["num_experts"]),
        experts_per_token=file["num_experts_per_token"],
        n_shared_experts=file["num_shared_experts"],
        first_k_dense=file["first_k_dense_replace"],
        held_experts=tuple(range(file["num_experts"])),
        routed_scale=file["routed_scaling_factor"],
        norm_topk=file["moe_renormalize"], eps=file["rms_norm_eps"],
        dtype=jnp.bfloat16)


build.published = lambda c: {
    "hidden_size": c.d_model, "head_dim": c.d_model // c.n_head,
    "num_attention_heads": c.n_head, "num_key_value_heads": c.n_head,
    "intermediate_size": c.d_ff, "moe_intermediate_size": c.d_expert,
    "kv_lora_rank": c.kv_rank, "qk_nope_head_dim": c.d_nope,
    "qk_rope_head_dim": c.d_rope, "v_head_dim": c.d_v,
    "num_experts_per_token": c.experts_per_token,
    "num_shared_experts": c.n_shared_experts,
    "first_k_dense_replace": c.first_k_dense,
    "num_hidden_layers": c.n_layer, "num_experts": len(c.held),
    "vocab_size": c.vocab_size, "model_max_length": c.max_seq_len,
    "routed_scaling_factor": c.routed_scale, "rms_norm_eps": c.eps}


#: The embedding's scale, after ``nemotron_h_family.EMBED_STD`` and for its
#: reason: this block has no embedding multiplier, and at the other
#: matrices' 0.02 every logit is made of layer outputs alone.
EMBED_STD = 12 * 0.02
#: The routed experts' down-projection over the other matrices' scale, after
#: ``latent_moe_family.ROUTED_DOWN_SCALE`` and for its reason (the same
#: gate: sigmoid, 8 a token, renormalised, times 2.4-2.8): a choice of the
#: router that bf16 and float32 make differently is a whole held pair in one
#: and not in the other.
ROUTED_DOWN_SCALE = 1 / 8


def init(cfg, key, dtype=None):
    """Seeded weights in the type served (``assumed`` of the configuration
    file gives the scales, ``EMBED_STD`` and ``ROUTED_DOWN_SCALE`` among
    them, and the KDA layers' ranges)."""
    import dataclasses

    from deepspeed_tpu.models import linear_latent_moe
    if dtype is not None:
        cfg = dataclasses.replace(cfg, param_dtype=dtype)
    out_std = 0.02 / math.sqrt(2 * cfg.n_layer)
    return linear_latent_moe.init(cfg, key, std=0.02, embed_std=EMBED_STD,
                                  routed_out_std=out_std * ROUTED_DOWN_SCALE)


# One latent row, one count: every one of the 32 heads scores the one latent
# row (``kv_rank + d_rope`` = 576 elements) and weighs its ``kv_rank`` values;
# the row is read once, two bytes an element, the 640 lanes it is stored in
# not counted.  The config carries ``latent_moe``'s names for those widths.
from .latent_moe_family import decode_count  # noqa: E402,F401


def state_step_count(cfg, rows: float):
    """``(operations, bytes)`` the KDA decode step must spend on ``rows``
    (live slot, KDA layer) meetings: the row's state is read and written
    once, float32 (``2 x d_k x heads d_v x 4`` bytes = 2 x 2,097,152 B at
    the published widths); an element takes its decay (1), its share of
    ``S'^T k`` (2), of ``k u^T`` (2) and of ``S^T q`` (2): 7 operations,
    0.9 an byte, so memory bound."""
    elements = rows * cfg.kda_head_dim * cfg.d_kda
    return 7.0 * elements, 8.0 * elements


def chunk_scan_count(cfg, tokens: float):
    """``(operations, bytes)`` the chunk scan must spend on ``tokens``
    (token, KDA layer) meetings, padded ones included (the kernel is handed
    whole chunks).  A token of a head, in a sub-chunk of ``C``: its row of
    the two score matrices, each entry a sum over ``d_k`` channels of a
    product of three (``2 x 3 C d_k``; the decay's exponential is not
    counted); its read of the carried state for ``u`` and for ``o`` and its
    write to it (``3 x 2 d_k d_v``); its row of ``(I + A)^-1 rhs`` and of
    the scores times ``U`` (``2 x 2 C d_v``).  The inverse itself (``log2
    C`` squarings a sub-chunk) is this implementation's way to solve the
    system and is not counted.  Bytes: ``q``, ``k``, ``v``, ``G`` in and
    ``o`` out in float32, ``k`` and ``G`` a second time transposed (the state
    itself moves once a chunk: counted with the inputs' rounding)."""
    C, K, H = cfg.kda_chunk, cfg.kda_head_dim, cfg.kda_heads
    per_head = 6.0 * C * K + 6.0 * K * K + 4.0 * C * K
    return tokens * H * per_head, tokens * 4.0 * 7 * H * K
