"""The latent-attention, routed-expert family (``models/latent_moe.py``) for
the benchmark: from a ``kimi_k2`` / ``deepseek_v3``-style ``config.json`` to
the program's config, the weights, and what the family's kernels must
compute and read.

The file's ``n_routed_experts`` counts the experts *held here* (the chip's
share of a deployment, listed in ``reduced``): the first that many of the
deployment's; the router's width is the published count
(``published.n_routed_experts``)."""

from __future__ import annotations

import math


def build(file: dict):
    import jax.numpy as jnp
    from deepspeed_tpu.models.latent_moe import LatentMoEConfig
    assert file["scoring_func"] == "sigmoid" and file["n_group"] == 1 \
        and file["topk_group"] == 1 and file["topk_method"] == "noaux_tc" \
        and file["hidden_act"] == "silu" and file["moe_layer_freq"] == 1 \
        and not file["tie_word_embeddings"] and not file["attention_bias"]
    rope = file.get("rope_scaling")
    return LatentMoEConfig(
        vocab_size=file["vocab_size"],
        max_seq_len=file["max_position_embeddings"],
        n_layer=file["num_hidden_layers"],
        n_head=file["num_attention_heads"], d_model=file["hidden_size"],
        d_ff=file["intermediate_size"],
        d_expert=file["moe_intermediate_size"],
        q_rank=file["q_lora_rank"], kv_rank=file["kv_lora_rank"],
        d_nope=file["qk_nope_head_dim"], d_rope=file["qk_rope_head_dim"],
        d_v=file["v_head_dim"],
        n_experts=file.get("published", {}).get(
            "n_routed_experts", file["n_routed_experts"]),
        experts_per_token=file["num_experts_per_tok"],
        n_shared_experts=file["n_shared_experts"],
        first_k_dense=file["first_k_dense_replace"],
        held_experts=tuple(range(file["n_routed_experts"])),
        routed_scale=file["routed_scaling_factor"],
        norm_topk=file["norm_topk_prob"], eps=file["rms_norm_eps"],
        rope_theta=float(file["rope_theta"]),
        yarn=None if not rope else (
            float(rope["factor"]), rope["original_max_position_embeddings"],
            float(rope["beta_fast"]), float(rope["beta_slow"]),
            float(rope["mscale"]), float(rope["mscale_all_dim"])),
        dtype=jnp.bfloat16)


build.published = lambda c: {
    "hidden_size": c.d_model, "num_attention_heads": c.n_head,
    "num_key_value_heads": c.n_head, "intermediate_size": c.d_ff,
    "moe_intermediate_size": c.d_expert, "q_lora_rank": c.q_rank,
    "kv_lora_rank": c.kv_rank, "qk_nope_head_dim": c.d_nope,
    "qk_rope_head_dim": c.d_rope, "v_head_dim": c.d_v,
    "num_experts_per_tok": c.experts_per_token,
    "n_shared_experts": c.n_shared_experts,
    "first_k_dense_replace": c.first_k_dense,
    "num_hidden_layers": c.n_layer, "n_routed_experts": len(c.held),
    "vocab_size": c.vocab_size, "max_position_embeddings": c.max_seq_len,
    "routed_scaling_factor": c.routed_scale, "rms_norm_eps": c.eps,
    "rope_theta": c.rope_theta}


#: the routed experts' down-projection against the other matrices that
#: write to the residual stream.  At 1 a held (token, expert) pair is 11%
#: of its position's logits (five layers, not 61) and a choice of the router
#: that bf16 and float32 make differently (0.4% of token-layers hold one of
#: a held expert: about every third run has one at a compared position)
#: reads 0.09-0.17 against ``compare.py``'s 0.03.  At 1/8 it reads
#: 0.012-0.021 beside bf16's 0.013-0.016, and leaving the routed product
#: out, a wrong group or a wrong layer of the stack still reads over the
#: limits (``reference/latent_moe_control.py``; PERF.md 6).
ROUTED_DOWN_SCALE = 1 / 8


def init(cfg, key, dtype=None):
    """Seeded weights in the type served (``assumed.weights`` of the
    configuration file gives the scales and why the routed experts' own:
    ``ROUTED_DOWN_SCALE``)."""
    import dataclasses

    from deepspeed_tpu.models import latent_moe
    if dtype is not None:
        cfg = dataclasses.replace(cfg, param_dtype=dtype)
    out_std = 0.02 / math.sqrt(2 * cfg.n_layer)
    return latent_moe.init(cfg, key, std=0.02,
                           routed_out_std=out_std * ROUTED_DOWN_SCALE)


def decode_count(cfg, rows_context_tokens: float):
    """``(operations, bytes)`` one latent decode call of one layer must
    spend on ``rows_context_tokens`` cached tokens: every head scores the
    one latent row (``kv_rank + d_rope``) and weighs its ``kv_rank`` values
    (the absorbed form); the row is read once, two bytes an element, the
    lanes it is padded to in storage not counted."""
    row = cfg.kv_rank + cfg.d_rope
    return (2.0 * rows_context_tokens * cfg.n_head * (row + cfg.kv_rank),
            2.0 * rows_context_tokens * row)


def expert_count(cfg, pairs: float, visits: float):
    """``(operations, bytes)`` the routed experts must spend on ``pairs``
    (token, expert) pairs held here that made ``visits`` (expert layer
    call, held expert) meetings: a pair is three products of ``d x f``
    (gate, up, down); a visit reads that expert's three matrices once, two
    bytes an element.  Activations are not counted: they are a hundredth
    of the matrices at these sizes."""
    per = 3.0 * cfg.d_model * cfg.d_expert
    return 2.0 * pairs * per, 2.0 * visits * per
