"""The hybrid state-space / attention family (``models/hybrid_ssm_moe.py``)
for the benchmark: from a ``granitemoehybrid`` ``config.json`` to the
program's config, the weights, and what the family's kernels must compute
and read.

The file's ``num_local_experts`` counts the experts *held here* (the chip's
share of a deployment, listed in ``reduced``): the first that many of the
deployment's; the router's width is the published count
(``published.num_local_experts``).  ``layer_types`` holds the first
``num_hidden_layers`` published entries: it is cut with the depth."""

from __future__ import annotations


def build(file: dict):
    import jax.numpy as jnp
    from deepspeed_tpu.models.hybrid_ssm_moe import HybridSSMMoEConfig
    assert file["hidden_act"] == "silu" and file["mamba_n_groups"] == 1 \
        and file["normalization_function"] == "rmsnorm" \
        and file["position_embedding_type"] == "nope" \
        and file["tie_word_embeddings"] and not file["attention_bias"] \
        and file["mamba_conv_bias"] and not file["mamba_proj_bias"]
    types = tuple(file["layer_types"])
    assert len(types) == file["num_hidden_layers"]
    d = file["hidden_size"]
    heads, head = file["mamba_n_heads"], file["mamba_d_head"]
    assert heads * head == file["mamba_expand"] * d
    return HybridSSMMoEConfig(
        vocab_size=file["vocab_size"],
        max_seq_len=file["max_position_embeddings"], layer_types=types,
        d_model=d, ssm_heads=heads, ssm_head_dim=head,
        ssm_state=file["mamba_d_state"], conv_kernel=file["mamba_d_conv"],
        ssm_chunk=file["mamba_chunk_size"],
        n_head=file["num_attention_heads"],
        n_kv_head=file["num_key_value_heads"],
        head_dim=d // file["num_attention_heads"],
        attn_scale=file["attention_multiplier"],
        n_experts=file.get("published", {}).get(
            "num_local_experts", file["num_local_experts"]),
        experts_per_token=file["num_experts_per_tok"],
        d_expert=file["intermediate_size"],
        d_shared=file["shared_intermediate_size"],
        held_experts=tuple(range(file["num_local_experts"])),
        embedding_multiplier=file["embedding_multiplier"],
        residual_multiplier=file["residual_multiplier"],
        logits_scaling=file["logits_scaling"], eps=file["rms_norm_eps"],
        dtype=jnp.bfloat16)


build.published = lambda c: {
    "hidden_size": c.d_model, "intermediate_size": c.d_expert,
    "shared_intermediate_size": c.d_shared,
    "mamba_expand": c.d_inner // c.d_model, "mamba_n_heads": c.ssm_heads,
    "mamba_d_head": c.ssm_head_dim, "mamba_d_state": c.ssm_state,
    "mamba_d_conv": c.conv_kernel, "mamba_chunk_size": c.ssm_chunk,
    "num_attention_heads": c.n_head, "num_key_value_heads": c.n_kv_head,
    "attention_multiplier": c.attn_scale,
    "num_experts_per_tok": c.experts_per_token,
    "num_local_experts": len(c.held), "num_hidden_layers": c.n_layer,
    "vocab_size": c.vocab_size, "max_position_embeddings": c.max_seq_len,
    "embedding_multiplier": c.embedding_multiplier,
    "residual_multiplier": c.residual_multiplier,
    "logits_scaling": c.logits_scaling, "rms_norm_eps": c.eps}


def init(cfg, key, dtype=None):
    """Seeded weights in the type served (``assumed`` of the configuration
    file gives the scales and the state-space layers' ranges)."""
    import dataclasses

    from deepspeed_tpu.models import hybrid_ssm_moe
    if dtype is not None:
        cfg = dataclasses.replace(cfg, param_dtype=dtype)
    return hybrid_ssm_moe.init(cfg, key, std=0.02)


def decode_count(cfg, rows_context_tokens: float):
    """``(operations, bytes)`` one grouped-head decode call of one attention
    layer must spend on ``rows_context_tokens`` cached tokens: every query
    head scores and weighs its key-value head's ``head_dim`` elements
    (``4 H D`` operations a token); the row, K and V of the key-value heads,
    is read once, two bytes an element."""
    return (4.0 * rows_context_tokens * cfg.n_head * cfg.head_dim,
            2.0 * rows_context_tokens * sum(cfg.cache_row))


def expert_count(cfg, pairs: float, visits: float):
    """``latent_moe_family.expert_count`` at this family's widths: a pair is
    three products of ``d x f``; a visit reads that expert's three matrices
    once."""
    per = 3.0 * cfg.d_model * cfg.d_expert
    return 2.0 * pairs * per, 2.0 * visits * per


def state_step_count(cfg, rows: float):
    """``(operations, bytes)`` the decode step must spend on ``rows`` (live
    slot, state-space layer) meetings: the row's state is read and written
    once, float32 (``2 x d_state x d_inner x 4`` bytes); an element takes
    its decay, its share of ``dt v B^T`` and of ``H C`` (5 operations)."""
    elements = rows * cfg.ssm_state * cfg.d_inner
    return 5.0 * elements, 8.0 * elements


def chunk_scan_count(cfg, tokens: float):
    """``(operations, bytes)`` the chunk scan must spend on ``tokens``
    (token, state-space layer) meetings, padded ones included (the kernel
    is handed whole chunks): a token's row of ``C B^T`` over its sub-chunk
    (``2 Q N``), its heads' ``(L o C B^T) dt v`` (``2 Q d_inner``), its
    read of the carried state and its write to it (``2 N d_inner`` each);
    ``dt v`` in and ``y`` out in float32, ``B`` and ``C`` twice (the state
    itself moves once a chunk: counted with the inputs' rounding)."""
    Q, N, di = cfg.ssm_chunk, cfg.ssm_state, cfg.d_inner
    return (tokens * (2.0 * Q * N + 2.0 * Q * di + 4.0 * N * di),
            tokens * (8.0 * di + 8.0 * N))
