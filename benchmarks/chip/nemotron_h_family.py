"""The hybrid family's single-part block (``models/hybrid_ssm_moe.py`` with
``mixer_ffn`` off) for the benchmark: from a ``nemotron_h`` ``config.json``
to the program's config, the weights, and what the family's kernels must
compute and read.

``hybrid_override_pattern`` gives each block ONE part: ``M`` a Mamba-2
mixer, ``E`` the expert layer, ``*`` attention; the file holds the first
``num_hidden_layers`` published characters (it is cut with the depth).  The
file's ``n_routed_experts`` counts the experts *held here* (the chip's share
of a deployment, listed in ``reduced``): the first that many of the
deployment's; the router's width is the published count
(``published.n_routed_experts``).

What this module cannot serve it refuses by name (the asserts of ``build``):
a sibling configuration is never served as something it is not."""

from __future__ import annotations

KINDS = {"M": "mamba", "E": "experts", "*": "attention"}


def build(file: dict):
    import jax.numpy as jnp
    from deepspeed_tpu.models.hybrid_ssm_moe import HybridSSMMoEConfig
    from deepspeed_tpu.moe.held_experts import RELU2
    assert file["model_type"] == "nemotron_h"
    assert file["n_group"] == file["topk_group"] == 1, \
        "the gate selects among all experts: no expert groups"
    assert file["mlp_hidden_act"] == "relu2" \
        and file["mamba_hidden_act"] == "silu"
    assert not file["tie_word_embeddings"], "the head is a matrix of its own"
    assert not (file["attention_bias"] or file["mlp_bias"]
                or file["use_bias"] or file["mamba_proj_bias"]) \
        and file["use_conv_bias"], "no bias but the convolution's"
    assert file["norm_topk_prob"] and file["n_shared_experts"] == 1
    assert not file.get("moe_latent_size"), \
        "latent experts (a projection around the routed experts)"
    assert not file.get("num_nextn_predict_layers"), "MTP layers"
    assert file["norm_eps"] == file["layer_norm_epsilon"]
    # stated and unused here: the inner width is heads x head_dim, and the
    # pattern holds no dense MLP block (``-``) for ``intermediate_size``
    assert file["expand"] == 2 \
        and file["intermediate_size"] == file["moe_intermediate_size"]
    pattern = file["hybrid_override_pattern"]
    assert len(pattern) == file["num_hidden_layers"] \
        and set(pattern) <= set(KINDS), pattern
    return HybridSSMMoEConfig(
        vocab_size=file["vocab_size"],
        max_seq_len=file["max_position_embeddings"],
        layer_types=tuple(KINDS[c] for c in pattern),
        d_model=file["hidden_size"], ssm_heads=file["mamba_num_heads"],
        ssm_head_dim=file["mamba_head_dim"],
        ssm_state=file["ssm_state_size"], conv_kernel=file["conv_kernel"],
        ssm_chunk=file["chunk_size"], ssm_groups=file["n_groups"],
        n_head=file["num_attention_heads"],
        n_kv_head=file["num_key_value_heads"], head_dim=file["head_dim"],
        attn_scale=file["head_dim"] ** -0.5,
        n_experts=file.get("published", {}).get(
            "n_routed_experts", file["n_routed_experts"]),
        experts_per_token=file["num_experts_per_tok"],
        d_expert=file["moe_intermediate_size"],
        d_shared=file["moe_shared_expert_intermediate_size"],
        held_experts=tuple(range(file["n_routed_experts"])),
        mixer_ffn=False, expert_form=RELU2, gate="sigmoid",
        routed_scale=file["routed_scaling_factor"],
        tie_head=False, eps=file["norm_eps"],
        dtype=jnp.bfloat16)


build.published = lambda c: {
    "hidden_size": c.d_model, "moe_intermediate_size": c.d_expert,
    "intermediate_size": c.d_expert, "expand": 2,
    "moe_shared_expert_intermediate_size": c.d_shared,
    "mamba_num_heads": c.ssm_heads, "mamba_head_dim": c.ssm_head_dim,
    "ssm_state_size": c.ssm_state, "conv_kernel": c.conv_kernel,
    "chunk_size": c.ssm_chunk, "n_groups": c.ssm_groups,
    "num_attention_heads": c.n_head, "num_key_value_heads": c.n_kv_head,
    "head_dim": c.head_dim, "num_experts_per_tok": c.experts_per_token,
    "n_routed_experts": len(c.held), "num_hidden_layers": c.n_layer,
    "routed_scaling_factor": c.routed_scale, "vocab_size": c.vocab_size,
    "max_position_embeddings": c.max_seq_len, "norm_eps": c.eps}


#: The embedding's scale.  This block has no embedding multiplier (Granite's
#: is 12), so at the other matrices' 0.02 the first mixer's output is ten
#: times the embedding and every logit is made of layer outputs alone: bf16
#: against the float32 reference then reads 0.014-0.03 rms a position, at
#: and over ``compare.py``'s 0.018.  At 12 x 0.02 the embedding is about a
#: fifth of the last layer's stream, as a trained embedding's is, and bf16
#: reads 0.010 (CPU at the published widths: PERF.md 6, PR 44).
EMBED_STD = 12 * 0.02
#: The routed experts' down-projection over the other matrices' scale, after
#: ``latent_moe_family.ROUTED_DOWN_SCALE`` and for its reason: the sigmoid
#: gate gives a token's sixth choice the weight of its first (2.5 / 6), so
#: where bf16 and float32 choose a held expert differently (a third of the
#: compared positions at 18 layers) a whole pair is in one and not in the
#: other: at full scale that reads 0.11-0.18 rms, at an eighth still 0.022 a
#: position, at a sixteenth nothing (0.0136 the worst position), while the
#: routed product left out still reads twice over the rms limit
#: (``reference/nemotron_h_control.py --fault zero``).
ROUTED_DOWN_SCALE = 1 / 16


def init(cfg, key, dtype=None):
    """Seeded weights in the type served (``assumed`` of the configuration
    file gives the scales, ``EMBED_STD`` and ``ROUTED_DOWN_SCALE`` among
    them, and the state-space layers' ranges)."""
    import dataclasses
    import math

    from deepspeed_tpu.models import hybrid_ssm_moe
    if dtype is not None:
        cfg = dataclasses.replace(cfg, param_dtype=dtype)
    out_std = 0.02 / math.sqrt(2 * cfg.n_layer)
    return hybrid_ssm_moe.init(cfg, key, std=0.02, embed_std=EMBED_STD,
                               routed_out_std=out_std * ROUTED_DOWN_SCALE)


# One family, one count: the grouped-head decode call (``4 H D`` operations
# a cached token: 16,384; K and V of the 2 key-value heads read once: 1,024
# B) and the state step (a row's float32 state read and written once, 2 x
# 2.097 MB, 5 operations an element) are counted as Granite's are.
from .hybrid_ssm_moe_family import decode_count, state_step_count  # noqa: E402,F401


def expert_count(cfg, pairs: float, visits: float):
    """A pair is TWO products of ``d x f`` (up, down; no gate); a visit
    reads that expert's two matrices once.  ``f`` is the published 1856
    whatever width the matrices are stored at."""
    per = 2.0 * cfg.d_model * cfg.d_expert
    return 2.0 * pairs * per, 2.0 * visits * per


def chunk_scan_count(cfg, tokens: float):
    """``(operations, bytes)`` the chunk scan must spend on ``tokens``
    (token, state-space layer) meetings, padded ones included: a token's row
    of each group's ``C B^T`` over its sub-chunk (``2 Q N`` a group), its
    heads' ``(L o C B^T) dt v`` (``2 Q d_inner``), its read of the carried
    state and its write to it (``2 N d_inner`` each); ``dt v`` in and ``y``
    out in float32, every group's ``B`` and ``C`` twice."""
    Q, N, di, G = cfg.ssm_chunk, cfg.ssm_state, cfg.d_inner, cfg.ssm_groups
    return (tokens * (2.0 * Q * N * G + 2.0 * Q * di + 4.0 * N * di),
            tokens * (8.0 * di + 8.0 * N * G))
