"""The gated-short-convolution family (``models/conv_moe.py``) for the
benchmark: from an ``lfm2_moe`` ``config.json`` to the program's config, the
weights, and what the family's kernels must compute and read.

``layer_types`` gives each layer its mixer (``conv``: the gated short
convolution of ``conv_L_cache`` taps; ``full_attention``: grouped rotary
heads of ``hidden_size / num_attention_heads`` elements); the first
``num_dense_layers`` layers end in a dense SwiGLU of ``intermediate_size``,
the others in ``num_experts`` experts of ``moe_intermediate_size``,
``num_experts_per_tok`` a token, ALL of them held here: the file's cut is in
depth (``num_hidden_layers``, with ``layer_types`` its first that many
entries) and in nothing else.

What this module cannot serve it refuses by name (the asserts of ``build``):
a sibling configuration is never served as something it is not."""

from __future__ import annotations

import math

KINDS = {"conv": "conv", "full_attention": "attention"}
#: the head's elements: the config has no key for them
HEAD_DIM = 64


def build(file: dict):
    import jax.numpy as jnp
    from deepspeed_tpu.models.conv_moe import ConvMoEConfig
    assert file["model_type"] == "lfm2_moe"
    assert not file["conv_bias"], "conv_bias: the convolution has no bias"
    assert not file.get("rope_scaling"), \
        "rope_scaling: one plain rotary table, nothing scaled"
    assert file.get("tie_word_embeddings", True), \
        "an untied head: the head is the embedding"
    assert file["conv_L_cache"] == 3, \
        "conv_L_cache: three taps (a tail of two) until another is tested"
    assert file["norm_topk_prob"], \
        "norm_topk_prob: the chosen scores are normalised"
    assert file["use_expert_bias"], \
        "use_expert_bias: the gate's choice is by score + bias"
    types = file["layer_types"]
    n, dense = file["num_hidden_layers"], file["num_dense_layers"]
    assert len(types) == n and set(types) <= set(KINDS), \
        f"layer_types: {sorted(set(types) - set(KINDS))} beside conv and " \
        "full_attention"
    # the dense FFNs lead, contiguous: a run of their own
    assert 0 <= dense <= n, "num_dense_layers"
    assert file["hidden_size"] == file["num_attention_heads"] * HEAD_DIM, \
        "hidden_size / num_attention_heads: heads of 64"
    return ConvMoEConfig(
        vocab_size=file["vocab_size"],
        max_seq_len=file["max_position_embeddings"],
        layer_types=tuple(KINDS[t] for t in types), n_dense=dense,
        d_model=file["hidden_size"], conv_kernel=file["conv_L_cache"],
        n_head=file["num_attention_heads"],
        n_kv_head=file["num_key_value_heads"], head_dim=HEAD_DIM,
        rope_theta=float(file["rope_theta"]),
        d_ff=file["intermediate_size"], n_experts=file["num_experts"],
        experts_per_token=file["num_experts_per_tok"],
        d_expert=file["moe_intermediate_size"],
        routed_scale=float(file["routed_scaling_factor"]),
        eps=file["norm_eps"], dtype=jnp.bfloat16)


build.published = lambda c: {
    "hidden_size": c.d_model, "intermediate_size": c.d_ff,
    "moe_intermediate_size": c.d_expert, "conv_L_cache": c.conv_kernel,
    "num_attention_heads": c.n_head, "num_key_value_heads": c.n_kv_head,
    "num_experts": c.n_experts, "num_experts_per_tok": c.experts_per_token,
    "num_dense_layers": c.n_dense, "num_hidden_layers": c.n_layer,
    "routed_scaling_factor": c.routed_scale, "rope_theta": c.rope_theta,
    "vocab_size": c.vocab_size, "max_position_embeddings": c.max_seq_len,
    "norm_eps": c.eps}


#: The embedding's scale, ``nemotron_h_family.EMBED_STD``'s for its reason:
#: this block has no embedding multiplier, so at the other matrices' 0.02
#: every logit is made of layer outputs alone; at 12 x 0.02 the embedding is
#: a part of the last layer's stream, as a trained embedding's is.  The head
#: is the same matrix, so a logit is ``0.24 |x|`` a unit of the norm's
#: output.
EMBED_STD = 12 * 0.02
#: The routed experts' down-projection over the other matrices' scale, after
#: ``nemotron_h_family.ROUTED_DOWN_SCALE`` and for its reason: where bf16 and
#: float32 choose differently a whole pair is in one and not in the other,
#: and with EVERY expert here every such pair is a held one.  At an eighth a
#: compared position with such a choice reads 0.018-0.019 rms beside the
#: others' 0.010-0.013 and a prompt 0.0158 against ``compare.py``'s 0.018 (one
#: seed of thirteen); at a sixteenth every position of the same seeds reads
#: 0.009-0.013 and every prompt 0.0100-0.0120, while the routed product left
#: out still reads 0.0270-0.0309, not correct (my chip runs, PR 61:
#: PERF.md 6).
ROUTED_DOWN_SCALE = 1 / 16


def init(cfg, key, dtype=None):
    """Seeded weights in the type served (``assumed`` of the configuration
    file gives the scales, ``EMBED_STD`` and ``ROUTED_DOWN_SCALE`` among
    them; the convolution's taps draw ``1 / sqrt(3)``: ``conv_moe.init``)."""
    import dataclasses

    from deepspeed_tpu.models import conv_moe
    if dtype is not None:
        cfg = dataclasses.replace(cfg, param_dtype=dtype)
    out_std = 0.02 / math.sqrt(2 * cfg.n_layer)
    return conv_moe.init(cfg, key, std=0.02, embed_std=EMBED_STD,
                         routed_out_std=out_std * ROUTED_DOWN_SCALE)


# One grouped row, one count, and one SwiGLU expert, as the window-and-full
# family's: every query head scores and weighs its key-value head's
# ``head_dim`` elements (``4 H D`` = 8,192 operations a cached token a layer)
# and the row, K and V of the 8 key-value heads, is read once (2,048 B): 4
# operations a byte, memory bound; a pair is three products of ``d x f``
# (2048 x 1792) and a visit reads that expert's three matrices once.
from .mellum_family import decode_count, expert_count  # noqa: E402,F401


def chunk_count(cfg, admissions):
    """``(operations, bytes, calls)`` the chunk-attention kernel must spend
    on the prompt passes of ``admissions``, ``(start, chunks, chunk)`` each
    (``mellum_family.chunk_count``'s full-layer half): one call an attention
    layer a chunk, padded queries included; the query at position ``p``
    scores and weighs the ``p + 1`` keys before and at it, ``4 H D``
    operations a (query, key) pair; a call reads the rows its queries' keys
    span once (K and V of the key-value heads: the prefix and the chunk) and
    its queries, and writes as much."""
    import numpy as np
    n = cfg.count("attention")
    row, qo = 2.0 * sum(cfg.cache_row), 4.0 * cfg.n_head * cfg.head_dim
    pairs = nbytes = calls = 0.0
    for start, chunks, C in admissions:
        for i in range(int(chunks)):
            seen = start + i * C + 1.0 + np.arange(C)
            pairs += n * seen.sum()
            nbytes += n * (C * qo + row * seen[-1])
            calls += n
    return 4.0 * pairs * cfg.n_head * cfg.head_dim, nbytes, calls
