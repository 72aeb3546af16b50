"""The linear-attention / grouped-attention family
(``models/linear_gqa_moe.py``) for the benchmark: from a ``solar_open2``
``config.json`` to the program's config, the weights, and what the family's
kernels must compute and read.

``gqa_layers`` (0-indexed: the grouped layer LEADS its period),
``gqa_interval`` and ``linear_attn_config`` are kept whole as published; the
builder takes the layers below ``num_hidden_layers``, and every layer that
is not in the list is a KDA layer.  The file's ``n_routed_experts`` counts
the experts *held here* (the chip's share of a deployment, listed in
``reduced``): the first that many of the deployment's; the router's width is
the published count (``published.n_routed_experts``).

What this module cannot serve it refuses by name (the asserts of ``build``):
a sibling configuration is never served as something it is not."""

from __future__ import annotations

import math


def build(file: dict):
    import jax.numpy as jnp
    from deepspeed_tpu.models.linear_gqa_moe import LinearGQAMoEConfig
    assert file["model_type"] == "solar_open2"
    assert file["first_k_dense_replace"] == 0, \
        "first_k_dense_replace: leading dense layers (every layer here is " \
        "an expert layer)"
    assert not file["use_rope"], \
        "use_rope: the grouped layers have no rotation and no position"
    assert not file["tie_word_embeddings"], \
        "tie_word_embeddings: the head is a matrix of its own"
    assert file["use_gqa_gate"], \
        "use_gqa_gate off: an ungated NoPE grouped layer beside a state is " \
        "granite-4.0-h-small-ep4's family's attention, not this one's"
    assert not file["kda_use_full_proj"], \
        "kda_use_full_proj: the decay and the output gate are low-rank pairs"
    assert file["kda_allow_neg_eigval"], \
        "kda_allow_neg_eigval off (beta in (0, 1)): " \
        "kimi-linear-48b-a3b-ep8's family, not this one"
    assert file["n_shared_experts"] == 1, \
        "n_shared_experts: one shared expert beside the routed ones"
    assert file["norm_topk_prob"], \
        "norm_topk_prob: the chosen scores are normalised"
    lin = file["linear_attn_config"]
    assert lin["num_kv_heads"] in (None, lin["num_heads"]), \
        "linear_attn_config.num_kv_heads: a KDA head has its own key and value"
    # stated and used by no layer: there is no dense FFN, and nothing rotates
    assert file["intermediate_size"] == \
        file["num_experts_per_tok"] * file["moe_intermediate_size"]
    n = file["num_hidden_layers"]
    return LinearGQAMoEConfig(
        vocab_size=file["vocab_size"],
        max_seq_len=file["max_position_embeddings"], n_layer=n,
        gqa_layers=tuple(i for i in file["gqa_layers"] if i < n),
        d_model=file["hidden_size"], d_expert=file["moe_intermediate_size"],
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        conv_kernel=lin["short_conv_kernel_size"], kda_beta_scale=2.0,
        n_head=file["num_attention_heads"],
        n_kv_head=file["num_key_value_heads"], head_dim=file["head_dim"],
        n_experts=file.get("published", {}).get(
            "n_routed_experts", file["n_routed_experts"]),
        experts_per_token=file["num_experts_per_tok"],
        n_shared_experts=file["n_shared_experts"],
        held_experts=tuple(range(file["n_routed_experts"])),
        routed_scale=float(file["routed_scaling_factor"]),
        norm_topk=file["norm_topk_prob"], eps=file["rms_norm_eps"],
        dtype=jnp.bfloat16)


build.published = lambda c: {
    "hidden_size": c.d_model, "head_dim": c.head_dim,
    "num_attention_heads": c.n_head, "num_key_value_heads": c.n_kv_head,
    "intermediate_size": c.experts_per_token * c.d_expert,
    "moe_intermediate_size": c.d_expert,
    "num_experts_per_tok": c.experts_per_token,
    "n_shared_experts": c.n_shared_experts, "first_k_dense_replace": 0,
    "num_hidden_layers": c.n_layer, "n_routed_experts": len(c.held),
    "vocab_size": c.vocab_size, "max_position_embeddings": c.max_seq_len,
    "routed_scaling_factor": c.routed_scale, "rms_norm_eps": c.eps}


#: The embedding's scale, ``kimi_linear_family.EMBED_STD``'s for its reason:
#: this block has no embedding multiplier, and at the other matrices' 0.02
#: every logit is made of layer outputs alone.
EMBED_STD = 12 * 0.02
#: The routed experts' down-projection over the other matrices' scale, after
#: ``kimi_linear_family.ROUTED_DOWN_SCALE`` and for its reason: where bf16 and
#: float32 choose differently a whole held pair is in one and not in the
#: other.  At a quarter a sound run read 0.0129-0.0148 largest and
#: 0.0122-0.0132 rms over three seeds, 1.4 times under the rms limit, and the
#: routed product left out 0.068 / 0.072 (my chip runs, PR 64); at an eighth
#: the readings are the configuration file's ``precision_judged``.
ROUTED_DOWN_SCALE = 1 / 8


def init(cfg, key, dtype=None):
    """Seeded weights in the type served (``assumed`` of the configuration
    file gives the scales, ``EMBED_STD`` and ``ROUTED_DOWN_SCALE`` among
    them, and the KDA layers' ranges)."""
    import dataclasses

    from deepspeed_tpu.models import linear_gqa_moe
    if dtype is not None:
        cfg = dataclasses.replace(cfg, param_dtype=dtype)
    out_std = 0.02 / math.sqrt(2 * cfg.n_layer)
    return linear_gqa_moe.init(cfg, key, std=0.02, embed_std=EMBED_STD,
                               routed_out_std=out_std * ROUTED_DOWN_SCALE)


# One grouped row and one SwiGLU expert, counted as the window-and-full
# family counts them: every query head scores and weighs its key-value head's
# ``head_dim`` elements (``4 H D`` = 32,768 operations a cached token of the
# grouped layer) and the row, K and V of the 8 key-value heads, is read once
# (4,096 B): 8 operations a byte, so the stream bounds it; a pair is three
# products of ``d x f`` (4096 x 1280) and a visit reads that expert's three
# matrices once (31.5 MB).
from .mellum_family import decode_count, expert_count  # noqa: E402,F401
# The KDA kernels, counted as the linear-attention family counts them, at 64
# heads: a (live slot, KDA layer) row of the step is ``d_k x heads d_v`` =
# 1,048,576 elements, 7 operations and 8 bytes each (2 x 4,194,304 B read and
# written); a (token, KDA layer) of the scan the WY form's scores, state
# reads and writes, and solves a head, padded tokens included.
from .kimi_linear_family import (  # noqa: E402,F401
    chunk_scan_count, state_step_count)


def chunk_count(cfg, admissions):
    """``(operations, bytes, calls)`` the chunk-attention kernel must spend
    on the prompt passes of ``admissions``, ``(start, chunks, chunk)`` each:
    one call a grouped layer a pass.  Every pass computes ``chunk`` rows
    here: ``serving.batcher.pass_widths`` gives a slot of more than four
    chunks no width under the chunk, so ``chunk`` a pass IS what ``serve.
    prefill``'s ``padded`` reads (``narrow`` 0), and nothing is credited
    that no pass computes (the three older counts over-read where a last
    pass is narrower: PERF.md 7).  The query at position ``p`` scores and
    weighs the ``p + 1`` keys before and at it, ``4 H D`` operations a
    (query, key) pair, padded queries included; a call reads the rows its
    queries' keys span once (K and V of the key-value heads: the prefix and
    the chunk) and its queries with their width again for what it writes."""
    import numpy as np
    n = cfg.cache_layers
    row, qo = 2.0 * sum(cfg.cache_row), 4.0 * cfg.n_head * cfg.head_dim
    pairs = nbytes = calls = 0.0
    for start, chunks, C in admissions:
        for i in range(int(chunks)):
            seen = start + i * C + 1.0 + np.arange(C)
            pairs += n * seen.sum()
            nbytes += n * (C * qo + row * seen[-1])
            calls += n
    return 4.0 * pairs * cfg.n_head * cfg.head_dim, nbytes, calls
