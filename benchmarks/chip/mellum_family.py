"""The window-and-full attention family (``models/window_moe.py``) for the
benchmark: from a ``mellum`` ``config.json`` to the program's config, the
weights, and what the family's kernels must compute and read.

``layer_types`` gives each layer its kind (``sliding_attention``: a window
of ``sliding_window`` tokens; ``full_attention``), ``rope_parameters`` each
kind's rotary table, ``mlp_layer_types`` says every layer's MLP is sparse.
The file's ``num_experts`` counts the experts *held here* (the chip's share
of a deployment, listed in ``reduced``): the first that many of the
deployment's; the router's width is the published count
(``published.num_experts``).

What this module cannot serve it refuses by name (the asserts of ``build``):
a sibling configuration is never served as something it is not."""

from __future__ import annotations

KINDS = {"sliding_attention": "window", "full_attention": "full"}


def build(file: dict):
    import jax.numpy as jnp
    from deepspeed_tpu.models.window_moe import WindowMoEConfig
    assert file["model_type"] == "mellum"
    assert file["hidden_act"] == "silu" and not file["attention_bias"]
    assert not file["tie_word_embeddings"], "the head is a matrix of its own"
    assert file["norm_topk_prob"], "weights: a softmax over the chosen"
    assert set(file["mlp_layer_types"]) == {"sparse"}, \
        "every layer's MLP is the expert layer: no dense block"
    # stated and unused here: a dense block's width, the active experts'
    assert file["intermediate_size"] == \
        file["num_experts_per_tok"] * file["moe_intermediate_size"]
    assert file["use_sliding_window"] and not file["max_window_layers"]
    types = file["layer_types"]
    assert len(types) == len(file["mlp_layer_types"]) \
        == file["num_hidden_layers"] and set(types) <= set(KINDS), types
    rope = file["rope_parameters"]
    plain, yarn = rope["sliding_attention"], rope["full_attention"]
    assert plain["rope_type"] == "default" and yarn["rope_type"] == "yarn" \
        and plain["rope_theta"] == yarn["rope_theta"]
    return WindowMoEConfig(
        vocab_size=file["vocab_size"],
        max_seq_len=file["max_position_embeddings"],
        layer_types=tuple(KINDS[t] for t in types),
        d_model=file["hidden_size"], n_head=file["num_attention_heads"],
        n_kv_head=file["num_key_value_heads"], head_dim=file["head_dim"],
        window=file["sliding_window"], qk_norm=True,
        rope_theta=float(yarn["rope_theta"]),
        yarn=(float(yarn["factor"]),
              int(yarn["original_max_position_embeddings"]),
              float(yarn["beta_fast"]), float(yarn["beta_slow"]),
              float(yarn["attention_factor"])),
        n_experts=file.get("published", {}).get("num_experts",
                                                file["num_experts"]),
        experts_per_token=file["num_experts_per_tok"],
        d_expert=file["moe_intermediate_size"],
        held_experts=tuple(range(file["num_experts"])),
        eps=file["rms_norm_eps"], dtype=jnp.bfloat16)


build.published = lambda c: {
    "hidden_size": c.d_model, "moe_intermediate_size": c.d_expert,
    "intermediate_size": c.experts_per_token * c.d_expert,
    "num_attention_heads": c.n_head, "num_key_value_heads": c.n_kv_head,
    "head_dim": c.head_dim, "sliding_window": c.window,
    "num_experts_per_tok": c.experts_per_token,
    "num_experts": len(c.held), "num_hidden_layers": c.n_layer,
    "vocab_size": c.vocab_size, "max_position_embeddings": c.max_seq_len,
    "rms_norm_eps": c.eps}


#: The embedding's scale, ``nemotron_h_family.EMBED_STD``'s for its reason:
#: this block has no embedding multiplier, so at the other matrices' 0.02
#: every logit is made of layer outputs alone; at 12 x 0.02 the embedding is
#: a part of the last layer's stream, as a trained embedding's is.
EMBED_STD = 12 * 0.02


def init(cfg, key, dtype=None):
    """Seeded weights in the type served (``assumed`` of the configuration
    file gives the scales)."""
    import dataclasses

    from deepspeed_tpu.models import window_moe
    if dtype is not None:
        cfg = dataclasses.replace(cfg, param_dtype=dtype)
    return window_moe.init(cfg, key, std=0.02, embed_std=EMBED_STD)


def decode_count(cfg, rows_context_tokens: float):
    """``(operations, bytes)`` one single-token sweep of one layer of either
    kind must spend on ``rows_context_tokens`` cached tokens (a ring's: the
    tokens its cells hold, never the context's): every query head scores
    and weighs its key-value head's ``head_dim`` elements (``4 H D`` = 16,384
    operations a token); the row, K and V of the 4 key-value heads, is read
    once, two bytes an element (2,048 B)."""
    return (4.0 * rows_context_tokens * cfg.n_head * cfg.head_dim,
            2.0 * rows_context_tokens * sum(cfg.cache_row))


def expert_count(cfg, pairs: float, visits: float):
    """``latent_moe_family.expert_count`` at this family's widths: a pair is
    three products of ``d x f`` (2304 x 896); a visit reads that expert's
    three matrices once."""
    per = 3.0 * cfg.d_model * cfg.d_expert
    return 2.0 * pairs * per, 2.0 * visits * per


def chunk_count(cfg, admissions):
    """``(operations, bytes, calls)`` the chunk-attention kernel must spend
    on the prompt passes of ``admissions``, ``(start, chunks, chunk)`` each:
    one call a layer a chunk, padded queries included (the kernel is handed
    whole chunks).  The query at position ``p`` scores and weighs the ``p +
    1`` keys before and at it on a full layer, ``min(p + 1, window)`` on a
    window layer, ``4 H D`` operations a (query, key) pair; a call reads
    the rows its queries' keys span once (K and V of the key-value heads:
    the prefix and the chunk on a full layer, the ring and the chunk on a
    window layer) and its queries, and writes as much."""
    import numpy as np
    n_full, n_window = cfg.count("full"), cfg.count("window")
    row, qo = 2.0 * sum(cfg.cache_row), 4.0 * cfg.n_head * cfg.head_dim
    pairs = nbytes = calls = 0.0
    for start, chunks, C in admissions:
        for i in range(int(chunks)):
            seen = start + i * C + 1.0 + np.arange(C)
            pairs += n_full * seen.sum() \
                + n_window * np.minimum(seen, cfg.window).sum()
            nbytes += (n_full + n_window) * C * qo + row * (
                n_full * seen[-1]
                + n_window * min(seen[-1], cfg.window + C))
            calls += n_full + n_window
    return 4.0 * pairs * cfg.n_head * cfg.head_dim, nbytes, calls
