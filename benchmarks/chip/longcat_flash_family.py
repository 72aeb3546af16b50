"""The shortcut-connected double-layer family
(``models/shortcut_latent_moe.py``) for the benchmark: from a LongCat-Flash
``config.json`` to the program's config, the weights, and what the family's
kernels must compute and read.

The file's ``n_routed_experts`` counts the experts *held here* (the chip's
share of a deployment, listed in ``reduced``): the first that many of the
deployment's; the router's width is the published count
(``published.n_routed_experts``) plus ``zero_expert_num``.  ``num_layers``
counts DOUBLE layers, as the source does.

What this module cannot serve it refuses by name (the asserts of ``build``):
a sibling configuration is never served as something it is not."""

from __future__ import annotations

import math


def build(file: dict):
    import jax.numpy as jnp
    from deepspeed_tpu.models.shortcut_latent_moe import (
        ShortcutLatentMoEConfig)
    assert file["attention_method"] == "MLA", \
        "attention_method: latent attention (MLA) is the one served"
    assert file["zero_expert_type"] == "identity", \
        "zero_expert_type: only identity zero-compute experts are served"
    assert file["mla_scale_q_lora"] and file["mla_scale_kv_lora"], \
        "mla_scale_q_lora / mla_scale_kv_lora off: both latents are rescaled"
    assert not file["attention_bias"], "attention_bias"
    assert not file.get("rope_scaling"), \
        "rope_scaling: plain rotary frequencies are the ones served"
    assert not file.get("tie_word_embeddings", False), \
        "tie_word_embeddings: the head is a matrix of its own"
    assert not file.get("mtp_num_layers") \
        and not file.get("num_nextn_predict_layers"), \
        "an MTP layer is not served"
    assert not file.get("norm_topk_prob", False), \
        "norm_topk_prob: the gate's weights are not renormalised (untested)"
    assert not file.get("router_bias", False), \
        "router_bias: the router has no bias term of its own"
    return ShortcutLatentMoEConfig(
        vocab_size=file["vocab_size"],
        max_seq_len=file["max_position_embeddings"],
        n_layer=file["num_layers"], n_head=file["num_attention_heads"],
        d_model=file["hidden_size"], d_ff=file["ffn_hidden_size"],
        d_expert=file["expert_ffn_hidden_size"],
        q_rank=file["q_lora_rank"], kv_rank=file["kv_lora_rank"],
        d_nope=file["qk_nope_head_dim"], d_rope=file["qk_rope_head_dim"],
        d_v=file["v_head_dim"],
        n_experts=file.get("published", {}).get(
            "n_routed_experts", file["n_routed_experts"]),
        n_zero_experts=file["zero_expert_num"],
        experts_per_token=file["moe_topk"],
        held_experts=tuple(range(file["n_routed_experts"])),
        routed_scale=float(file["routed_scaling_factor"]),
        eps=file["rms_norm_eps"], rope_theta=float(file["rope_theta"]),
        dtype=jnp.bfloat16)


build.published = lambda c: {
    "hidden_size": c.d_model, "ffn_hidden_size": c.d_ff,
    "expert_ffn_hidden_size": c.d_expert,
    "num_attention_heads": c.n_head, "q_lora_rank": c.q_rank,
    "kv_lora_rank": c.kv_rank, "qk_nope_head_dim": c.d_nope,
    "qk_rope_head_dim": c.d_rope, "v_head_dim": c.d_v,
    "moe_topk": c.experts_per_token, "zero_expert_num": c.n_zero_experts,
    "num_layers": c.n_layer, "n_routed_experts": len(c.held),
    "vocab_size": c.vocab_size, "max_position_embeddings": c.max_seq_len,
    "routed_scaling_factor": c.routed_scale, "rms_norm_eps": c.eps,
    "rope_theta": c.rope_theta}


#: The scales the weights are drawn at beside the usual 0.02 into a layer
#: and 0.02 / sqrt(2 x 8 sublayers) back to the stream; each was set from
#: readings of ``reference/longcat_flash_control.py`` at the published widths
#: (largest / rms against ``compare.py``'s 0.03 / 0.018; my chip runs, PR 57,
#: PERF.md 6).
#:
#: The embedding's, after ``nemotron_h_family.EMBED_STD`` and for its reason:
#: this block has no embedding multiplier, and at the other matrices' 0.02
#: every logit is made of layer outputs alone (a dense FFN 12,288 wide writes
#: thirty times the embedding's rms back): bf16 against the float32 reference
#: then reads 0.026 / 0.0196 on a sound run, over the limit.  A sound run
#: reads 0.012 / 0.011 at 12 x 0.02, 0.0084 / 0.0078 at 1.0, 0.004 / 0.0038 at
#: 2.5, while the routed product left out reads 0.064 / 0.0455 at 1.0 and
#: 0.034 / 0.0226 at 2.5: at 1.0 both sides have a factor of two and more.
#: The precision below the stated one, every layer matrix on 255 levels a
#: channel (``int8``), reads 0.0331 / 0.0309 and 0.0370 / 0.0315 at 1.0
#: beside sound 0.0086 / 0.0078 and 0.0185 / 0.0096 (0.0465 / 0.0390 at 0.5,
#: 0.0517 / 0.0458 at 0.24): not ``correct`` at every scale tried, so 1.0
#: stays.
EMBED_STD = 1.0
#: The router's.  The gate weighs a chosen output by 6 p with no
#: renormalisation, and a zero-compute pair writes the NORMED input (unit
#: rms) back with that weight.  At 0.02 the logits spread by 1.6 and single
#: weights reach 0.1; at this scale they spread by 0.4 and a chosen weight is
#: about 0.02, so a 12th choice that bf16 and float32 make differently
#: (common: 12 of 768 crowded scores) moves little: 0.002 and 0.005 read
#: alike, and so does a router computed wholly in bf16 (``bf16_router``:
#: 0.0093 / 0.0078 beside a sound 0.0084 / 0.0078), which the check therefore
#: does NOT see (``longcat_flash_control.UNSEEN``); the control's
#: ``--in-common`` counts the choices themselves and does.
ROUTER_STD = 0.005
#: The selection bias': a hundredth of a uniform gate's probability (1/768),
#: the order of the gaps between neighbouring scores, so that selection by
#: ``p + b`` differs from selection by ``p``.
ROUTER_BIAS_STD = 0.01 / 768
#: The two up-projections out of the latents (``W_qb``, ``W_kvb``), after
#: ``dots3_family.UP_STD`` and for its reason: both latents leave their norms
#: rescaled (by 2 and by 3.46), so at 0.02 a head's scores spread by about
#: 3.5 and a sound run reads 0.045 / 0.041 (the embedding at 0.02) where
#: this scale reads 0.026 / 0.0196; halving it again gains nothing (0.030 /
#: 0.013 against 0.0084 / 0.0078, the embedding at 1.0).
UP_STD = 0.01
#: The routed experts' down-projection over the other matrices that write to
#: the stream.  A held pair weighs 0.02 where a sigmoid gate's weighs 0.3
#: (``latent_moe_family.ROUTED_DOWN_SCALE`` is an eighth for that gate), so
#: the routed product needs the opposite help to be seen at all: left out it
#: reads 0.064 / 0.0455 at 8 and 0.121 / 0.090 at 16, a sound run 0.0084 /
#: 0.0078 and 0.0081 / 0.0073.
ROUTED_DOWN_SCALE = 8.0


def init(cfg, key, dtype=None):
    """Seeded weights in the type served (``assumed.weights`` of the
    configuration file gives the scales and why each: ``EMBED_STD``,
    ``ROUTER_STD``, ``UP_STD``, ``ROUTED_DOWN_SCALE``)."""
    import dataclasses

    from deepspeed_tpu.models import shortcut_latent_moe
    if dtype is not None:
        cfg = dataclasses.replace(cfg, param_dtype=dtype)
    out_std = 0.02 / math.sqrt(2 * cfg.cache_layers)
    return shortcut_latent_moe.init(
        cfg, key, std=0.02, router_std=ROUTER_STD,
        routed_out_std=out_std * ROUTED_DOWN_SCALE,
        router_bias_std=ROUTER_BIAS_STD, up_std=UP_STD, embed_std=EMBED_STD)


# One latent row, one count (``kv_rank + d_rope`` = 576 elements scored by
# each of the 64 heads, ``kv_rank`` values weighed; read once, two bytes an
# element), and a held pair's three products of ``d x f``: the config
# carries ``latent_moe``'s names for those widths.  A zero-compute pair
# costs nothing and is not a pair here (``serve.moe_pairs``' ``held`` does
# not count it).
from .latent_moe_family import decode_count, expert_count  # noqa: E402,F401


def chunk_count(cfg, admissions):
    """``(operations, bytes, calls)`` the latent chunk-attention kernel must
    spend on the prompt passes of ``admissions``, ``(start, chunks, chunk)``
    each: TWO calls a layer a chunk (one an attention sublayer), padded
    queries included (the kernel is handed whole chunks).

    Counted for the CHEAPER of the two forms, the up-projected one: the
    query at position ``p`` scores ``p + 1`` keys of ``d_nope + d_rope``
    elements a head and weighs as many values of ``d_v`` (``2 H (192 +
    128)`` operations a (query, key) pair), and every key the call spans
    is up-projected once (``2 kv_rank H (d_nope + d_v)`` a key: the kernel
    the program runs needs no such product, so the form's whole cost is
    counted here).  The program runs the ABSORBED form (``2 H (576 + 512)``
    a pair, 3.4 times the pair's cost and no up-projection: 2.2 times the
    operations of a 1,024-token chunk at a prefix of 2,048), so this share
    reads what an up-projected prompt pass would leave of it.  A call reads
    the latent rows its queries' keys span once (576 elements, the lanes
    they are padded to not counted) and its queries, and writes its
    results, two bytes an element."""
    import numpy as np
    H = cfg.n_head
    pair = 2.0 * H * (cfg.d_nope + cfg.d_rope + cfg.d_v)
    up = 2.0 * cfg.kv_rank * H * (cfg.d_nope + cfg.d_v)
    row = 2.0 * (cfg.kv_rank + cfg.d_rope)
    qo = 2.0 * H * (cfg.d_nope + cfg.d_rope + cfg.d_v)
    ops = nbytes = calls = 0.0
    for start, chunks, C in admissions:
        for i in range(int(chunks)):
            seen = start + i * C + 1.0 + np.arange(C)
            ops += pair * seen.sum() + up * seen[-1]
            nbytes += C * qo + row * seen[-1]
            calls += 1
    n = cfg.cache_layers
    return n * ops, n * nbytes, n * calls
