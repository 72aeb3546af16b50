"""KV-cached inference for the GPT-MoE family: an expert FFN and a scan step.

Counterpart of the reference's MoE inference stack
(``ops/transformer/inference/moe_inference.py`` ``DeepSpeedMoEInference``
and the expert-group creation in ``inference/engine.py:190``): the gate runs
in eval mode (dropless — see ``_moe_infer_obj``; no RTS/aux loss) and experts
shard over the ``expert`` mesh axis declaratively — the all-to-all the
reference issues by hand falls out of XLA's dispatch/combine einsums.

A GPT-MoE stack is the dense stack with an expert FFN on every second layer,
and nothing about its cache differs.  The cache class, the pool layout
``[n_layer, B, S_max, H*D]`` (layers in depth order), the layer scan and the
slot ops are ``gpt_inference``'s own; this module's ``FAMILY`` supplies what
one scan step does — a dense block at layer ``2i``, an expert block at layer
``2i+1`` — and a prompt pass that bounds the gate's dispatch tensors.

What this family could share with ``latent_moe_inference`` and does not yet:
the expert FFN.  ``moe/held_experts.py`` (a router, then the held experts'
pairs multiplied grouped, dropless, linear in the tokens of a call) would
replace ``_moe_infer_obj``'s ``[t, E, t]`` dispatch and with it
``_PREFILL_CHUNK``'s 128-token walk; it needs the GShard softmax gate as a
second ``route`` and GELU experts with biases in the grouped product
(ROADMAP D2/D3).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax.numpy as jnp

from . import gpt, gpt_inference
from .gpt_inference import KVCache
from .gpt_moe import GPTMoEConfig, _moe_obj, logical_axes

PyTree = Any


def apply(params: PyTree, tokens: jnp.ndarray, config: GPTMoEConfig):
    """Uncached full-sequence logits (eval gating)."""
    from . import gpt_moe
    return gpt_moe.apply(params, tokens, config, train=False)[0]


def _moe_infer_obj(config: GPTMoEConfig):
    """Dropless gate for serving: eval capacity gating can mask tokens
    when routing skews (capacity = max(int(t·k·cf/E), min_capacity)),
    which at inference silently corrupts served logits and — because
    capacity depends on the per-call token count — makes a K+1-token
    verify chunk route differently from K+1 single-token decodes.  The
    inference family therefore reserves worst-case capacity (= tokens per
    call; calls are small chunks, so the [t,E,C=t] dispatch stays cheap),
    making decode/extend/prefill exact and mutually consistent — the
    contract speculative verification rides.  Training/eval ``apply``
    keeps capacity gating for throughput, as the reference does
    (sharded_moe.py:278)."""
    return _moe_obj(config, drop_tokens=False)


def _moe_ffn(x, attn_p, moe_p, moe, config: GPTMoEConfig):
    """Post-attention expert FFN half (eval gating)."""
    h2 = gpt._layer_norm(x, attn_p["ln2_scale"], attn_p["ln2_bias"])
    moe_out, _aux, _counts = moe.apply(moe_p, h2, train=False, constrain=None)
    return x + moe_out


def moe_step(params: PyTree, config: GPTMoEConfig, valid=None):
    """The GPT-MoE half of ``gpt_inference._layer_scan``: one segment whose
    scan step is one (dense, MoE) pair, layers ``2i`` and ``2i+1`` of the
    one pool."""
    moe = _moe_infer_obj(config)

    def body(x, p, i, attend, cache):
        dense_p, attn_p, moe_p = p
        a, cache = attend(x, dense_p, 2 * i, cache)
        x = gpt_inference._block_tail(x, a, dense_p, config)
        a, cache = attend(x, attn_p, 2 * i + 1, cache)
        x = x + gpt.attn_project(a, attn_p, config)
        return _moe_ffn(x, attn_p, moe_p, moe, config), cache

    return [((params["dense_blocks"], params["moe_attn_blocks"],
              params["moe_blocks"]), body)]


# dropless gating reserves capacity = tokens-per-call, so the dispatch/
# combine tensors are [t, E, t] — fine for decode/verify chunks, quadratic
# for a whole long prompt.  Prefill therefore processes at most this many
# tokens per gate call, walking longer prompts through `extend` (which
# composes exactly with prefill — tested contract).
_PREFILL_CHUNK = 128


def _bounded_prefill(params: PyTree, tokens: jnp.ndarray,
                     config: GPTMoEConfig, cache: KVCache, family,
                     valid=None,
                     head: bool = True) -> Tuple[jnp.ndarray, KVCache]:
    """Prompt pass filling the cache; returns (logits, cache), or with
    ``head`` False the layer stack's output in the logits' place
    (``gpt_inference.prefill``).

    Long prompts (> ``_PREFILL_CHUNK`` gated tokens) run as a chain of
    ``extend`` chunks to keep the dropless dispatch tensors bounded at
    [B·chunk, E, B·chunk] instead of [B·S, E, B·S]: ``prefill(t[:, :c]) ;
    extend(t[:, c:])`` equals one full ``prefill``, the contract the
    speculative verify pass rides too (dropless gating keeps rows and
    chunks independent, so neither chunking nor ragged ``lengths`` can
    perturb a token's routing).  ``valid`` is the families' common
    signature; no state here reads it, and the chunk walk drops it."""
    B, S = tokens.shape
    if B * S <= _PREFILL_CHUNK:
        return gpt_inference.prefill(params, tokens, config, cache,
                                     family=family, valid=valid, head=head)
    # chunk bounds depend only on the static shape, so this also
    # unrolls under an outer jit (the engine's whole-generate program)
    chunk = max(_PREFILL_CHUNK // B, 1)
    outs = []
    for s0 in range(0, S, chunk):
        out, cache = gpt_inference.extend(params, tokens[:, s0:s0 + chunk],
                                          config, cache, family=family,
                                          head=head)
        outs.append(out)
    return jnp.concatenate(outs, axis=1), cache


#: the dense family's row, projections and attention; the step, the prompt
#: pass and the uncached forward differ
FAMILY = dataclasses.replace(
    gpt_inference.DENSE, step=moe_step, prompt_pass=_bounded_prefill,
    apply=apply, logical_axes=logical_axes,
    unsupported={"draft": gpt_inference.DENSE_DRAFTS_ONLY})
