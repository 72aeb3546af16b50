"""Cached inference for the linear-attention / grouped-attention family: a
step, a two-bank grouped row and a per-slot state, together.

The cache class, the layer scan and the slot ops are ``gpt_inference``'s
own (the one cache family of the tree); this module's ``FAMILY`` brings
what ``gpt_inference.Family`` asks of a model family:

- the **row** (``config.cache_row``): K and V of the key-value heads, two
  banks of ``n_kv_head * head_dim``, nothing rotated, for the grouped
  layers alone (``config.cache_layers``): the pool is ``[L_gqa, B, S_max,
  row]``;
- the **state** (``config.cache_state``): the linear-attention family's:
  ``S`` ``[L_kda, B, d_k, heads * d_v]`` float32 and the last ``conv_kernel
  - 1`` pre-activation inputs of the convolution over ``q | k | v``
  ``[L_kda, B, K - 1, 3 d_kda]``: the cache's ``state`` leaf; the slot ops
  insert, read and zero it with the banks;
- the **step**: ``linear_latent_moe_inference.kda_step``, the layer loop of
  the tree's KDA families (its ``_kda_mixer`` for a KDA layer, with ``beta``
  in (0, 2): ``config.kda_beta_scale``), with this family's full layer: a
  grouped layer goes through the scan's ``attend`` at its index among the
  grouped layers (``project`` returns the queries WITH their gates, ``(q,
  z)``; the hybrid state-space family's two calls of the grouped kernels
  attend, and the gate ``a * sigmoid(z)`` is applied to what they return,
  before ``W_o``).  The expert layers add their pair counts to
  ``cache.stats`` and a KDA layer its state counters (``STATE_COUNTERS``),
  each group where ``stats_groups`` says.

Not supported, refused where it is asked for (``UNSUPPORTED``): the int8
cache, paging, pooled prefixes, speculation and serving as a draft.  An
admission keeps the row cache (``gpt_inference.in_place`` is for caches of
two banks alone: a recurrence's state must not see a pass's padding).
"""

from __future__ import annotations

from typing import Any

import jax

from . import (gpt_inference, hybrid_ssm_moe_inference,
               linear_latent_moe_inference, linear_gqa_moe as model)
from .hybrid_ssm_moe_inference import STATE_COUNTERS
from .linear_gqa_moe import LinearGQAMoEConfig
from .linear_latent_moe_inference import kda_step, stats_groups

PyTree = Any

#: what this family is refused, in the linear-attention family's words (the
#: per-slot state is the same leaf) but for the int8 cache's: no latent row
UNSUPPORTED = {
    **linear_latent_moe_inference.UNSUPPORTED,
    "int8": "the linear-attention families cache in the compute dtype only: "
            "the state is float32 and has no heads' scale banks",
}


def _step(params: PyTree, config: LinearGQAMoEConfig, valid):
    def grouped(x, p, _, i, j, attend, cache):
        with jax.named_scope("gqa_attention"):
            a, cache = attend(x, p, j, cache)
            return model.attention_output(x, a, p, config), cache

    return kda_step(params, config, valid, grouped, model.ffn)


def _project(x, p, config: LinearGQAMoEConfig, positions):
    return model.attention_project(x, p, config, positions)


def _attend_cached(qz, cache, pos, config: LinearGQAMoEConfig, idx,
                   active=None, sweep=None):
    q, z = qz
    return model.gated(hybrid_ssm_moe_inference._attend_cached(
        q, cache, pos, config, idx, active=active, sweep=sweep), z)


def _attend_fresh(qz, fresh, cache, config: LinearGQAMoEConfig, idx):
    q, z = qz
    return model.gated(hybrid_ssm_moe_inference._attend_fresh(
        q, fresh, cache, config, idx), z)


FAMILY = gpt_inference.Family(
    step=_step, project=_project, attend_fresh=_attend_fresh,
    attend_cached=_attend_cached, windows=lambda config, max_len: None,
    embed=lambda params, tokens, config, positions=None:
        model.embed(params, tokens, config),
    logits=model.lm_logits, apply=model.apply,
    logical_axes=model.logical_axes, unsupported=UNSUPPORTED,
    stats_groups=stats_groups, state_counters=STATE_COUNTERS)
