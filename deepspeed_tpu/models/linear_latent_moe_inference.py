"""Cached inference for the linear-attention / latent-attention family: a
step, a latent row and a per-slot state, together.

The cache class, the layer scan and the slot ops are ``gpt_inference``'s
own (the one cache family of the tree); this module's ``FAMILY`` brings
what ``gpt_inference.Family`` asks of a model family:

- the **row** (``config.cache_row``): one bank, the latent row ``[c | k_r]``
  in whole lane rows, for the latent layers alone (``config.cache_layers``):
  the pool is ``[L_latent, B, S_max, row]``;
- the **state** (``config.cache_state``): what a KDA layer keeps of a
  conversation whatever its length: ``S`` ``[L_kda, B, d_k, heads * d_v]``
  float32 and the last ``conv_kernel - 1`` pre-activation inputs of the
  convolution over ``q | k | v`` ``[L_kda, B, K - 1, 3 d_kda]``.  It is the
  cache's ``state`` leaf, as the hybrid state-space family's is; the slot
  ops insert, read and zero it with the bank;
- the **step**: one segment per run (``config.units``).  A latent layer goes
  through the scan's ``attend`` at its index among the latent layers, every
  pass through ``ops/pallas/decode_attention.py``'s latent kernels in the
  form its shape picks (``latent_moe.up_projection``; a run's latent
  ``wkv_b`` stacks have their head-major copies); a KDA
  layer advances layer ``j`` of the state stacks in place: one token a live
  slot through ``kda_decode_step`` (a freed slot neither steps nor moves), a
  chunk through ``kda_chunk_scan`` to the state after the chunk's last REAL
  token (``valid``: a padded tail takes ``g = 0`` and ``beta = 0`` and the
  convolution tail kept is that of the last real tokens).  The expert layers
  add their pair counts to ``cache.stats`` and a KDA layer its state
  counters (the hybrid family's ``STATE_COUNTERS``: ``ssm_rows_stepped`` is
  a live slot x KDA layer of a tick, ``scan_tokens_*`` a token x KDA layer of
  a chunk), each group where ``stats_groups`` says.

Not supported, refused where it is asked for (``UNSUPPORTED``): the int8
cache, paging, pooled prefixes, speculation and serving as a draft.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from ..moe.held_experts import n_pair_counts
from ..ops.pallas import delta_rule, ssm
from . import gpt_inference, latent_moe, linear_latent_moe as model
from .gpt_inference import KVCache
from .hybrid_ssm_moe import run_parts
from .hybrid_ssm_moe_inference import STATE_COUNTERS
# the latent layers' projection and their two calls of the latent kernels are
# the latent family's own: they read a config's widths and nothing else
from .latent_moe_inference import _attend_cached, _attend_fresh, _project
from .linear_latent_moe import DENSE, KDA, ROUTED, LinearLatentMoEConfig

PyTree = Any

#: what this family is refused, with the reason
UNSUPPORTED = {
    "speculative": "a rejected draft token would have to be rolled back out "
                   "of the per-slot delta-rule state, and a ragged verify "
                   "pass carries no per-row count of real tokens",
    "paging": "a parked conversation's per-slot state has no block to live "
              "in: the pager moves token-indexed banks only",
    "prefix": "a pooled prefix would need a snapshot of the per-slot state "
              "at its end; the pool keeps token-indexed banks only",
    "int8": "the linear-attention family caches in the compute dtype only: "
            "the int8 cache's scale banks are per head, a latent row has no "
            "heads and the state is float32",
    "draft": gpt_inference.DENSE_DRAFTS_ONLY,
}


def stats_groups(config: LinearLatentMoEConfig) -> Dict[str, slice]:
    """Where each group of this family's device counters lies in
    ``cache.stats``: the one place that knows.  ``moe_pairs``: the expert
    layers' ``pair_counts``; ``state_steps``: ``STATE_COUNTERS``."""
    pairs = n_pair_counts(len(config.held))
    return {"moe_pairs": slice(0, pairs),
            "state_steps": slice(pairs, pairs + len(STATE_COUNTERS))}


def _kda_mixer(x, p, j, cache: KVCache, valid, work,
               config: LinearLatentMoEConfig):
    """A KDA layer's mixer on ``x`` [B, S, d] against layer ``j`` of the
    state stacks; returns ``(x, state, counters [3])``."""
    B, S, _ = x.shape
    s_stack, tails = cache.state
    qkv, g, beta, gate = model.kda_inputs(x, p, config)
    with jax.named_scope("kda_conv"):
        tail = lax.dynamic_index_in_dim(tails, j, 0, keepdims=False)
        u_act, tail = ssm.causal_conv(qkv, tail, p["conv_w"], None, valid)
        tails = lax.dynamic_update_slice(tails, tail[None], (j, 0, 0, 0))
        q, k, v = model.kda_scan_inputs(u_act, config)
    real = jnp.sum(valid)
    if S == 1:
        with jax.named_scope("kda_decode_step"):
            o, s_stack = delta_rule.kda_decode_step(
                s_stack, j, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                active=valid > 0, work=work)
        counters = jnp.stack([real, 0, 0])
    else:
        with jax.named_scope("kda_chunk_scan"):
            o, s_stack = delta_rule.kda_chunk_scan(
                s_stack, j, q, k, v, g, beta, valid=valid,
                chunk=config.kda_chunk)
        counters = jnp.stack([0, real, B * S - real])
    return (model.kda_output(x, o, gate, p, config), (s_stack, tails),
            counters.astype(jnp.int32))


def kda_step(params: PyTree, config, valid, full, ffn,
             extras=lambda kind, p: None):
    """``Family.step`` of a family whose layers are KDA layers and full
    layers of ONE other kind: the layer loop both such families share.
    ``full(x, p, extra, i, j, attend, cache) -> (x, cache)`` is the full
    layer's mixer (``j``: its index among the full layers; ``i``: the
    repetition of its run), ``extra`` what ``extras(kind, p)`` made of its
    position's stacks outside the scan (None for a KDA position);
    ``ffn(x, p, config, label, experts=, layer=)`` a layer's second half."""
    segments = []
    # a tick's work list, built once for all its KDA layers
    work = ssm.live_rows(valid > 0, valid.shape[0])
    groups = stats_groups(config)

    def layer(x, label, p, experts, extra, i, j, attend, cache):
        """Layer ``j`` of its mixer's kind, repetition ``i`` of its run."""
        stats = cache.stats
        if label.startswith(KDA):
            x, state, counters = _kda_mixer(x, p, j, cache, valid, work,
                                            config)
            cache = dataclasses.replace(cache, state=state)
            stats = stats.at[groups["state_steps"]].add(counters)
        else:
            x, cache = full(x, p, extra, i, j, attend, cache)
        x, counts = ffn(x, p, config, label, experts=experts, layer=i)
        if counts is not None:
            stats = stats.at[groups["moe_pairs"]].add(counts)
        return x, dataclasses.replace(cache, stats=stats)

    for (unit, firsts, n), run in zip(config.units, params["runs"]):
        parts = run_parts(run)
        kinds = [model.mixer_of(label) for label in unit]
        # the routed experts' matrices are never an ``xs`` of the layer scan
        # (a slice of a stack handed to a Pallas call is copied out first):
        # the body closes over the run's whole stacks
        routed = [None if label.endswith(DENSE) else
                  {k: p[k] for k in ROUTED} for label, p in zip(unit, parts)]
        extra = [extras(kind, p) for kind, p in zip(kinds, parts)]

        def body(x, ps, i, attend, cache, unit=unit, kinds=kinds,
                 firsts=firsts, routed=routed, extra=extra):
            for label, kind, first, p, experts, ex in zip(
                    unit, kinds, firsts, ps, routed, extra):
                x, cache = layer(x, label, p, experts, ex, i,
                                 first + i * kinds.count(kind), attend, cache)
            return x, cache

        segments.append((tuple(
            p if experts is None else
            {k: v for k, v in p.items() if k not in ROUTED}
            for p, experts in zip(parts, routed)), body))
    return segments


def _step(params: PyTree, config: LinearLatentMoEConfig, valid):
    def latent(x, p, heads, i, j, attend, cache):
        """``heads``: the layer's run's ``wkv_b`` stack, head-major."""
        with jax.named_scope("latent_attention"):
            p = latent_moe.with_up(p, heads, i)
            a, cache = attend(x, p, j, cache)
            return latent_moe.latent_output(x, a, p, config), cache

    return kda_step(
        params, config, valid, latent, model.ffn,
        extras=lambda kind, p: None if kind == KDA else
        latent_moe.head_major(p["wkv_b"], config))


FAMILY = gpt_inference.Family(
    step=_step, project=_project, attend_fresh=_attend_fresh,
    attend_cached=_attend_cached, windows=lambda config, max_len: None,
    embed=lambda params, tokens, config, positions=None:
        model.embed(params, tokens, config),
    logits=model.lm_logits, apply=model.apply,
    logical_axes=model.logical_axes, unsupported=UNSUPPORTED,
    stats_groups=stats_groups, state_counters=STATE_COUNTERS,
    chunk_form=latent_moe.chunk_form)
