"""Cached inference for the hybrid state-space / attention family: a step, a
row and a per-slot state.

The cache class, the layer scan and the slot ops are ``gpt_inference``'s
own (the one cache family of the tree); this module's ``FAMILY`` brings
what ``gpt_inference.Family`` asks of a model family:

- the **row** (``config.cache_row``): K and V of the key-value heads, two
  banks of ``n_kv_head * head_dim``, for the attention layers alone
  (``config.cache_layers``): the pool is ``[L_attn, B, S_max, row]``;
- the **state** (``config.cache_state``): what a state-space layer keeps of
  a conversation whatever its length: the running sum ``H`` ``[L_ssm, B,
  d_state, heads * head_dim]`` float32 and the last ``conv_kernel - 1``
  pre-activation inputs of the convolution ``[L_ssm, B, K - 1, d_conv]``.
  It is the cache's ``state`` leaf; the slot ops insert, read and zero it
  with the banks;
- the **step**: one segment per run, in depth order (``config.units``: a
  unit of kinds repeated, the scan's body the unit's layers in order).  An
  attention layer goes through the scan's ``attend`` at its index among the
  attention layers; a state-space layer advances layer ``j`` of the state
  stacks in place: one token a live slot through ``ssm_decode_step`` (a
  freed slot neither steps nor moves), a chunk through ``ssd_chunk_scan``
  to the state after the chunk's last REAL token (``valid``: a padded tail
  takes ``dt = 0`` and the convolution tail kept is that of the last real
  tokens); an expert layer owns no row and no state.  The expert layer
  (every layer's second half, or a layer of its own: ``config.mixer_ffn``)
  adds its pair counts to ``cache.stats`` and a state-space layer its state
  counters (``STATE_COUNTERS``), each group where ``stats_groups`` says.

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
from ..ops.pallas import ssm
from . import gpt_inference, hybrid_ssm_moe as model
from .gpt_inference import KVCache
from .hybrid_ssm_moe import ATTENTION, MAMBA, HybridSSMMoEConfig

PyTree = Any

#: what this family is refused, with the reason
UNSUPPORTED = {
    "speculative": "a rejected draft token would have to be rolled back out "
                   "of the per-slot state, and a ragged verify pass carries "
                   "no per-row count of real tokens",
    "paging": "a parked conversation's per-slot state has no block to live "
              "in: the pager moves token-indexed banks only",
    "prefix": "a pooled prefix would need a snapshot of the per-slot state "
              "at its end; the pool keeps token-indexed banks only",
    "int8": "the hybrid state-space family caches in the compute dtype only",
    "draft": gpt_inference.DENSE_DRAFTS_ONLY,
}

#: the counters of this family's group ``state_steps`` in ``cache.stats``:
#: state rows stepped by ticks (live slot x state-space layer), and real and
#: padded tokens through the chunk scan (token x state-space layer)
STATE_COUNTERS = ("ssm_rows_stepped", "scan_tokens_real",
                  "scan_tokens_padded")


def stats_groups(config: HybridSSMMoEConfig) -> Dict[str, slice]:
    """Where each group of this family's device counters lies in
    ``cache.stats``: the one place that knows.  ``moe_pairs``: the expert
    layer's ``pair_counts``; ``state_steps``: ``STATE_COUNTERS``."""
    pairs = n_pair_counts(len(config.held))
    return {"moe_pairs": slice(0, pairs),
            "state_steps": slice(pairs, pairs + len(STATE_COUNTERS))}


def _mamba_mixer(x, p, j, cache: KVCache, valid, work,
                 config: HybridSSMMoEConfig):
    """A state-space layer's mixer on ``x`` [B, S, d] against layer ``j``
    of the state stacks; returns ``(x, state, counters [3])``."""
    B, S, _ = x.shape
    h_stack, tails = cache.state
    z, u, dt = model.ssm_inputs(x, p, config)
    with jax.named_scope("ssm_conv"):
        tail = lax.dynamic_index_in_dim(tails, j, 0, keepdims=False)
        u_act, tail = ssm.causal_conv(u, tail, p["conv_w"], p["conv_b"],
                                      valid)
        tails = lax.dynamic_update_slice(tails, tail[None], (j, 0, 0, 0))
        v, dt, a, Bm, Cm = model.ssm_scan_inputs(u_act, dt, p, config)
    real = jnp.sum(valid)
    if S == 1:
        P = config.ssm_head_dim
        wide = lambda t: jnp.repeat(t[:, 0], P, axis=-1)      # [B, H] -> HP
        with jax.named_scope("ssm_decode_step"):
            y, h_stack = ssm.ssm_decode_step(
                h_stack, j, wide(dt) * v.reshape(B, -1).astype(jnp.float32),
                wide(jnp.exp(dt * a)), Bm[:, 0], Cm[:, 0], active=valid > 0,
                work=work, groups=config.ssm_groups)
        y = y[:, None]
        counters = jnp.stack([real, 0, 0])
    else:
        with jax.named_scope("ssd_chunk_scan"):
            y, h_stack = ssm.ssd_chunk_scan(
                h_stack, j, v, dt, a, Bm, Cm, valid=valid,
                chunk=config.ssm_chunk, groups=config.ssm_groups)
        counters = jnp.stack([0, real, B * S - real])
    return (model.ssm_output(x, y, v, z, p, config), (h_stack, tails),
            counters.astype(jnp.int32))


def _step(params: PyTree, config: HybridSSMMoEConfig, valid):
    segments = []
    # a tick's work list, built once for all its state-space layers
    work = ssm.live_rows(valid > 0, valid.shape[0])
    groups = stats_groups(config)
    # the routed experts' matrices are never an ``xs`` of the layer scan (a
    # slice of a stack handed to a Pallas call is copied out first): a
    # segment's body closes over its run's whole stacks and reads its layer
    # in place
    routed_keys = config.routed_keys

    def layer(x, kind, p, experts, i, j, attend, cache):
        """Layer ``j`` of its kind, repetition ``i`` of its run."""
        stats = cache.stats
        if kind == MAMBA:
            x, state, counters = _mamba_mixer(x, p, j, cache, valid, work,
                                              config)
            cache = dataclasses.replace(cache, state=state)
            stats = stats.at[groups["state_steps"]].add(counters)
        elif kind == ATTENTION:
            a, cache = attend(x, p, j, cache)
            x = model.attention_output(x, a, p, config)
        if model.has_ffn(kind, config):
            x, counts = model.expert_ffn(x, p, config, experts=experts,
                                         layer=i)
            stats = stats.at[groups["moe_pairs"]].add(counts)
        return x, dataclasses.replace(cache, stats=stats)

    for (unit, firsts, n), run in zip(config.units, params["runs"]):
        parts = model.run_parts(run)
        routed = [{k: p[k] for k in routed_keys if k in p} for p in parts]

        def body(x, ps, i, attend, cache, unit=unit, firsts=firsts,
                 routed=routed):
            for kind, first, p, experts in zip(unit, firsts, ps, routed):
                x, cache = layer(x, kind, p, experts, i,
                                 first + i * unit.count(kind), attend, cache)
            return x, cache

        segments.append((tuple({k: v for k, v in p.items()
                                if k not in routed_keys} for p in parts),
                         body))
    return segments


def _project(x, p, config: HybridSSMMoEConfig, positions):
    return model.attention_project(x, p, config)


def _attend_cached(q, cache: KVCache, pos, config: HybridSSMMoEConfig, idx,
                   active=None, sweep=None):
    from ..ops.pallas.decode_attention import cached_attention
    return cached_attention(q, cache.k, cache.v, pos,
                            sm_scale=config.attn_scale, layer=idx,
                            active=active, sweep=sweep,
                            kv_heads=config.n_kv_head)


def _attend_fresh(q, fresh, cache, config: HybridSSMMoEConfig, idx):
    # a prompt pass is a chunk at position 0 of the rows just written
    return _attend_cached(q, cache, jnp.zeros((), jnp.int32), config, idx)


FAMILY = gpt_inference.Family(
    step=_step, project=_project, attend_fresh=_attend_fresh,
    attend_cached=_attend_cached, windows=lambda config, max_len: None,
    embed=lambda params, tokens, config, positions=None:
        model.embed(params, tokens, config),
    logits=model.lm_logits, apply=model.apply,
    logical_axes=model.logical_axes, unsupported=UNSUPPORTED,
    stats_groups=stats_groups, state_counters=STATE_COUNTERS)
