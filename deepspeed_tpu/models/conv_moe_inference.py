"""Cached inference for the gated-short-convolution family: a step, a row
and a per-slot tail.

The cache class, the layer scan and the slot ops are ``gpt_inference``'s
own (the one cache family of the tree); this module's ``FAMILY`` brings
what ``gpt_inference.Family`` asks of a model family:

- the **row** (``config.cache_row``): K (rotated) and V of the key-value
  heads, two banks of ``n_kv_head * head_dim``, for the attention layers
  alone (``config.cache_layers``): the pool is ``[L_attn, B, S_max, row]``;
- the **state** (``config.cache_state``): ONE array, ``[L_conv, B,
  conv_kernel - 1, d_model]`` in the compute dtype: what a convolution layer
  keeps of a conversation whatever its length, the last values of ``s = B *
  u``.  It is the cache's ``state`` leaf (a tuple of one); the slot ops
  insert, read and zero it with the banks;
- the **step**: one segment per run (``config.units``), the scan's body the
  unit's layers in order, each position knowing its mixer and its FFN
  statically.  An attention layer goes through the scan's ``attend`` at its
  index among the attention layers; a convolution layer reads and writes
  layer ``j`` of the tails in place, to the tail after the call's last REAL
  token (``valid``: a padded chunk's tail is taken inside it, a freed slot's
  comes back bit for bit: ``ops/pallas/ssm.py::causal_conv``).  An expert
  layer adds its pair counts to ``cache.stats`` and a convolution layer its
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
from . import conv_moe as model, gpt_inference, hybrid_ssm_moe_inference
from .conv_moe import CONV, DENSE, ROUTED, ConvMoEConfig
from .gpt_inference import KVCache
from .hybrid_ssm_moe import run_parts

PyTree = Any

#: what this family is refused, in the hybrid family's words: a tail is a
#: per-slot leaf with no token axis, as a state-space layer's state is
UNSUPPORTED = {
    **hybrid_ssm_moe_inference.UNSUPPORTED,
    "int8": "the short-convolution family caches in the compute dtype only",
}

#: the counters of this family's group ``state_steps`` in ``cache.stats``:
#: tails stepped by ticks (live slot x convolution layer), and real and
#: padded tokens through a chunk's convolutions (token x convolution layer)
STATE_COUNTERS = ("conv_rows_stepped", "conv_tokens_real",
                  "conv_tokens_padded")


def stats_groups(config: ConvMoEConfig) -> Dict[str, slice]:
    """Where each group of this family's device counters lies in
    ``cache.stats``: the one place that knows.  ``moe_pairs``: the expert
    layers' ``pair_counts``; ``state_steps``: ``STATE_COUNTERS``."""
    pairs = n_pair_counts(len(config.held))
    return {"moe_pairs": slice(0, pairs),
            "state_steps": slice(pairs, pairs + len(STATE_COUNTERS))}


def _conv_mixer(x, p, j, cache: KVCache, valid, config: ConvMoEConfig):
    """A convolution layer's mixer on ``x`` [B, S, d] against layer ``j``
    of the tails; returns ``(x, state, counters [3])``."""
    B, S, _ = x.shape
    tails, = cache.state
    gate_b, gate_c, u = model.conv_inputs(x, p, config)
    tail = lax.dynamic_index_in_dim(tails, j, 0, keepdims=False)
    gated, tail = model.conv_mix(gate_b, gate_c, u, tail, p, config, valid)
    tails = lax.dynamic_update_slice(tails, tail[None], (j, 0, 0, 0))
    real = jnp.sum(valid)
    counters = jnp.stack([real, 0, 0] if S == 1 else
                         [0, real, B * S - real])
    return (model.conv_output(x, gated, p, config), (tails,),
            counters.astype(jnp.int32))


def _step(params: PyTree, config: ConvMoEConfig, valid):
    segments = []
    groups = stats_groups(config)

    def layer(x, label, p, experts, i, j, attend, cache):
        """Layer ``j`` of its mixer's kind, repetition ``i`` of its run."""
        stats = cache.stats
        if label.startswith(CONV):
            x, state, counters = _conv_mixer(x, p, j, cache, valid, config)
            cache = dataclasses.replace(cache, state=state)
            stats = stats.at[groups["state_steps"]].add(counters)
        else:
            with jax.named_scope("full_attention"):
                a, cache = attend(x, p, j, cache)
                x = model.attention_output(x, a, p, config)
        x, counts = model.ffn(x, p, config, label, experts=experts, layer=i)
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

        def body(x, ps, i, attend, cache, unit=unit, kinds=kinds,
                 firsts=firsts, routed=routed):
            for label, kind, first, p, experts in zip(unit, kinds, firsts,
                                                      ps, routed):
                x, cache = layer(x, label, p, experts, i,
                                 first + i * kinds.count(kind), attend, cache)
            return x, cache

        segments.append((tuple(
            p if experts is None else
            {k: v for k, v in p.items() if k not in ROUTED}
            for p, experts in zip(parts, routed)), body))
    return segments


def _project(x, p, config: ConvMoEConfig, positions):
    return model.attention_project(x, p, config, positions)


def _attend_cached(q, cache: KVCache, pos, config: ConvMoEConfig, idx,
                   active=None, sweep=None):
    from ..ops.pallas.decode_attention import cached_attention
    return cached_attention(q, cache.k, cache.v, pos,
                            sm_scale=config.attn_scale, layer=idx,
                            active=active, sweep=sweep,
                            kv_heads=config.n_kv_head)


def _attend_fresh(q, fresh, cache, config: ConvMoEConfig, idx):
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
