"""Cached inference for the latent-attention, routed-expert family: a step
and a row.

The cache class, the layer scan and the slot ops are ``gpt_inference``'s
own (the one cache family of the tree); this module's ``FAMILY`` brings
what ``gpt_inference.Family`` asks of a model family:

- the **row**: one bank, ``[c | R(k_r)]`` (``config.cache_row``: the latent
  and the shared rotary key, rounded up to whole lane rows), so the pool is
  ``[L, B, S_max, W]`` and a cached token costs ``W`` elements a layer
  whatever the number of heads;
- the **step**: two segments, the leading dense layers (``first_k_dense``)
  and the expert layers, layers in depth order in the one pool; the expert
  layers add their pair counts to ``cache.stats`` (``[n_pair_counts(n_held)]``:
  pairs held here, pairs routed, expert visits, pairs per held expert, pages
  of pairs run beyond a call's first, pairs on zero-compute experts);
- projections and attention through ``ops/pallas/decode_attention.py``'s
  latent kernels, in the form a pass's shape picks
  (``latent_moe.up_projection``): a tick and a few tokens absorbed, a
  chunk up-projected inside the chunk kernel, through the head-major copy
  of each stack's ``wkv_b`` that ``step`` makes outside its scans and
  hands every layer (``latent_moe.with_up``).  A prompt pass is a chunk at
  position 0: the expert layer's cost is linear in a call's tokens, so no
  family-side chunk walk bounds it.

Not supported (``UNSUPPORTED``), each refused where it is asked for: the
int8 cache where the cache is made (its scale banks are per head; a latent
row has no heads), speculation and paging by the batcher, serving as a
draft by the engine and the batcher.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax.numpy as jnp

from . import gpt_inference, latent_moe
from .gpt_inference import KVCache
from ..moe.held_experts import n_pair_counts
from .latent_moe import LatentMoEConfig

PyTree = Any

#: what this family is refused, with the reason
UNSUPPORTED = {
    "speculative": "a dense draft's proposals are verified by a ragged "
                   "extend this family has never been tested through",
    "paging": "parked latent rows have no re-admission test yet",
    "int8": "the latent-attention family caches in the compute dtype only: "
            "the int8 cache's scale banks are per head and a latent row has "
            "no heads",
    "draft": gpt_inference.DENSE_DRAFTS_ONLY,
}


def stats_groups(config: LatentMoEConfig) -> Dict[str, slice]:
    """Where each group of this family's device counters lies in
    ``cache.stats``: the expert layers' ``pair_counts`` and nothing else."""
    if not config.n_moe_layers:
        return {}
    return {"moe_pairs": slice(0, n_pair_counts(len(config.held)))}


#: the routed experts' matrices: never an ``xs`` of the layer scan (a slice
#: of a stack handed to a Pallas call is copied out first); the expert
#: segment's body closes over the whole stacks and reads its layer in place
_ROUTED = ("w_gu", "w_down")


def _step(params: PyTree, config: LatentMoEConfig, valid=None):
    first = config.first_k_dense
    moe = params["moe_blocks"]
    routed = {k: moe[k] for k in _ROUTED}
    heads = {k: latent_moe.head_major(params[k]["wkv_b"], config)
             for k in ("dense_blocks", "moe_blocks")}

    def dense_body(x, p, i, attend, cache):
        p = latent_moe.with_up(p, heads["dense_blocks"], i)
        a, cache = attend(x, p, i, cache)
        x = latent_moe.latent_output(x, a, p, config)
        return latent_moe.dense_ffn(x, p, config), cache

    def moe_body(x, p, i, attend, cache):
        p = latent_moe.with_up(p, heads["moe_blocks"], i)
        a, cache = attend(x, p, first + i, cache)
        x = latent_moe.latent_output(x, a, p, config)
        x, counts = latent_moe.expert_ffn(x, p, config, experts=routed,
                                          layer=i)
        return x, dataclasses.replace(cache, stats=cache.stats + counts)

    segments = []
    if first:
        segments.append((params["dense_blocks"], dense_body))
    if config.n_moe_layers:
        segments.append(({k: v for k, v in moe.items() if k not in _ROUTED},
                         moe_body))
    return segments


def _project(x, p, config: LatentMoEConfig, positions):
    queries, row = latent_moe.latent_project(x, p, config, positions)
    return queries, (row,)


def _attend_cached(q, cache: KVCache, pos, config: LatentMoEConfig, idx,
                   active=None, sweep=None):
    from ..ops.pallas.decode_attention import cached_attention
    # a pass in the up-projected form brings its layer's up-projection
    q, up = q if isinstance(q, tuple) else (q, None)
    return cached_attention(q, cache.k, None, pos,
                            sm_scale=config.softmax_scale, layer=idx,
                            active=active, sweep=sweep,
                            latent_rank=config.kv_rank, latent_up=up)


def _attend_fresh(q, fresh, cache, config: LatentMoEConfig, idx):
    # a prompt pass is a chunk at position 0 of the rows just written
    return _attend_cached(q, cache, jnp.zeros((), jnp.int32), config, idx)


FAMILY = gpt_inference.Family(
    step=_step, project=_project, attend_fresh=_attend_fresh,
    attend_cached=_attend_cached, windows=lambda config, max_len: None,
    embed=lambda params, tokens, config, positions=None:
        latent_moe.embed(params, tokens, config),
    logits=latent_moe.lm_logits, apply=latent_moe.apply,
    logical_axes=latent_moe.logical_axes, unsupported=UNSUPPORTED,
    stats_groups=stats_groups, chunk_form=latent_moe.chunk_form)
