"""Cached inference for the selected-latent / window-latent family: a step,
three kinds of cached state and a selection a query.

The cache class, the layer scan and the slot ops are ``gpt_inference``'s
own (the one cache family of the tree); this module's ``FAMILY`` brings
what ``gpt_inference.Family`` asks of a model family:

- the **row** (``config.cache_row``): a full layer's cached token is TWO
  banks of different width, the latent row ``[c | R(k_r)]`` (whole lane
  rows) and the index key; the single-token sweep streams the first by
  blocks (``config.cache_sweep_row``), the index reads the second;
- the **rings** (``config.cache_ring`` / ``cache_ring_row``): a window layer
  keeps the last ``ring_cells`` tokens' latent rows, of ITS width, position
  ``p`` in cell ``p mod ring_cells``; the ring is the window rounded up to
  whole blocks of the sweep, and a bias hides, to the token, the cells past
  the window (``decode_attention.ring_bias`` for a single token, written
  first; ``latent_ring_attention`` for a chunk, written after);
- the **selection** (full layers, every pass): the index's scores over the
  row's cached keys (``index_scores``), the exact ``min(t + 1, index_topk)``
  largest as a bias (``topk_bias``), and the latent kernels under that bias:
  a MASKED sweep, which steps and streams every live block and attends to
  the chosen tokens alone.  A prompt pass is a chunk at position 0;
- the **form** of a latent pass, by its shape as in ``latent_moe_inference``
  (``latent_moe.up_projection``, each kind of layer by its own widths): a
  tick and a few tokens absorbed, a chunk UP-PROJECTED inside the chunk
  kernel under its bias (the selection's, or a ring's band), through the
  head-major copy of each stack's ``wkv_b`` that ``step`` makes outside its
  scans (a window layer's 192-wide key part in 256 lanes);
- the **step**: one segment per run (``config.runs``), the scan's body the
  unit's layers in order, each position knowing its kind and its FFN's form
  statically; attention runs under the named scope of its kind
  (``sparse_latent_attention`` / ``window_latent_attention``, the index and
  the selection under ``index_select`` inside the first), every expert half
  adds its pair counts to ``cache.stats`` and every single-token pass what
  the selection did (``SELECT_COUNTERS``).

Not supported, refused where it is asked for (``UNSUPPORTED``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..moe.held_experts import n_pair_counts
from . import gpt_inference, latent_moe, sparse_latent_moe as model
from .gpt_inference import KVCache
from .hybrid_ssm_moe import run_parts
from .sparse_latent_moe import (DENSE, FULL, ROUTED, WINDOW,
                                SparseLatentMoEConfig)

PyTree = Any

_RING = ("a ring is not a prefix of its conversation: it holds the last "
         "``ring_cells`` tokens in cells ``p mod ring_cells``")
#: what this family is refused, with the reason
UNSUPPORTED = {
    "paging": _RING + ", the pager trims every rank-4 leaf of a cache to its "
              "first ``length`` rows (``serving/paging.py::_is_bank``), "
              "which would cut a ring, and a parked index bank has no "
              "re-admission test",
    "prefix": _RING + ", so a pooled prefix's rings are those of ITS last "
              "tokens: a fork would need each ring as it stood at the fork",
    "speculative": _RING + ", so a rejected draft token has already "
                   "overwritten the cell of a token the window still holds, "
                   "and a ragged verify pass carries no per-row count of "
                   "real tokens",
    "int8": "the selected-latent family caches in the compute dtype only: "
            "the int8 cache's scale banks are per head and neither a latent "
            "row nor an index key has heads",
    "draft": gpt_inference.DENSE_DRAFTS_ONLY,
}

#: the named scope a layer's attention is traced under, by kind
SCOPES = {FULL: "sparse_latent_attention", WINDOW: "window_latent_attention"}

#: the group ``sparse_select`` of ``cache.stats``, grown by every
#: SINGLE-TOKEN pass (a tick; an admission's chunks count nothing here), a
#: live row a layer: ``eligible``: cached tokens a full layer's query could
#: have chosen from (``t + 1``); ``selected``: those it attended to (``min(t
#: + 1, index_topk)``); ``streamed``: latent rows the masked sweep's copies
#: moved for it (its live blocks, whole); ``ring_live``: ring cells a window
#: layer's query saw (``min(t + 1, window)``)
SELECT_COUNTERS = ("eligible", "selected", "streamed", "ring_live")


def stats_groups(config: SparseLatentMoEConfig) -> Dict[str, slice]:
    """Where each group of this family's device counters lies in
    ``cache.stats``: the expert layers' ``pair_counts``, then
    ``SELECT_COUNTERS``."""
    pairs = n_pair_counts(len(config.held))
    return {"moe_pairs": slice(0, pairs),
            "sparse_select": slice(pairs, pairs + len(SELECT_COUNTERS))}


def _counts(config: SparseLatentMoEConfig, **grown):
    """A vector as long as ``cache.stats`` with ``SELECT_COUNTERS`` named in
    ``grown`` set: what an attend hook hands ``_layer_scan`` to add."""
    where = stats_groups(config)["sparse_select"]
    return jnp.zeros((where.stop,), jnp.int32).at[where].set(jnp.stack(
        [jnp.asarray(grown.get(name, 0), jnp.int32)
         for name in SELECT_COUNTERS]))


def _step(params: PyTree, config: SparseLatentMoEConfig, valid):
    pairs = stats_groups(config)["moe_pairs"]
    segments = []
    for (unit, firsts, n), run in zip(config.runs, params["runs"]):
        parts = run_parts(run)
        kinds = [label.split("+")[0] for label in unit]
        # the routed experts' matrices are never an ``xs`` of the layer
        # scan (a slice of a stack handed to a Pallas call is copied out
        # first): the body closes over the run's whole stacks
        routed = [None if label.endswith(DENSE) else
                  {k: p[k] for k in ROUTED} for label, p in zip(unit, parts)]
        heads = [latent_moe.head_major(p["wkv_b"], config, config.dims(kind))
                 for kind, p in zip(kinds, parts)]

        def body(x, ps, i, attend, cache, unit=unit, kinds=kinds,
                 firsts=firsts, routed=routed, heads=heads):
            for label, kind, first, p, experts, up in zip(
                    unit, kinds, firsts, ps, routed, heads):
                p = latent_moe.with_up(p, up, i)
                with jax.named_scope(SCOPES[kind]):
                    a, cache = attend(x, p, first + i * kinds.count(kind),
                                      cache, ring=kind == WINDOW)
                    x = model.attention_output(x, a, p, config, kind)
                x, counts = model.ffn(x, p, config, label, experts=experts,
                                      layer=i)
                if counts is not None:
                    cache = dataclasses.replace(
                        cache, stats=cache.stats.at[pairs].add(counts))
            return x, cache

        segments.append((tuple(
            p if experts is None else
            {k: v for k, v in p.items() if k not in ROUTED}
            for p, experts in zip(parts, routed)), body))
    return segments


def _project(x, p, config: SparseLatentMoEConfig, positions, ring=False):
    return model.attention_project(x, p, config, positions,
                                   WINDOW if ring else FULL)


def _attend_cached(q, cache: KVCache, pos, config: SparseLatentMoEConfig,
                   idx, active=None, sweep=None, ring=False, fresh=None):
    from ..ops.pallas import decode_attention as da
    dm = config.dims(WINDOW if ring else FULL)
    queries, index, up = q
    B, Sq = queries.shape[:2]
    p = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (B,))
    live = jnp.ones((B,), bool) if active is None else active
    kw = dict(sm_scale=dm.softmax_scale, rank=dm.kv_rank)
    if ring:
        bank, = cache.ring
        if Sq > 1:
            return da.latent_ring_attention(queries, bank, fresh[0], p,
                                            config.window, idx, up=up, **kw)
        R = bank.shape[2]       # written already: a short pool under a bias
        a = da.latent_cached_attention(
            queries, bank, jnp.minimum(p, R - 1), layer=idx, active=active,
            sweep=sweep, bias=da.ring_bias(p, R, config.window), **kw)
        return a, _counts(config, ring_live=jnp.sum(
            jnp.where(live, jnp.minimum(p + 1, config.window), 0)))
    # a tick's two work lists, built once before the layer scan: the latent
    # sweep's and the index's (``config.cache_second_sweep_block``)
    sweep, index_sweep = sweep if isinstance(sweep, tuple) and len(sweep) == 2 \
        else (sweep, None)
    with jax.named_scope("index_select"):
        scores = da.index_scores(index.q, index.w, cache.v, pos=p, layer=idx,
                                 active=active, sweep=index_sweep)
        bias = da.topk_bias(scores, p[:, None] + jnp.arange(Sq)[None],
                            config.index_topk)
    a = da.latent_cached_attention(queries, cache.k, pos, layer=idx,
                                   active=active, sweep=sweep, bias=bias,
                                   up=up, **kw)
    if Sq > 1:
        return a
    block_k = gpt_inference._row_plan(config, cache.max_len).block_k or 1
    return a, _counts(
        config, eligible=jnp.sum(jnp.where(live, p + 1, 0)),
        selected=jnp.sum(jnp.where(
            live, jnp.minimum(p + 1, config.index_topk), 0)),
        streamed=jnp.sum(jnp.where(live, (p // block_k + 1) * block_k, 0)))


def _attend_fresh(q, fresh, cache, config: SparseLatentMoEConfig, idx,
                  ring=False):
    # a prompt pass is a chunk at position 0: of the rows just written, or,
    # on a window layer, beside a ring no token has reached
    return _attend_cached(q, cache, jnp.zeros((), jnp.int32), config, idx,
                          ring=ring, fresh=fresh)


FAMILY = gpt_inference.Family(
    step=_step, project=_project, attend_fresh=_attend_fresh,
    attend_cached=_attend_cached, windows=lambda config, max_len: None,
    embed=lambda params, tokens, config, positions=None:
        model.embed(params, tokens, config),
    logits=model.lm_logits, apply=model.apply,
    logical_axes=model.logical_axes, unsupported=UNSUPPORTED,
    stats_groups=stats_groups, select_counters=SELECT_COUNTERS,
    chunk_form=model.chunk_form)
