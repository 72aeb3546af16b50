"""Cached inference for the window-and-full attention family: a step, a row
and two pools.

The cache class, the layer scan and the slot ops are ``gpt_inference``'s
own (the one cache family of the tree); this module's ``FAMILY`` brings
what ``gpt_inference.Family`` asks of a model family:

- the **row** (``config.cache_row``): K and V of the key-value heads, keys
  rotated, two banks of ``n_kv_head * head_dim``;
- the **pools**: the full layers own whole rows (``config.cache_layers``:
  the banks are ``[L_full, B, S_max, row]``), the window layers a ring each
  (``config.cache_ring``: ``KVCache.ring`` is ``[L_window, B, window,
  row]``, position ``p`` in cell ``p mod window``).  Keys are cached
  rotated, so the order of a ring's cells does not matter to a softmax: a
  single token is written over the one that just left the window and
  sweeps ``min(p + 1, window)`` cells with NO band (the grouped sweep over
  a short pool at frontier ``min(p, window - 1)``); a chunk attends over
  the ring as it was and its own rows (``decode_attention.ring_attention``:
  a band of ``window + chunk`` keys whatever the prefix) and is written
  after;
- the **step**: one segment per run (``config.units``), the scan's body the
  unit's layers in order, each position knowing its kind, so its pool and
  its rotary table, statically; attention runs under the named scope of its
  kind (``window_attention`` / ``full_attention``), and every layer's
  expert half adds its pair counts to ``cache.stats``.

Not supported, refused where it is asked for (``UNSUPPORTED``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from . import gpt_inference, window_moe as model
from .gpt_inference import KVCache
from .hybrid_ssm_moe import run_parts
from ..moe.held_experts import n_pair_counts
from .window_moe import FULL, ROUTED, WINDOW, WindowMoEConfig

PyTree = Any

_RING = ("a ring is not a prefix of its conversation: it holds the last "
         "``window`` tokens in cells ``p mod window``")
#: what this family is refused, with the reason
UNSUPPORTED = {
    "paging": _RING + ", and the pager trims every rank-4 leaf of a cache "
              "to its first ``length`` rows (``serving/paging.py::_is_bank``)"
              ", which would cut a ring",
    "prefix": _RING + ", so a pooled prefix's rings are those of ITS last "
              "tokens: a fork would need each ring as it stood at the fork",
    "speculative": _RING + ", so a rejected draft token has already "
                   "overwritten the cell of a token the window still holds, "
                   "and a ragged verify pass carries no per-row count of "
                   "real tokens",
    "int8": "the window-and-full family caches in the compute dtype only",
    "draft": gpt_inference.DENSE_DRAFTS_ONLY,
}

#: the named scope a layer's attention is traced under, by kind
SCOPES = {WINDOW: "window_attention", FULL: "full_attention"}


def stats_groups(config: WindowMoEConfig) -> Dict[str, slice]:
    """Where each group of this family's device counters lies in
    ``cache.stats``: the expert layers' ``pair_counts`` and nothing else."""
    return {"moe_pairs": slice(0, n_pair_counts(len(config.held)))}


def _step(params: PyTree, config: WindowMoEConfig, valid):
    segments = []
    for (unit, firsts, n), run in zip(config.units, params["runs"]):
        parts = run_parts(run)
        # the routed experts' matrices are never an ``xs`` of the layer
        # scan (a slice of a stack handed to a Pallas call is copied out
        # first): the body closes over the run's whole stacks
        routed = [{k: p[k] for k in ROUTED} for p in parts]

        def body(x, ps, i, attend, cache, unit=unit, firsts=firsts,
                 routed=routed):
            for kind, first, p, experts in zip(unit, firsts, ps, routed):
                with jax.named_scope(SCOPES[kind]):
                    a, cache = attend(x, p, first + i * unit.count(kind),
                                      cache, ring=kind == WINDOW)
                    x = model.attention_output(x, a, p, config)
                x, counts = model.expert_ffn(x, p, config, experts=experts,
                                             layer=i)
                cache = dataclasses.replace(cache,
                                            stats=cache.stats + counts)
            return x, cache

        segments.append((tuple({k: v for k, v in p.items()
                                if k not in ROUTED} for p in parts), body))
    return segments


def _project(x, p, config: WindowMoEConfig, positions, ring=False):
    return model.attention_project(x, p, config, positions,
                                   WINDOW if ring else FULL)


def _attend_cached(q, cache: KVCache, pos, config: WindowMoEConfig, idx,
                   active=None, sweep=None, ring=False, fresh=None):
    from ..ops.pallas.decode_attention import cached_attention, ring_attention
    kw = dict(sm_scale=config.attn_scale, kv_heads=config.n_kv_head)
    if not ring:
        return cached_attention(q, cache.k, cache.v, pos, layer=idx,
                                active=active, sweep=sweep, **kw)
    rk, rv = cache.ring
    if q.shape[1] == 1:     # written already: a short pool, no band
        return cached_attention(q, rk, rv,
                                jnp.minimum(pos, rk.shape[2] - 1), layer=idx,
                                active=active, sweep=sweep, **kw)
    return ring_attention(q, rk, rv, *fresh, pos, config.window, idx, **kw)


def _attend_fresh(q, fresh, cache, config: WindowMoEConfig, idx, ring=False):
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
    stats_groups=stats_groups)
