"""Cached inference for the shortcut-connected double-layer family: a step
and a row.

The cache class, the layer scan and the slot ops are ``gpt_inference``'s
own (the one cache family of the tree); this module's ``FAMILY`` brings
what ``gpt_inference.Family`` asks of a model family:

- the **row**: one bank, ``[c | R(k_r)]`` with ``c`` after its rescale
  (``config.cache_row``), and TWO rows a token a layer: sublayer ``j`` of
  layer ``i`` owns cache layer ``2 i + j`` of the one pool
  (``config.cache_layers = 2 n_layer``, the hook GPT-MoE's pair of blocks
  uses);
- the **step**: one segment, one double layer a scan step
  (``shortcut_latent_moe.double_layer``): ``attend`` twice, the expert
  branch's result carried across the second sublayer, the held experts'
  stacks read in place; the branch's pair counts go to ``cache.stats``
  (``held_experts.pair_counts``: with ``zero``, the pairs on zero-compute
  experts, last);
- projections and attention through ``ops/pallas/decode_attention.py``'s
  latent kernels in the form a pass's shape picks, as
  ``latent_moe_inference``'s: each sublayer's ``wkv_b`` stack has its
  head-major copy.

Not supported (``UNSUPPORTED``), each refused where it is asked for: the
int8 cache where the cache is made, speculation, paging and pooled prefixes
by the batcher, serving as a draft by the engine and the batcher.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

from . import gpt_inference, latent_moe
from . import shortcut_latent_moe as model
from .gpt_inference import KVCache  # noqa: F401  (the one cache class)
from ..moe.held_experts import n_pair_counts
# the latent kernels' two calls are the latent family's own: they read a
# config's ``softmax_scale`` and ``kv_rank`` and nothing else
from .latent_moe_inference import _attend_cached, _attend_fresh
from .shortcut_latent_moe import ShortcutLatentMoEConfig

PyTree = Any

#: what this family is refused, with the reason
UNSUPPORTED = {
    "speculative": "a dense draft's proposals are verified by a ragged "
                   "extend this family has never been tested through",
    "paging": "parked latent rows have no re-admission test yet",
    "prefix": "a pooled prefix's rows are copied a cache layer at a time "
              "and this family's two rows a layer have no pooled-prefix "
              "test yet",
    "int8": "the latent-attention families cache in the compute dtype only: "
            "the int8 cache's scale banks are per head and a latent row has "
            "no heads",
    "draft": gpt_inference.DENSE_DRAFTS_ONLY,
}


def stats_groups(config: ShortcutLatentMoEConfig) -> Dict[str, slice]:
    """Where each group of this family's device counters lies in
    ``cache.stats``: the expert branches' ``pair_counts`` and nothing
    else."""
    return {"moe_pairs": slice(0, n_pair_counts(len(config.held)))}


#: the held experts' matrices: never an ``xs`` of the layer scan (a slice
#: of a stack handed to a Pallas call is copied out first); the body closes
#: over the whole stacks and reads its layer in place
_ROUTED = ("w_gu", "w_down")


def _step(params: PyTree, config: ShortcutLatentMoEConfig, valid=None):
    blocks = params["blocks"]
    routed = {k: blocks["moe"][k] for k in _ROUTED}
    sublayers = ("attn0", "attn1")
    heads = {k: latent_moe.head_major(blocks[k]["wkv_b"], config)
             for k in sublayers}

    def body(x, p, i, attend, cache):
        held = [cache]
        p = {**p, **{k: latent_moe.with_up(p[k], heads[k], i)
                     for k in sublayers}}

        def attend_sublayer(x, pa, j):
            a, held[0] = attend(x, pa, 2 * i + j, held[0])
            return a

        x, counts = model.double_layer(x, p, config, attend_sublayer,
                                       experts=routed, layer=i)
        cache = held[0]
        return x, dataclasses.replace(cache, stats=cache.stats + counts)

    return [({**blocks, "moe": {k: v for k, v in blocks["moe"].items()
                                if k not in _ROUTED}}, body)]


def _project(x, p, config: ShortcutLatentMoEConfig, positions):
    queries, row = model.project(x, p, config, positions)
    return queries, (row,)


FAMILY = gpt_inference.Family(
    step=_step, project=_project, attend_fresh=_attend_fresh,
    attend_cached=_attend_cached, windows=lambda config, max_len: None,
    embed=lambda params, tokens, config, positions=None:
        model.embed(params, tokens, config),
    logits=model.lm_logits, apply=model.apply,
    logical_axes=model.logical_axes, unsupported=UNSUPPORTED,
    stats_groups=stats_groups, chunk_form=latent_moe.chunk_form)
