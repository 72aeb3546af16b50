"""KV-cached inference applys for the GPT family.

Counterpart of the reference's ``DeepSpeedTransformerInference``
(``model_implementations/transformers/ds_transformer.py:17``) and its
``softmax_context`` KV-cache attention
(``csrc/transformer/inference/csrc/pt_binding.cpp``): prefill runs the
training forward while recording K/V; decode advances one token against the
cache.  Both are pure functions over (params, cache) so the whole generate
loop jits into a single XLA program — the role CUDA-graph capture plays in
the reference (``inference/engine.py:464``), played instead by jit tracing.

Architecture variants ride the shared ``models/gpt.py`` helpers, so every
injected family (GPT-2 learned positions, OPT relu+offset, BLOOM alibi,
NeoX rotary + parallel residual, untied heads) decodes through this one
implementation — and so does GPT-MoE (``gpt_moe_inference``), which brings
only its own scan step: the cache class, the layer scan and the slot ops
below are the one cache family of the tree.

What a family brings is a :class:`Family`, the whole of what a family is
(``models.cache_family(config)`` returns it): its scan step, how a layer
makes its queries and the row it caches, how it attends, its banded layers,
its embedding and head, its uncached ``apply``, what it refuses and what it
counts.  The cache, the passes and the slot ops below are the object's
methods, so a caller's ``fam.prefill(params, tokens, cfg, cache)`` reads
the same for every family.
The ROW is the family's too (``config.cache_row``: the widths of the banks a
cached token takes in a layer): the dense block keeps K and V, two banks of
``H*D``; a latent-attention block (``latent_moe_inference``) keeps one
compressed row shared by all heads.  ``KVCache``, ``init_cache``, the slot
ops and the sweep's block take that row, never ``n_head * head_dim``.

Beside the token-indexed banks the cache has ONE optional leaf of another
kind, ``state``: what a family keeps PER SLOT whatever the conversation's
length (a state-space layer's running sum and its convolution tail,
``hybrid_ssm_moe_inference``).  The family declares it beside its row
(``config.cache_state``: ``(layers, per-slot shape, dtype)`` for each
array), ``init_cache`` makes it ``[layers, B, ...]`` with no ``S`` axis, and
the slot ops treat each array like a bank without one; it is None for every
family that keeps none.  The layers that own banks and the layers that own
state index two different stacks (``config.cache_layers`` banks' layers,
default all), and a pass tells the scan how many of a row's tokens are real
(``valid``): attention masks a padded position at read time, a recurrence
would carry it forever.

Cache layout [L, B, S_max, H*D]: static shapes (XLA requirement), masked by
the current length; decode attention reads the cache tiled over S_max with
positions beyond ``pos`` masked.  A token's heads are folded into ONE row
because the layout is the point: the TPU lays a bf16 array whose last
dimension is 64 (half a lane row) out with the long token dimension on the
lanes, and a [.., S_max, H, D] pool can then take no token's K/V in place —
the compiler re-lays a whole layer around every write and every kernel
call (PERF.md, PR 25).  With H*D last the stored order IS row-major: a
token's K (all heads) is one row, written in place at ``[layer, slot,
pos]``, and the decode kernel's ``[block_k, H*D]`` blocks are read straight
out of the pool.  The layer scan carries the stacked banks (never slices a
layer out), and a caller that donates the cache (the batcher's tick) gets
it updated in place.
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Any, Dict, Mapping, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import gpt

PyTree = Any


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class KVCache:
    """The banks of one cache: ``k`` and ``v`` for a family whose row is two
    banks, ``k`` alone (``v`` None) for a one-bank row (a latent row).
    ``k_scale``/``v_scale`` are ``None`` for a full-precision cache; for
    an int8 cache (``kv_cache_dtype: "int8"``) k/v hold codes and the
    scales are per-vector fp32 [L, B, S_max, H] — half the cache HBM,
    dequantized inside the decode kernel's VMEM stream.  ``stats`` (None
    for most families) is a small int32 vector of counters a family's scan
    step adds to on the device (an expert layer's pair counts): it rides
    the donated cache and reaches the host with the tick's own pull.
    ``state`` (None for most families) is a tuple of per-slot arrays
    ``[layers, B, ...]`` with no token axis, a reset slot's all zero.
    ``ring`` (None for most families) is a SECOND pool, one bank for each
    of ITS row's (``cache_ring_row``: the banks' own widths unless the
    family says otherwise): ``[L_ring, B, R, w]``, the layers that see a
    window of the conversation and keep no more of it: the token at position
    ``p`` lies in cell ``p mod R`` whatever ``S_max`` is."""

    k: jnp.ndarray        # [L, B, S_max, row[0]]
    v: Any                # [L, B, S_max, row[1]] or None
    length: jnp.ndarray   # [] int32 — tokens already cached
    k_scale: Any = None
    v_scale: Any = None
    stats: Any = None
    state: Any = None
    ring: Any = None

    def tree_flatten(self):
        return (self.k, self.v, self.length, self.k_scale, self.v_scale,
                self.stats, self.state, self.ring), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def batch(self) -> int:
        return self.k.shape[1]

    @property
    def max_len(self) -> int:
        return self.k.shape[2]

    @property
    def int8(self) -> bool:
        return self.k_scale is not None


def cache_row(config) -> Tuple[int, ...]:
    """The widths of the banks a cached token takes in one layer: the
    family's declaration (``config.cache_row``), else the dense block's two
    banks of ``H*D``."""
    row = getattr(config, "cache_row", None)
    return tuple(row) if row is not None else \
        (config.n_head * config.head_dim,) * 2


def cache_layers(config) -> int:
    """The layers that own banks (``config.cache_layers``), else all."""
    return getattr(config, "cache_layers", config.n_layer)


def cache_ring(config, max_len: int) -> Optional[Tuple[int, int]]:
    """``(layers, R)`` of the family's second pool over ``max_len``-token
    slots (``config.cache_ring``: the layers that keep a window and the
    window's length), else None.  A slot shorter than the window never
    laps its ring, so the ring is no longer than the slot."""
    ring = getattr(config, "cache_ring", None)
    return None if not ring else (int(ring[0]), min(int(ring[1]), max_len))


def cache_ring_row(config) -> Tuple[int, ...]:
    """The widths of a ring's banks (``config.cache_ring_row``: window
    layers whose cached token is not the full layers'), else the row's."""
    row = getattr(config, "cache_ring_row", None)
    return tuple(row) if row is not None else cache_row(config)


def init_cache(config, batch: int, max_len: int, kv_dtype=None,
               stats: Optional[Dict[str, slice]] = None) -> KVCache:
    """``kv_dtype``: None → cache in the compute dtype; ``"int8"``/
    ``jnp.int8`` → int8 codes + per-vector fp32 scales (beyond-reference:
    halves decode HBM traffic and doubles the context/batch a chip's
    cache budget holds; the two-bank dense row only).  ``stats``: the
    groups of the family's counter vector, name -> where the group lies
    (its ``Family.stats_groups``, which owns the layout; none: no
    vector)."""
    row = cache_row(config)
    layers = cache_layers(config)
    shapes = [(layers, batch, max_len, w) for w in row]
    declared = getattr(config, "cache_state", None)
    state = None if not declared else tuple(
        jnp.zeros((n, batch) + tuple(shape), dtype)
        for n, shape, dtype in declared)
    ring = cache_ring(config, max_len)
    if kv_dtype in ("int8", jnp.int8):
        if state is not None or ring is not None:
            raise NotImplementedError(
                "the int8 cache exists for families without per-slot state "
                "and without rings")
        if len(row) != 2:
            raise NotImplementedError(
                "the int8 cache (codes and per-head scale banks) exists for "
                f"the two-bank dense row only; this family's row is {row}")
        scales = shapes[0][:-1] + (config.n_head,)
        return KVCache(k=jnp.zeros(shapes[0], jnp.int8),
                       v=jnp.zeros(shapes[1], jnp.int8),
                       length=jnp.zeros((), jnp.int32),
                       k_scale=jnp.zeros(scales, jnp.float32),
                       v_scale=jnp.zeros(scales, jnp.float32))
    banks = [jnp.zeros(shape, config.dtype) for shape in shapes]
    return KVCache(k=banks[0], v=banks[1] if len(banks) > 1 else None,
                   length=jnp.zeros((), jnp.int32),
                   stats=jnp.zeros((max(g.stop for g in stats.values()),),
                                   jnp.int32) if stats else None,
                   state=state,
                   ring=None if ring is None else tuple(
                       jnp.zeros((ring[0], batch, ring[1], w), config.dtype)
                       for w in cache_ring_row(config)))


# ------------------------------------------------------------- slot ops
#
# A continuous-batching server owns ONE fixed-geometry multi-slot cache and
# retires/admits conversations per ROW without touching the others.  These
# three ops are that contract: ``row`` may be a traced scalar, so one
# compiled program serves every slot — admitting into slot 7 never
# recompiles the program that admitted into slot 2.  They walk whatever
# banks the family's row has (a bank the cache lacks is None and stays so),
# the rings of its second pool and the per-slot state where the family keeps
# either: every such array leads with ``[layers, B]``, and what follows
# (tokens, a ring's cells, or none) is the slot's.


def _each_bank(f, cache: KVCache, *others: KVCache) -> dict:
    """``f`` over every bank (and scale bank) the cache holds, and over the
    arrays of its rings and of its per-slot state."""
    out = {name: f(getattr(cache, name), *(getattr(o, name) for o in others))
           for name in ("k", "v", "k_scale", "v_scale")
           if getattr(cache, name) is not None}
    for name in ("state", "ring"):
        if getattr(cache, name) is not None:
            out[name] = tuple(
                f(a, *(getattr(o, name)[i] for o in others))
                for i, a in enumerate(getattr(cache, name)))
    return out


def _at_slot(buf, row):
    """Start indices of slot ``row`` in a ``[layers, B, ...]`` array."""
    return (0, row) + (0,) * (buf.ndim - 2)


def write_slot(cache: KVCache, row, src: KVCache) -> KVCache:
    """Insert a batch-1 cache into slot ``row`` of a live multi-slot cache
    (admission: a newly prefilled prompt lands in a slot freed by a
    finished generation).  ``src`` must share the cache dtype layout;
    its ``max_len`` must not exceed the slot cache's.  ``length`` keeps
    max-frontier semantics — the slot engine tracks per-row lengths
    itself.  ``src``'s counters are added to the pool's."""
    if src.int8 != cache.int8:
        raise ValueError(
            f"write_slot dtype mismatch: src int8={src.int8}, "
            f"cache int8={cache.int8}")
    if src.max_len > cache.max_len:
        raise ValueError(
            f"write_slot src max_len {src.max_len} exceeds the slot "
            f"cache's {cache.max_len}")

    def ins(dst, s):
        return lax.dynamic_update_slice(dst, s, _at_slot(dst, row))

    return dataclasses.replace(
        cache, length=jnp.maximum(cache.length, src.length),
        stats=None if cache.stats is None else cache.stats + src.stats,
        **_each_bank(ins, cache, src))


def reset_slot(cache: KVCache, row) -> KVCache:
    """Zero slot ``row``'s K/V (and scales, and per-slot state): a retired
    conversation's K/V never bleeds into the next tenant, even through a
    masked read, and a state-space layer starts from zero."""
    def z(buf):
        blank = jnp.zeros((buf.shape[0], 1) + buf.shape[2:], buf.dtype)
        return lax.dynamic_update_slice(buf, blank, _at_slot(buf, row))

    return dataclasses.replace(cache, **_each_bank(z, cache))


def read_slot(cache: KVCache, row, length=None) -> KVCache:
    """Slot ``row`` as a batch-1 cache (retiring a live conversation back
    to a session).  ``length`` is the row's true frontier (the multi-slot
    ``cache.length`` only tracks the max).  Counters stay with the pool:
    the copy's start at zero."""
    def rd(buf):
        return lax.dynamic_slice(buf, _at_slot(buf, row),
                                 (buf.shape[0], 1) + buf.shape[2:])

    return dataclasses.replace(
        cache, length=jnp.asarray(
            length if length is not None else cache.length, jnp.int32),
        stats=None if cache.stats is None else jnp.zeros_like(cache.stats),
        **_each_bank(rd, cache))


def _cached_attention(q, cache_k, cache_v, pos, config: gpt.GPTConfig,
                      window=None, k_scale=None, v_scale=None, layer=None,
                      active=None, sweep=None, row=None):
    """q: [B, S_q, H, D] attending to cache[:, :pos+S_q]; with ``layer``
    the cache operands are the stacked [L, B, S_max, H*D] pool and the
    decode kernel reads that layer in place.  ``active`` / ``sweep``
    (single-token decode only): the live rows and the kernel's work list
    built from them, see ``cached_attention``; ``row`` (a chunk only): the
    pool's rows the queries read, see there too.

    ``pos`` is the number of tokens already in the cache before this call;
    query i sits at absolute position pos+i and sees cache slots ≤ pos+i.
    ``window`` (traced per-layer scalar) bands visibility; with
    ``pos_embed == "alibi"`` the per-head ``-slope·dist`` bias is added.
    Both now ride the streaming kernels (window-skipping cache blocks /
    biasing in VMEM) with the dense reference as the non-tiling fallback
    — so an int8 cache (``k_scale``/``v_scale``) composes with
    alibi/windowed models and still dequantizes block-by-block in VMEM.
    """
    from ..ops.pallas.decode_attention import cached_attention
    scale = config.attn_softmax_scale
    slopes = None
    if config.pos_embed == "alibi":
        # train/prefill's _alibi_attention fixes the scale at 1/sqrt(D)
        # (gpt.py) — decode must agree or generation diverges from the
        # cache the prefill filled
        scale = None
        if window is None:
            # banded layers in train/prefill run _windowed_attention,
            # which carries NO alibi bias — window takes precedence here
            # too, for the same prefill/decode consistency
            slopes = gpt.alibi_slopes(config.n_head)
    if scale is None:
        scale = 1.0 / math.sqrt(config.head_dim)
    return cached_attention(q, cache_k, cache_v, pos, sm_scale=scale,
                            k_scale=k_scale, v_scale=v_scale,
                            window=window, slopes=slopes, layer=layer,
                            active=active, sweep=sweep, row=row)


def _block_tail(x, attn, p, config: gpt.GPTConfig):
    """Post-attention half of the block, honouring parallel_residual."""
    attn_out = gpt.attn_project(attn, p, config)
    if config.parallel_residual:
        return x + attn_out + gpt.mlp_out(x, p, config)
    return gpt.mlp_residual(x + attn_out, p, config)


def dense_step(params: PyTree, config: gpt.GPTConfig, valid=None):
    """The dense stack's half of :func:`_layer_scan`: one segment,
    ``[(stacks, body)]``.  One scan step is one block: attention at layer
    ``i``, then its tail."""
    def body(x, p, i, attend, cache):
        a, cache = attend(x, p, i, cache)
        return _block_tail(x, a, p, config), cache

    return [(params["blocks"], body)]


def _dense_project(x, p, config, positions):
    q, k, v = gpt.qkv_proj(x, p, config, positions=positions)
    return q, (k, v)


def _dense_attend_fresh(q, fresh, cache, config, idx):
    # prefill attention runs on the unpadded k/v (training flash path);
    # only decode reads back through the padded cache
    k, v = fresh
    return gpt._attention(q, k, v, config,
                          window=gpt.layer_window(config, idx, k.shape[1]))


def _dense_attend_cached(q, cache, pos, config, idx, active=None,
                         sweep=None, row=None):
    return _cached_attention(
        q, cache.k, cache.v, pos, config,
        window=gpt.layer_window(config, idx, cache.max_len),
        k_scale=cache.k_scale, v_scale=cache.v_scale, layer=idx,
        active=active, sweep=sweep, row=row)


def _dense_windows(config: gpt.GPTConfig, max_len: int):
    """The dense stack's banded layers: None where it has none, else
    ``layer indices -> their windows`` (``gpt.layer_window``, the one
    source of the alternation rule)."""
    if config.local_attention_window <= 0:
        return None
    return lambda idx: gpt.layer_window(config, idx, max_len)


#: why a family other than the dense one is refused as a speculative draft
#: (its ``unsupported["draft"]``)
DENSE_DRAFTS_ONLY = ("a draft's whole point is being small, and the proposal "
                     "loop rides the dense family")


def _row_plan(config, max_len: int, itemsize: int = 2,
              windows=((None, 1),), ring: bool = False):
    """The kernel file's plan (``decode_attention.sweep_plan``) for this
    config's row: which single-token sweep serves it, its block and where
    its last copy ends.  The row is the config's own declaration
    (``cache_row``; two banks hold ``row / head_dim`` key-value heads):
    ``ring``: its rings' (``cache_ring_row``); else, where a bank of the row
    is not the sweep's to stream (an index's keys beside a latent row), the
    banks the family names (``config.cache_sweep_row``)."""
    from ..ops.pallas.decode_attention import sweep_plan
    row = cache_ring_row(config) if ring else \
        tuple(getattr(config, "cache_sweep_row", None) or cache_row(config))
    return sweep_plan(
        row, max_len, config.n_head,
        kv_heads=row[0] // config.head_dim if len(row) == 2 else None,
        itemsize=itemsize, windows=windows)


@dataclasses.dataclass(frozen=True, eq=False)
class Family:
    """What a model family is: ``models.cache_family(config)`` returns one,
    and the engine, the batcher and speculative decoding drive a model
    through nothing else.  Its hooks, each with the dense block's default:

    ``step(params, config, valid)``: the scan's segments, ``[(stacks,
    body), ...]`` in depth order: the parameter stacks a scan walks and
    ``body(x, p, i, attend, cache) -> (x, cache)``, what step ``i`` of that
    segment does with ``attend(x, p, layer, cache) -> (attention output,
    cache)`` (:func:`dense_step`: one block a step; GPT-MoE: a dense and an
    expert block, layers ``2i`` and ``2i+1`` of the same pool; a stack with
    leading dense layers: two segments; layers of two kinds: a segment per
    run of one kind).  A body may add to ``cache.stats`` and advance
    ``cache.state``; ``valid`` [B] int32 is how many of the call's tokens
    are real in each row (a tick: 1 for a live row, 0 for a freed one), for
    a body whose state a padded position would corrupt.
    ``project(x, p, config, positions) -> (q, fresh)``: a layer's queries
    and the row it caches, one array per bank, ``[B, S, ...]`` (trailing
    dimensions are folded into the bank's one).
    ``attend_fresh(q, fresh, cache, config, layer)``: a prompt pass from
    position 0; ``attend_cached(q, cache, pos, config, layer, active,
    sweep)``: a chunk or one token against layer ``layer`` of the pool.
    ``windows(config, max_len)``: its banded layers, None for none, else
    ``bank-owning layer indices -> windows``.  With the row the config
    declares (``cache_row`` / ``cache_layers``) that is all a family says
    of its single-token sweep: the kernel, its block and its copy boundary
    are ``decode_attention.sweep_plan``'s, the work list and the host's
    counts are built from it here (:meth:`sweep_plan`, :func:`_sweeps`).
    ``embed(params, tokens, config, positions)`` and ``logits(params, x,
    config)``.
    ``prompt_pass(params, tokens, config, cache, family, valid, head)``: a
    family's own prompt pass (GPT-MoE bounds its gate's dispatch tensors);
    None: one :func:`prefill`.

    Beside the hooks: ``apply(params, tokens, config)``, the uncached
    full-sequence logits, and ``logical_axes(config)``; ``unsupported``:
    feature (``speculative``, ``paging``, ``prefix``, ``int8``, ``draft``:
    serving as a speculative draft) -> why the family is refused it;
    ``stats_groups(config)``: where each group of its device counters lies
    in ``cache.stats`` (name -> slice; the one place that knows);
    ``state_counters``: the names of its group ``state_steps``;
    ``select_counters``: the names of its group ``sparse_select`` (a family
    whose queries attend to a selection of their cache); ``chunk_form(config,
    chunk)``: the name of the form its passes of ``chunk`` positions take,
    for a family whose passes have more than one (the latent families'
    ``"up_projected"`` / ``"absorbed"``; None: one form, nothing to say);
    ``pool_rows``: its ``attend_cached`` takes ``row=`` and reads a chunk's
    keys out of that row of a pool of any number of rows (the dense block's
    does), so an ``extend`` may work on a slot's own row (:func:`in_place`)."""
    step: Any
    project: Any = _dense_project
    attend_fresh: Any = _dense_attend_fresh
    attend_cached: Any = _dense_attend_cached
    windows: Any = _dense_windows
    embed: Any = gpt.embed
    logits: Any = gpt.lm_logits
    prompt_pass: Any = None
    apply: Any = gpt.apply
    logical_axes: Any = gpt.logical_axes
    unsupported: Mapping[str, str] = dataclasses.field(default_factory=dict)
    stats_groups: Any = lambda config: {}
    state_counters: Tuple[str, ...] = ()
    select_counters: Tuple[str, ...] = ()
    chunk_form: Any = None
    pool_rows: bool = False

    # the cache, the passes and the slot ops: the module's functions below
    # with this family in them, defined once for every family

    def init_cache(self, config, batch: int, max_len: int,
                   kv_dtype=None) -> KVCache:
        why = self.unsupported.get("int8")
        if why and kv_dtype is not None:
            raise NotImplementedError(f"{why} (kv_cache_dtype={kv_dtype!r})")
        return init_cache(config, batch, max_len, kv_dtype,
                          stats=self.stats_groups(config))

    def prefill(self, params, tokens, config, cache, valid=None,
                head=True):
        return (self.prompt_pass or prefill)(params, tokens, config, cache,
                                             family=self, valid=valid,
                                             head=head)

    def extend(self, params, tokens, config, cache, lengths=None,
               valid=None, row=None, head=True):
        return extend(params, tokens, config, cache, lengths=lengths,
                      family=self, valid=valid, row=row, head=head)

    def decode_step(self, params, token, config, cache, lengths=None,
                    active=None):
        return decode_step(params, token, config, cache, lengths=lengths,
                           active=active, family=self)

    write_slot = staticmethod(write_slot)
    read_slot = staticmethod(read_slot)
    reset_slot = staticmethod(reset_slot)

    def sweep_plan(self, config, max_len: int, itemsize: int = 2):
        """The plan of this family's single-token sweep over ``max_len``-
        token slots (``decode_attention.SweepPlan``), its calls in one tick
        counted by window (host values): what a server counts live blocks
        and streamed tokens by, with no device read."""
        layers = cache_layers(config)
        of = self.windows(config, max_len)
        plan = _row_plan(
            config, max_len, itemsize,
            windows=((None, layers),) if of is None else tuple(
                collections.Counter(
                    int(w) for w in np.asarray(of(np.arange(layers)))
                ).items()))
        ring = cache_ring(config, max_len)
        if ring is None:
            return plan
        # the rings are a pool of their own length, planned by it
        return plan._replace(ring=_row_plan(
            config, ring[1], itemsize, windows=((None, ring[0]),),
            ring=True))


def _sweeps(family: Family, pos, B, config, max_len, active):
    """The single-token sweep's work list (``decode_sweep``) for every
    bank-owning layer, built ONCE, before the layer scan: a function of
    the step's ``pos`` and ``active`` alone, and, in a banded stack, of
    each layer's window (all layers' lists in one vectorised build).  Its
    block is the plan's, which is the host's and the kernel's.  A family
    with rings gets a second list, the rings' (a pool ``R`` long at
    frontier ``min(pos, R - 1)``: once a ring has lapped, all of it is
    live), and one that names a second sweep's block
    (``config.cache_second_sweep_block(max_len)``) a pair of lists for its
    whole rows.  Returns ``(layer, ring=False) -> sweep``."""
    from ..ops.pallas.decode_attention import decode_sweep
    block_k = _row_plan(config, max_len).block_k
    of = family.windows(config, max_len)
    ring = cache_ring(config, max_len)
    if of is None and ring is None:
        sweep = decode_sweep(pos, B, max_len, block_k, active)
        return lambda idx: sweep
    if ring is not None:
        R = ring[1]
        sweeps = (decode_sweep(pos, B, max_len, block_k, active),
                  decode_sweep(jnp.minimum(pos, R - 1), B, R,
                               _row_plan(config, R, ring=True).block_k,
                               active))
        # a second sweep of the same rows at another block (an index over
        # its own bank of keys): its list rides beside the first
        second = getattr(config, "cache_second_sweep_block", None)
        if second is not None:
            sweeps = ((sweeps[0], decode_sweep(pos, B, max_len,
                                               second(max_len), active)),
                      sweeps[1])
        return lambda idx, ring=False: sweeps[bool(ring)]
    windows = of(jnp.arange(cache_layers(config)))
    sweeps = jax.vmap(
        lambda w: decode_sweep(pos, B, max_len, block_k, active, w))(windows)
    return lambda idx: jax.tree_util.tree_map(lambda a: a[idx], sweeps)


#: the dense GPT family; also every speculative draft's
DENSE = Family(step=dense_step, pool_rows=True)


def in_place(family, cache) -> bool:
    """Whether one row's ``extend`` may run on a row of the POOL ``cache``
    itself (``row=``), with no batch-1 cache made of it and none copied
    back: the family's attention reads a row where it lies
    (``Family.pool_rows``) and the cache is banks alone, two of them in the
    compute dtype.  Anything else a cache holds needs the row apart: a
    recurrence's state must not see a pass's padding, a ring is no prefix
    of a row, a latent row and scale banks have kernels that read a layer
    of a batch.  Decided by what the cache holds, never by a model's name."""
    return bool(getattr(family, "pool_rows", False)) \
        and isinstance(cache, KVCache) and cache.v is not None \
        and not cache.int8 and cache.state is None and cache.ring is None


def _layer_scan(x, params, cache: KVCache, config, positions, write, attn,
                family: Family = DENSE, valid=None):
    """The one layer-stack scan every cache-filling path shares.

    The cache rides the scan's CARRY, never its ``xs``/``ys``: no layer is
    sliced out of the pool and none is written back into a second stack.
    ``write(bank, layer, val)`` places this step's row (or scale) column(s)
    into layer ``layer`` of a stacked bank, in place; int8 caches quantize
    per vector first and write codes + scales through the same ``write``.
    ``attn(q, fresh, cache, layer)`` computes the sublayer's attention
    (prefill reads the fresh unpadded rows; extend/decode read layer
    ``layer`` of the updated pool where it lies).  ``family``: see
    :class:`Family`; ``valid``: its ``step``'s.  Returns (hidden
    states, updated KVCache, ``length`` untouched).

    A body whose layer keeps a RING (``cache.ring``, layer ``idx`` of that
    pool) says so statically: ``attend(x, p, idx, cache, ring=True)``.  The
    family's hooks are then handed ``ring=True`` too (its ``project`` may
    rotate a window layer by another table), the row goes to cell ``pos mod
    R`` (``_ring_write``: real tokens only, the last ``R`` of them), and the
    order depends on the call: a single token is written and then sweeps its
    ring (it overwrites the token that just left the window), a chunk
    attends over the ring as it was and its own rows (``fresh=``) and is
    written after: written first it would overwrite keys its first queries
    still see.

    An attend hook may count: where ``attn`` returns ``(output, counts)``,
    ``counts`` (a vector as long as ``cache.stats``, the family's layout) is
    added to the cache's counters.
    """
    int8 = cache.int8
    if int8:
        from ..ops.pallas.decode_attention import quantize_kv

    def fold(t):
        """[B, S, ...] → [B, S, *]: a token's row in one bank."""
        return t.reshape(t.shape[:2] + (-1,))

    def counted(a, cache):
        if not isinstance(a, tuple):
            return a, cache
        a, counts = a
        return a, dataclasses.replace(cache, stats=cache.stats + counts)

    def ring_attend(q, fresh, idx, cache):
        def put(cache):
            with jax.named_scope("cache_update"):
                return dataclasses.replace(cache, ring=tuple(
                    _ring_write(bank, idx, fold(val.astype(bank.dtype)),
                                positions, valid)
                    for bank, val in zip(cache.ring, fresh)))

        single = fresh[0].shape[1] == 1
        if single:
            cache = put(cache)
        with jax.named_scope("cache_read"):
            a, cache = counted(attn(q, fresh, cache, idx, ring=True), cache)
        return a, cache if single else put(cache)

    def attend(x, p, idx, cache, ring=False):
        # the scopes name, in a profiler's trace, a layer's queries and
        # row, and the two places a tick touches the slot cache
        with jax.named_scope("project"):
            q, fresh = family.project(x, p, config, positions,
                                      **({"ring": True} if ring else {}))
        if ring:
            return ring_attend(q, fresh, idx, cache)
        with jax.named_scope("cache_update"):
            if int8:
                (kq, ks), (vq, vs) = map(quantize_kv, fresh)
                cache = dataclasses.replace(
                    cache, k=write(cache.k, idx, fold(kq)),
                    v=write(cache.v, idx, fold(vq)),
                    k_scale=write(cache.k_scale, idx, fold(ks)),
                    v_scale=write(cache.v_scale, idx, fold(vs)))
            else:
                banks = [write(bank, idx, fold(val.astype(bank.dtype)))
                         for bank, val in zip((cache.k, cache.v), fresh)]
                cache = dataclasses.replace(
                    cache, k=banks[0], v=banks[1] if len(banks) > 1 else None)
        with jax.named_scope("cache_read"):
            return counted(attn(q, fresh, cache, idx), cache)

    for stacks, body in family.step(params, config, valid):
        def layer(carry, xs, body=body):
            (x, cache), (p, i) = carry, xs
            return body(x, p, i, attend, cache), None

        n_steps = jax.tree_util.tree_leaves(stacks)[0].shape[0]
        (x, cache), _ = lax.scan(layer, (x, cache),
                                 (stacks, jnp.arange(n_steps)))
    return x, cache


def _ring_write(bank, layer, val, positions, valid):
    """``val`` [B, S, w], the rows of the tokens at ``positions`` ([S] or
    [B, S], consecutive in a row), into layer ``layer`` of a ring bank ``[L,
    B, R, w]``: position ``p`` to cell ``p mod R``.  Only a row's REAL
    tokens (``valid`` [B] of them) are written, and of those the last ``R``:
    padding past a prompt's end would land on cells the next tokens'
    windows still hold (in a bank of whole rows it lies past the frontier),
    and a pass longer than the ring would write a cell twice.

    One token a row is one cell a row.  A chunk never scatters: the ``R``
    rows that may land are rolled to their cells (cell ``c`` takes token
    ``(c - first) mod R`` of them) and laid over the layer's rings where
    they are real, one slice read and written."""
    B, S, _ = val.shape
    R = bank.shape[2]
    pos = jnp.broadcast_to(positions if positions.ndim == 2
                           else positions[None], (B, S))
    if S == 1:
        cols = jnp.where(valid > 0, pos[:, 0] % R, R)
        return bank.at[layer, jnp.arange(B), cols].set(val[:, 0],
                                                       mode="drop")
    n = min(S, R)
    start = jnp.clip(valid - R, 0, S - n)       # the last R real tokens'
    rows = val if S == n else jax.vmap(
        lambda v, s: lax.dynamic_slice_in_dim(v, s, n, 0))(val, start)
    if n < R:
        rows = jnp.pad(rows, ((0, 0), (0, R - n), (0, 0)))
    first = (pos[:, 0] + start) % R             # the cell of rows[:, 0]
    rolled = jax.vmap(lambda r, f: lax.dynamic_slice_in_dim(
        jnp.concatenate([r, r], 0), R - f, R, 0))(rows, first)
    landed = (jnp.arange(R)[None] - first[:, None]) % R \
        < (valid - start)[:, None]
    old = lax.dynamic_index_in_dim(bank, layer, 0, keepdims=False)
    return lax.dynamic_update_slice(
        bank, jnp.where(landed[..., None], rolled, old)[None],
        (layer, 0, 0, 0))


def _chunk_scatter(bank, layer, val, pos0):
    """``val`` [B, S_c, w], row ``b``'s at cells ``pos0[b] ..`` of ITS row
    of layer ``layer`` of a bank ``[L, B, S, w]``: a scatter of ``B * S_c``
    rows, which drops those past the row's end."""
    B, Sc, _ = val.shape
    return bank.at[layer, jnp.arange(B)[:, None],
                   pos0[:, None] + jnp.arange(Sc)].set(val)


def _chunk_slice(bank, layer, val, pos0, row=0):
    """:func:`_chunk_scatter` of ONE row (``B == 1``, ``S_c <= S``), whose
    cells are consecutive: one update slice, the same cells written bit for
    bit, into row ``row`` of the bank (a batch-1 cache's only one; a slot's
    own, where an admission works on the pool: :func:`in_place`).  An
    update slice CLAMPS its start where a scatter drops, so a
    chunk that would pass the row's end starts ``over`` cells early (0
    everywhere else), its rows moved up by as many over the cells as they
    were: what lies before the frontier is written back as read, the rows
    past the end fall off.

    The scatter also held the bank row-major, as the pool is, in a program
    that makes its own row cache (an admission's).  Left free, the TPU's
    compiler lays that cache out tokens-on-lanes for the chunk kernel's
    read and re-lays both banks whole for the slot write (2 x 0.35 ms an
    admission of GPT-2 350M, by its own estimate): the layout is said
    here."""
    from jax.experimental.layout import Layout, with_layout_constraint
    S, Sc = bank.shape[2], val.shape[1]
    at = jnp.minimum(pos0[0], S - Sc)
    over = jnp.minimum(pos0[0] - at, Sc)
    old = lax.dynamic_slice(bank, (layer, row, at, 0), (1,) + val.shape)
    rows = jnp.where((jnp.arange(Sc) >= over)[:, None],
                     jnp.roll(val, over, axis=1), old[0])
    return with_layout_constraint(
        lax.dynamic_update_slice(bank, rows[None], (layer, row, at, 0)),
        Layout(major_to_minor=tuple(range(bank.ndim))))


def _real_tokens(valid, B: int, S: int):
    """``valid`` as the ``[B]`` int32 a family's ``step`` takes (default:
    all ``S`` tokens of every row are real)."""
    return jnp.full((B,), S, jnp.int32) if valid is None else \
        jnp.broadcast_to(jnp.asarray(valid, jnp.int32).reshape(-1), (B,))


def prefill(params: PyTree, tokens: jnp.ndarray, config, cache: KVCache,
            family: Family = DENSE, valid=None,
            head: bool = True) -> Tuple[jnp.ndarray, KVCache]:
    """Run the prompt through the model, filling cache[0:S].

    Returns (logits [B, S, padded_vocab] fp32, cache).  Assumes an empty
    cache (length 0) — chunked prefill composes by calling with growing
    ``cache.length`` via :func:`extend`.  ``family`` (here, in ``extend``
    and in ``decode_step``) is the model family's, see :class:`Family`.
    ``head`` False (here and in ``extend``; a Python bool, static under
    ``jit``): the layer stack's output ``x`` [B, S, d] in the compute dtype
    stands in the logits' place, before the final norm, so
    ``family.logits(params, x, config)`` of any rows of it are those rows'
    logits: for a caller that wants one row's (an admission,
    ``serving.batcher.admission``) or none (a pass that only fills the
    cache).
    ``valid`` [B] (here and in ``extend``, of every family's): how many of
    a row's ``S`` tokens are real, when the caller pads (default: all).
    Only a family with per-slot state reads it; the banks take the padding
    either way.
    """
    B, S = tokens.shape
    positions = jnp.arange(S)
    x = family.embed(params, tokens, config, positions=positions)

    def write(bank, layer, val):
        return lax.dynamic_update_slice(bank, val[None], (layer, 0, 0, 0))

    def attn(q, fresh, cache, idx, **ring):
        return family.attend_fresh(q, fresh, cache, config, idx, **ring)

    x, cache = _layer_scan(x, params, cache, config, positions, write, attn,
                           family, _real_tokens(valid, B, S))
    out = family.logits(params, x, config) if head else x
    return out, dataclasses.replace(cache, length=jnp.asarray(S, jnp.int32))


def extend(params: PyTree, tokens: jnp.ndarray, config, cache: KVCache,
           lengths=None, family: Family = DENSE, valid=None, row=None,
           head: bool = True) -> Tuple[jnp.ndarray, KVCache]:
    """Chunked prefill: append ``tokens`` [B, S_c] at positions
    ``cache.length .. cache.length+S_c-1``, attending causally over the
    cached prefix + the chunk.

    Composes: ``prefill(p, t[:, :c]) ; extend(p, t[:, c:])`` equals one
    full ``prefill`` (same logits for the appended chunk, same cache) —
    long prompts process in bounded-activation chunks, and a multi-turn
    server appends each new turn to the session's existing cache instead
    of re-prefilling the whole conversation.  Works on fp and int8
    caches (the chunk path reads the cache densely, dequantizing when
    int8).

    Returns (logits [B, S_c, padded_vocab] fp32, cache advanced by S_c);
    with ``head`` False, ``x`` [B, S_c, d] in the logits' place, as
    :func:`prefill` does.

    Overflow: appending past ``max_len`` is checked eagerly (host call
    with a concrete ``cache.length``); under an outer jit the length is
    traced and the caller must size the cache — a clamped write would
    silently corrupt the cached prefix.

    ``lengths`` [B] makes the chunk RAGGED (batched speculative verify:
    each row's S_c tokens sit at ITS frontier): row b's chunk lands at
    slots ``lengths[b] .. lengths[b]+S_c-1`` and attends through its own
    live prefix; ``cache.length`` advances to ``max(lengths) + S_c`` and
    the caller tracks per-row lengths.

    ``row`` (scalar, may be traced; with ``lengths`` [1] and one row of
    tokens, where :func:`in_place` says the family and the cache allow it):
    ``cache`` is a POOL of any number of rows and the chunk is written to,
    and attends over, row ``row`` of it where it lies (an admission's pass
    on the slot it fills).  Cells past the chunk keep what they held: every
    reader masks by the row's length.  ``cache.length`` keeps the pool's
    max-frontier meaning.
    """
    B, Sc = tokens.shape
    ragged = lengths is not None
    pos0 = lengths if ragged else cache.length
    if row is not None and not (ragged and B == 1 and Sc <= cache.max_len
                                and in_place(family, cache)):
        raise ValueError(
            "extend(row=) is one row's ragged chunk (lengths [1]) on a pool "
            "of banks alone, for a family whose attention reads a row of it")
    if not isinstance(pos0, jax.core.Tracer) and \
            int(jnp.max(pos0)) + Sc > cache.max_len:
        raise ValueError(
            f"extend of {Sc} tokens at length {int(jnp.max(pos0))} "
            f"overflows the cache (max_len {cache.max_len}); the write "
            "would clamp and corrupt the cached prefix")
    at_row = {} if row is None else {"row": row}
    if ragged:
        positions = pos0[:, None] + jnp.arange(Sc)          # [B, S_c]
        # one row's chunk is consecutive cells of that row: a slice
        place = _chunk_slice if B == 1 and Sc <= cache.max_len \
            else _chunk_scatter

        def write(bank, layer, val):
            return place(bank, layer, val, pos0, **at_row)
    else:
        positions = pos0 + jnp.arange(Sc)   # [S_c], shared across rows

        def write(bank, layer, val):
            return lax.dynamic_update_slice(bank, val[None],
                                            (layer, 0, pos0, 0))

    x = family.embed(params, tokens, config, positions=positions)

    def attn(q, fresh, cache, idx, **ring):
        if ring:    # the chunk is not in its ring yet: its rows ride along
            ring["fresh"] = fresh
        return family.attend_cached(q, cache, pos0, config, idx, **ring,
                                    **at_row)

    x, cache = _layer_scan(x, params, cache, config, positions, write, attn,
                           family, _real_tokens(valid, B, Sc))
    out = family.logits(params, x, config) if head else x
    length = jnp.max(pos0) + Sc
    return out, dataclasses.replace(
        cache, length=length if row is None else jnp.maximum(
            cache.length, length))


def decode_step(params: PyTree, token: jnp.ndarray, config, cache: KVCache,
                lengths=None, active=None,
                family: Family = DENSE) -> Tuple[jnp.ndarray, KVCache]:
    """One-token decode: token [B] int32 at position cache.length — or,
    with ``lengths`` [B], at per-row positions (ragged right-padded
    prompts: each row's token lands on ITS next slot and sees only ITS
    live prefix; pad-slot K/V is overwritten as rows catch up).

    ``active`` [B] bool (default: every row) names the live rows of a slot
    batch.  A dead row's attention is not computed — the kernel neither
    steps nor streams for it — so its logits are junk; its token is still
    written to its own cell, as every row's is.

    Returns (logits [B, padded_vocab] fp32, cache advanced by one).
    """
    B = token.shape[0]
    ragged = lengths is not None
    pos = lengths if ragged else cache.length
    positions = pos[:, None] if ragged else pos[None]
    x = family.embed(params, token[:, None], config, positions=positions)
    with jax.named_scope("sweep"):
        sweep_of = _sweeps(family, pos, B, config, cache.max_len, active)

    def write(bank, layer, val):
        """One new [B, 1, *] row per slot at [layer, :, pos] (pos shared or
        per-row)."""
        if ragged:
            return bank.at[layer, jnp.arange(B), pos].set(val[:, 0])
        return lax.dynamic_update_slice(bank, val[None], (layer, 0, pos, 0))

    def attn(q, fresh, cache, idx, **ring):
        return family.attend_cached(q, cache, pos, config, idx,
                                    active=active,
                                    sweep=sweep_of(idx, **ring), **ring)

    x, cache = _layer_scan(
        x, params, cache, config, positions, write, attn, family,
        _real_tokens(active, B, 1))
    logits = family.logits(params, x[:, 0], config)
    new_len = (jnp.max(pos) + 1) if ragged else pos + 1
    return logits, dataclasses.replace(cache, length=new_len)
