"""Slot-based continuous batcher: ONE fixed-geometry ragged decode batch.

The inference engine's generate paths size a program per call batch; a
server cannot afford that — traffic is heterogeneous and endless.  The
batcher instead owns a single ``[L, B=slots, max_len, H*D]`` KV cache and
drives it with a closed set of compiled programs whose shapes never depend
on a request:

- an **admission** is ONE program launch fed by one hand-over of host
  arrays: inside it the prompt runs batch-1 through fixed-width chunks
  (prompts right-pad up to a multiple of ``prefill_chunk``; pad K/V lands
  beyond the row's frontier where per-row visibility masks it) in a loop
  whose trip count is traced, the finished batch-1 cache is inserted into
  a free slot with the model family's ``write_slot`` and the row is bound
  — prompt length and ``row`` are traced, so a 9-token prompt into slot 0
  and a 700-token one into slot 7 share one program (a second name
  continues a pooled prefix; :meth:`SlotBatcher.build_prefix` and the
  fleet's prefill worker, which need a batch-1 cache back, run the same
  chunks a launch each).  Where the pool is banks alone and the family
  reads a row of it where it lies (``gpt_inference.in_place``: the dense
  and the GPT-MoE family's bf16 pool) there is no batch-1 cache and no
  insertion: the chunks are written to, and attend over, the slot's own
  row of the donated pool;
- each decode **tick** advances every slot one token through the family's
  ragged ``decode_step`` (per-slot frontiers, per-slot RNG keys, per-slot
  greedy/temperature — all traced operands of one compiled program).

With ``serving.speculative`` enabled the tick loop runs BATCHED
draft/verify rounds instead (``docs/serving.md`` "Speculative tick"): a
second fixed-geometry slot cache holds a small dense draft model's K/V,
admitted and released in lockstep with the target.  Each round the draft
proposes ``draft_k`` tokens per slot (ragged ``decode_step`` scan), ONE
ragged target ``extend`` verifies all slots' windows at their own
frontiers, and the per-slot accept counts advance frontiers by
1..draft_k+1 tokens — rejected positions roll back by the scalar-length
reset (pad K/V beyond the frontier stays masked and is overwritten by
the next round's window).  Greedy slots emit the target's own argmax
chain bit for bit; sampled slots ride the :func:`~deepspeed_tpu.
inference.speculative.spec_accept` rejection rule, exact against the
target distribution.  The extra programs (``draft_step``,
``verify_extend``, ``spec_accept``, the draft admission set) register in
the same :class:`CompiledProgramRegistry`, so the zero-steady-state-
recompile contract covers speculation too.

After the first request of each shape class warms the programs up, the
batcher never compiles again: :meth:`compile_counts` exposes the jit cache
sizes so tests can assert exactly that.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..inference.bucketing import bucket_cache_len, bucket_draft_k
from ..inference.sampling import filter_logits
from ..inference.speculative import (spec_accept_batch, spec_accept_keys,
                                     spec_draft_keys)
from ..models import gpt_inference
from ..telemetry.spans import SpanName, Tracer
from ..utils.compile_watch import CompiledProgramRegistry, hot_path
from .config import ServingConfig
from .paging import cache_bank_bytes


class _Counted(NamedTuple):
    """A plain tick's tokens with the counters the family's scan steps
    added up on the device since the tick before (``KVCache.stats``): one
    pytree, so one pull brings both."""
    tokens: Any
    counts: Any


@dataclasses.dataclass
class PrefixEntry:
    """A shared prompt prefix held as a batch-1 cache of slot geometry —
    forks are zero-copy (jax arrays are immutable), so N conversations
    over one system prompt hold one copy of its K/V."""

    cache: Any
    length: int


#: widths of an admission's passes beside the chunk, in both directions.  A
#: pass over the weights costs their stream whatever it holds until its
#: rows' products take longer, on a v5e at 197e12 / 819e9 = 240 rows: a pass
#: of 128 rows pays for 240 (2.22 ms of GPT-2 medium's device time), the
#: first width past the ridge runs twice the rows for a quarter more (2.84
#: ms at 256), and past it a pass is bound by its products (5.14 ms at 512
#: for two of 256's 5.68; of a stack that streams every expert 16.5 / 20.9 /
#: 30.0 ms at 256 / 512 / 1,024: PERF.md 6, PR 62) while every width is one
#: more body of the model to trace, lower and load: 0.65 s of a server's
#: start for GPT-2 medium (PERF.md 6, PR 52), 2.4-15 s in the cells with a
#: chunk of 512 or 1,024 (PR 62).  UPWARD one width stands, the first past
#: the ridge: a chunk under it runs its whole passes there.  DOWNWARD one
#: width stands too, the chunk's HALF where that is no narrower than the
#: ridge (a pass under it costs what the ridge costs): a prompt's LAST
#: pass, the only one that holds padding, runs there if what is left of
#: the prompt fits.  It saves at most half a chunk of a prompt's rows and
#: costs a body of the model like any width, so it stands only where a slot
#: is at most ``NARROW_SLOT_CHUNKS`` chunks long: prompts of a pass or two,
#: of which half a chunk is a fifth (``lfm2-serve-assist-sat``, 3 chunks a
#: slot: +3.5% tokens/s for +3% of its start; at 8 to 16 chunks a slot the
#: same width read +0.3 to +1.6% for +2.5 to +21% of the start).  The
#: quarter was measured and dropped: +0.8% more there for 2.9 s of 30.
#: ``WIDE_PASSES`` is a tuple because the admission runs ONE loop over the
#: wide widths of :func:`pass_widths` whatever their number, and the tests
#: patch in widths (and a ridge) small enough for their slots.
WIDE_PASSES = (256,)
NARROW_FLOOR = 256
NARROW_SLOT_CHUNKS = 4


def pass_widths(chunk: int, max_len: int) -> Tuple[int, ...]:
    """The widths of an admission's passes at ``prefill_chunk`` ``chunk``
    over ``max_len``-token slots, descending: those of ``WIDE_PASSES`` that
    are whole multiples of the chunk, wider than it and no longer than the
    slot; the chunk itself; then, for a prompt's last pass, the chunk's
    half where that is whole and no narrower than ``NARROW_FLOOR`` and the
    slot at most ``NARROW_SLOT_CHUNKS`` chunks long (``(1024, 512)`` at a
    chunk of 1,024 in slots of 3,072; the chunk alone in slots of 8,192, at
    256 and in a slot shorter than 256; ``(256, 128)`` at 128)."""
    half = chunk // 2
    narrow = (chunk % 2 == 0 and half >= NARROW_FLOOR
              and max_len <= NARROW_SLOT_CHUNKS * chunk)
    return tuple(w for w in WIDE_PASSES
                 if w > chunk and w % chunk == 0 and w <= max_len) + (
                     chunk,) + ((half,) if narrow else ())


def last_pass(n, chunk: int, widths: Tuple[int, ...]):
    """Which of ``widths`` the last pass of ``n`` tokens (> 0) takes, as
    its place among those narrower than ``chunk``, counted from 1 (0: the
    chunk, or a wider pass that holds it): the narrowest that holds what
    stands in the prompt's last chunk.  ``n`` is a host integer or a traced
    one: the admission program's rule and its host's count are this one
    function."""
    tail = n - chunk * ((n - 1) // chunk)
    return sum((tail <= w) * 1 for w in widths if w < chunk)


def ladder_passes(n: int, chunk: int, widths: Tuple[int, ...],
                  first: int = 0) -> Tuple[int, int, int]:
    """``(passes, wide, narrow)`` of an admission of ``n`` tokens at
    ``prefill_chunk`` ``chunk`` whose ``first`` chunks run as chunks (1: a
    fresh row's first, the ``prefill``, where no width is narrower than the
    chunk; 0 where it continues a prefix or starts empty): ``narrow`` is the
    width of the last pass where :func:`last_pass` finds one narrower than
    the chunk, else 0; the chunks the prompt is padded to, less that one,
    are taken by each width of ``widths`` from the widest down to the chunk
    in the WHOLE passes that fit what is left, the chunk the rest; ``wide``
    counts the positions of the passes wider than the chunk.  A prompt
    that fits a narrow width is that one pass.  The host's count of the
    admission program's own trip counts."""
    which = last_pass(n, chunk, widths)
    narrow = 0 if not which else [w for w in widths if w < chunk][which - 1]
    left = -(-n // chunk) - (narrow > 0)
    passes = min(first, left)
    left -= passes
    wide = 0
    for w in widths:
        if w > chunk:
            trips = left // (w // chunk)
            passes, wide, left = passes + trips, wide + trips * w, \
                left - trips * (w // chunk)
    return passes + left + (narrow > 0), wide, narrow


def admission(fam, cfg, max_len: int, kv_dtype):
    """The function of the admission program for model family ``fam`` (a
    ``gpt_inference.Family``, as ``models.cache_family`` returns it) at
    ``cfg`` over ``max_len``-token slots: what :class:`SlotBatcher`
    registers as ``admit`` / ``admit_prefix`` (and again at the wide
    chunk), and what the compile tests lower for a described chip."""

    def admit(params, pool, lengths, last, keys, greedy, temp, active,
              tokens, meta, key, prefix=None):
        """One admission, whole: the chunk loops, the slot write and the
        bind.  ``tokens`` [max_len // C, C] is the prompt (past the prefix)
        padded by the host to the slot's chunk count; ``meta`` int32 [7] is
        ``(row, start, n, greedy, temperature's bits, fold?, fold's
        bits)``: the prompt's ``n`` real tokens continue ``prefix`` (a
        batch-1 cache of slot geometry, shared, never donated) at
        ``start``, or fill a fresh row cache from 0, and the row's key is
        ``key``, or ``fold_in(key, fold)`` as the host's own
        ``jax.random.fold_in`` gives it.  After the first chunk a loop a
        width of :func:`pass_widths` down to the chunk runs the whole
        passes of that width that fit the chunks left of the prompt as it
        is padded (so a wide pass computes the positions its chunks would),
        the loop at ``C`` the rest; where a width narrower than ``C`` holds
        what stands in the prompt's last chunk (:func:`last_pass`), those
        loops stop a chunk short and ONE pass of the narrowest such width
        (half the chunk: :func:`pass_widths` gives one) runs over the
        prompt's end, under a ``lax.switch``, so only that pass holds
        padding and less than half of it is (:func:`ladder_passes` counts
        them all).  Every trip count and the choice are traced, so one
        compiled program serves every prompt length; every pass is the
        family's own ``prefill`` / ragged ``extend``, as
        :meth:`SlotBatcher._chunked_prefill` runs them one launch each at
        ``C``, asked for NO head (``head=False``): a pass returns the layer
        stack's output, the loops carry the one row ``[d]`` of it that may
        be the last real token's, and the family's ``logits`` (final norm
        and head product, float32 out) runs ONCE, after the last pass, on
        that row, so no program holds a ``[rows, vocabulary]`` array.  A
        fresh row's first chunk is the ``prefill`` where the
        ladder stops at the chunk; where it goes down, every width is one
        more body of the model in this program (2.4-3.6 s of a warm
        start each in most cells with a chunk of 512 or 1,024, 15 s in
        one: PERF.md 6, PR 62), so a fresh row starts empty and its first pass is the
        ``extend`` at position 0 like the rest: a body a width, and none
        of ``prefill``.

        IN PLACE (``gpt_inference.in_place``: no ``prefix``, a pool of banks
        alone, a family that reads a row where it lies) there is no row
        cache at all: the loops' carry is the donated pool and every pass
        the ``extend`` on ``(row, start + at)`` of it, the first from
        position 0 (so none of ``prefill`` here either, and the wide
        passes start with the prompt), no zero-fill before and no slot
        write after.  The cells past the frontier keep what the row's last
        tenant left: every reader masks by the row's length (the tick's
        sweep, the chunk kernel's ``pos``), as it masks a padded pass's
        rows."""
        C = tokens.shape[1]
        row, start, n = meta[0], meta[1], meta[2]
        widths = pass_widths(C, max_len)
        narrow = tuple(w for w in widths if w < C)
        in_place = prefix is None and gpt_inference.in_place(fam, pool)
        at_row = {"row": row} if in_place else {}
        # the last pass's width among ``narrow`` (0: none of them), and the
        # chunks that run at ``C`` and wider
        which = last_pass(n, C, widths)
        full = (n + C - 1) // C - (which > 0) * 1
        # the named scopes are the parts of the one program, by which a
        # profiler's device time is split (``telemetry.device_time``):
        # ``admit_row_cache`` the batch-1 row cache's allocation and
        # zero-fill, ``admit_chunk`` a pass of any width (the family's own
        # scopes below it, and none of them a head: a pass returns the
        # layer stack's output), ``admit_head`` the whole head, the one row
        # of a pass's output kept and, after the last pass, the family's
        # final norm and head product on that one row,
        # ``admit_slot_write`` the row's copy into the pool (neither it nor
        # ``admit_row_cache`` in place), ``admit_bind`` the slot's vectors

        def real(at, w):
            # where the prompt ends inside the pass of ``w`` tokens at
            # ``at``: a family that keeps state per slot must not let a
            # recurrence take the padding (the banks take it either way: it
            # lies past the frontier)
            return jnp.clip(n - at, 0, w)[None]

        @jax.named_scope("admit_head")
        def take(x, at):
            # the last real token's row ``[d]`` of the pass's output if the
            # pass at ``at`` holds it (the last pass does; an earlier one's
            # row is junk that the next iteration replaces): the loops carry
            # that, and the head runs once, after them, on the row kept
            idx = jnp.clip(n - 1 - at, 0, x.shape[1] - 1)
            return lax.dynamic_index_in_dim(x[0], idx, 0, keepdims=False)

        def passes(w, done):
            # pass ``i`` of ``w`` tokens after the first ``done``
            def one(i, carry):
                at = done + i * w
                with jax.named_scope("admit_chunk"):
                    x, cache = fam.extend(
                        params,
                        lax.dynamic_slice(tokens.reshape(-1), (at,), (w,))[
                            None], cfg, carry[1], lengths=(start + at)[None],
                        valid=real(at, w), head=False, **at_row)
                return take(x, at), cache
            return one

        # the row kept before any pass ran: a position's hidden state as
        # the embedding makes it (a scan hands it on in the type it took it)
        x0 = jax.eval_shape(
            lambda p: fam.embed(p, tokens[:1, :1], cfg,
                                positions=jnp.arange(1)), params)
        no_row = jnp.zeros(x0.shape[2:], x0.dtype)
        if prefix is not None:
            done, carry = 0, (no_row,
                              dataclasses.replace(prefix, length=start))
        elif in_place:
            done, carry = 0, (no_row, pool)
        else:
            with jax.named_scope("admit_row_cache"):
                fresh = fam.init_cache(cfg, 1, max_len, kv_dtype=kv_dtype)
            if narrow:
                # every pass an ``extend``, the first from the empty row
                done, carry = 0, (no_row, fresh)
            else:
                with jax.named_scope("admit_chunk"):
                    x, cache = fam.prefill(params, tokens[:1], cfg, fresh,
                                           valid=real(0, C), head=False)
                done, carry = 1, (take(x, 0), cache)
        for w in (w for w in widths if w >= C):
            trips = jnp.maximum(full - done, 0) // (w // C)
            carry = lax.fori_loop(jnp.int32(0), trips, passes(w, done * C),
                                  carry)
            done = done + trips * (w // C)
        if narrow:
            # ... and ONE pass over the prompt's end at the narrow width
            # chosen, if one was: a branch a width (what a body re-lays of
            # its weights then lives inside its branch; a loop of 0 or 1
            # trips hoists it out, to live beside the chunk's)
            carry = lax.switch(which, [lambda c: c] + [
                (lambda c, w=w: passes(w, full * C)(jnp.int32(0), c))
                for w in narrow], carry)
        kept, cache = carry
        with jax.named_scope("admit_head"):
            # the family's final norm and head on the one row, float32 out:
            # what a pass's own head gave row ``n - 1`` of its logits
            vec = fam.logits(params, kept[None], cfg)[0]
        with jax.named_scope("admit_bind"):
            key = jnp.where(meta[5] != 0, jax.random.fold_in(
                key, lax.bitcast_convert_type(meta[6], jnp.uint32)), key)
        if in_place:
            pool = cache
        else:
            with jax.named_scope("admit_slot_write"):
                pool = fam.write_slot(pool, row, cache)
        with jax.named_scope("admit_bind"):
            return (pool,
                    lengths.at[row].set(start + n), last.at[row].set(vec),
                    keys.at[row].set(key), greedy.at[row].set(meta[3] != 0),
                    temp.at[row].set(
                        lax.bitcast_convert_type(meta[4], jnp.float32)),
                    active.at[row].set(True), vec)

    return admit


class SlotBatcher:
    """Continuous batching over ``config.slots`` decode slots."""

    def __init__(self, engine, config: ServingConfig,
                 tracer: Optional[Tracer] = None, draft=None):
        #: telemetry tracer shared with the owning gateway (keeps no
        #: records when serving runs without telemetry)
        self.tracer = tracer if tracer is not None else Tracer(
            enabled=False, name="serving")
        self._engine = engine
        self._fam = engine._family
        cfg = engine.model_config
        self._cfg = cfg
        self._kv_dtype = engine._kv_dtype
        self.slots = config.slots
        self.max_len = bucket_cache_len(config.max_len or cfg.max_seq_len,
                                        cfg.max_seq_len)
        # a chunk wider than the slot cannot even land its first write
        self.chunk = min(int(config.prefill_chunk), self.max_len)
        #: degraded-mode prefill chunk (the ladder's ``chunk_widen``
        #: rung): double width = half the passes over the weights at the
        #: cost of more pad compute (the per-chunk dispatch it was made to
        #: halve is gone: an admission is one launch at either width).
        #: Runs through its OWN registered programs (the ``*_wide`` names)
        #: — re-tracing the normal ones at a new shape would count as a
        #: recompile.
        self.chunk_wide = min(self.chunk * 2, self.max_len)
        self._wide = False
        fam = self._fam
        B = self.slots
        # what the family does not serve is refused here, with its reason
        for feature, on in (
                ("speculative", config.speculative_config.enabled),
                ("paging", config.paging_config.enabled)):
            if on:
                self.refuse(feature)
        self.cache = fam.init_cache(cfg, B, self.max_len,
                                    kv_dtype=self._kv_dtype)
        #: bytes of the batch-1 cache every fresh prefill allocates: the
        #: family's row, whatever its banks
        self._row_cache_bytes = cache_bank_bytes(self.cache) // B
        #: whether an admission with no prefix works on the slot's own row
        #: of the pool (no row cache, no slot write): the program's own rule
        self._in_place = gpt_inference.in_place(fam, self.cache)
        #: the plan of the family's single-token sweep over this pool (its
        #: kernel, block, copy boundary and calls a tick): what
        #: ``sweep_blocks`` and ``sweep_by_kind`` count by
        self._sweep = fam.sweep_plan(cfg, self.max_len,
                                     self.cache.k.dtype.itemsize)
        #: what the family's scan steps counted on the device
        #: (``KVCache.stats``), summed over the ticks pulled so far; None
        #: for a family that counts nothing
        self.device_counts = None
        #: where each group of counters lies in them: the family's layout,
        #: read by name through ``counts``
        self._stats_groups = fam.stats_groups(cfg)
        #: the names of the group ``state_steps``, which a family with
        #: per-slot state has
        self.state_counters = fam.state_counters
        #: and of the group ``sparse_select`` (a family that attends to a
        #: selection of its cache)
        self.select_counters = fam.select_counters
        # the slots' vectors are made on the host and handed over: made on
        # the device each is a program of its own to compile, at every start
        self.lengths = jnp.asarray(np.zeros((B,), np.int32))
        self.keys = jnp.asarray(
            np.tile(np.asarray(jax.random.PRNGKey(0)), (B, 1)))
        self.greedy = jnp.asarray(np.ones((B,), bool))
        self.temp = jnp.asarray(np.ones((B,), np.float32))
        self.active = jnp.asarray(np.zeros((B,), bool))
        self._last = None          # [B, padded_vocab], set on first admit
        #: program launches made for admissions, every path's: 1 for an
        #: admission (with or without a prefix), a chunk each where
        #: :meth:`_chunked_prefill` builds a prefix first, the draft's own
        #: under speculation
        self.admit_launches = 0
        #: speculative tick state (None/0 fields when speculation is off)
        self.spec = bool(config.speculative_config.enabled)
        self.draft_k = 0
        self._dcfg = None
        self._dparams = None
        self.draft_cache = None
        #: per-slot PENDING token: sampled from the frontier logits but
        #: not yet cache-written — each spec round emits
        #: ``[cur, accepted drafts]`` and the accept rule's resample or
        #: bonus token becomes the next ``cur``
        self.cur = None
        #: degradation-ladder level for speculation: 0 = full ``draft_k``
        #: rounds, 1 = shrunk ``draft_k2`` rounds, 2 = paused (plain
        #: one-token ticks).  Output semantics are exact at every level —
        #: the accept rule is exact for any proposal, and pause/resume
        #: flush/reseed the pending token through the same split/sample
        #: the plain tick performs.
        self.spec_level = 0
        self.draft_k2 = 0
        #: True while paused ticking: ``cur`` is stale, ``_last`` is live
        self._paused = False
        if self.spec:
            self._init_draft(config, draft)
            self.draft_cache = self._dfam.init_cache(self._dcfg, B,
                                                     self.max_len)
            self.cur = jnp.zeros((B,), jnp.int32)
            self.draft_k2 = max(1, self.draft_k // 2)
        #: extra slot positions a speculative round may write past the
        #: reply budget (the gateway's admission margin)
        self.spec_overshoot = self.draft_k if self.spec else 0
        #: every program the batcher drives, by name — the serving gate
        #: (gateway CompileWatch, compile_report.py) watches this
        self.registry = CompiledProgramRegistry("serving",
                                                tracer=self.tracer)
        self._build_programs(config)

    def counts(self, group: str):
        """One group of the family's cumulative device counters
        (``moe_pairs``: the expert layers' ``pair_counts``; ``state_steps``:
        the ``state_counters``; ``sparse_select``: the ``select_counters``),
        or None where the family has no such group
        or no tick has been pulled yet."""
        where = self._stats_groups.get(group)
        if where is None or self.device_counts is None:
            return None
        return self.device_counts[where]

    def unsupported(self, feature: str) -> Optional[str]:
        """Why the family does not serve ``feature`` (its ``unsupported``),
        or None if it does."""
        return self._fam.unsupported.get(feature)

    def refuse(self, feature: str) -> None:
        """Raise if the family does not serve ``feature``, with its
        reason."""
        why = self.unsupported(feature)
        if why:
            raise NotImplementedError(
                f"serving.{feature} with {type(self._cfg).__name__}: {why}")

    def _init_draft(self, config: ServingConfig, draft) -> None:
        """Resolve the draft model: an engine / ``(cfg, params)`` tuple
        passed to ``serve(draft=...)``, or the config's geometry spec
        (random-init dense GPT over the target's vocabulary — the bench
        fixture path).  The draft must be dense GPT: its whole point is
        being small, and the proposal loop rides ``gpt_inference``."""
        from ..models import gpt
        from ..models.gpt_moe import GPTMoEConfig
        from ..runtime.config import DeepSpeedConfigError
        cfg = self._cfg
        spec_cfg = config.speculative_config
        if draft is None and spec_cfg.draft is None:
            raise DeepSpeedConfigError(
                "serving.speculative.enabled needs a draft model: pass "
                "draft=(GPTConfig, params) / a dense InferenceEngine to "
                "engine.serve(), or set serving.speculative.draft to a "
                "geometry spec {n_layer, d_model, n_head[, seed]}")
        if draft is None:
            d = spec_cfg.draft
            dcfg = gpt.GPTConfig(
                vocab_size=cfg.vocab_size, max_seq_len=cfg.max_seq_len,
                n_layer=int(d.get("n_layer", 2)),
                n_head=int(d.get("n_head", cfg.n_head)),
                d_model=int(d.get("d_model", max(cfg.d_model // 4,
                                                 cfg.n_head))),
                dtype=cfg.dtype, vocab_round_to=cfg.vocab_round_to)
            dparams = gpt.init(dcfg, jax.random.PRNGKey(
                int(d.get("seed", 0))))
        elif hasattr(draft, "model_config") and hasattr(draft, "params"):
            if draft._family.unsupported.get("draft"):
                raise NotImplementedError(
                    "the serving draft must be a dense GPT-family engine")
            dcfg, dparams = draft.model_config, draft.params
        else:
            dcfg, dparams = draft
        if not isinstance(dcfg, gpt.GPTConfig) or \
                isinstance(dcfg, GPTMoEConfig):
            raise TypeError(
                "serving draft must be (gpt.GPTConfig, params) or a dense "
                f"GPT-family InferenceEngine (got config {type(dcfg)})")
        if dcfg.vocab_size != cfg.vocab_size:
            raise ValueError(
                "serving draft and target must share a vocabulary "
                f"({dcfg.vocab_size} vs {cfg.vocab_size})")
        if dcfg.max_seq_len < self.max_len:
            raise ValueError(
                f"serving draft max_seq_len ({dcfg.max_seq_len}) is "
                f"smaller than the {self.max_len}-token slot")
        # the draft computes in the target's serving dtype so one
        # deployment has one numeric story (proposals never change the
        # emitted distribution either way)
        self._dcfg = dataclasses.replace(dcfg, dtype=cfg.dtype)
        self._dparams = jax.tree_util.tree_map(
            lambda p: p.astype(cfg.dtype)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, dparams)
        self._dfam = gpt_inference.DENSE
        self.draft_k = bucket_draft_k(int(spec_cfg.draft_k),
                                      cap=self.max_len)

    # ------------------------------------------------------------ programs

    def _build_programs(self, config: ServingConfig) -> None:
        fam, cfg = self._fam, self._cfg
        top_k, top_p = int(config.top_k), float(config.top_p)
        vocab = cfg.vocab_size

        def tick(params, cache, lengths, last, keys, greedy, temp, active):
            with jax.named_scope("sample"):
                lg = last[:, :vocab]
                ks = jax.vmap(jax.random.split)(keys)     # [B, 2, 2]
                next_keys, subkeys = ks[:, 0], ks[:, 1]
                filt = filter_logits(lg, temp[:, None], top_k=top_k,
                                     top_p=top_p)
                sampled = jax.vmap(jax.random.categorical)(subkeys, filt)
                nxt = jnp.where(greedy, jnp.argmax(lg, -1),
                                sampled).astype(jnp.int32)
            logits, cache = fam.decode_step(params, nxt, cfg, cache,
                                            lengths=lengths, active=active)
            # only live slots advance; a freed slot re-writes its own cell
            new_lengths = jnp.where(active, lengths + 1, lengths)
            if cache.stats is not None:
                # the counters leave with the tokens (one pull) and start
                # the next tick at zero
                nxt = _Counted(nxt, cache.stats)
                cache = dataclasses.replace(
                    cache, stats=jnp.zeros_like(cache.stats))
            return nxt, logits, cache, new_lengths, next_keys

        def admission_named(name):
            # a function object a name: jit objects of one function share a
            # cache, and a name's compiles are counted from its own; the
            # name is the program's in a profiler's trace (``jit_admit``)
            fn = admission(fam, cfg, self.max_len, self._kv_dtype)
            fn.__name__ = name
            return fn

        def release(lengths, active, row):
            return lengths.at[row].set(0), active.at[row].set(False)

        self._p = self.registry.register_all({
            # a chunk a launch, for a caller that keeps the batch-1 cache
            # (:meth:`_chunked_prefill`) and no logits: they run no head (a
            # ``jit``'s output is computed whether or not the host reads it)
            "prefill": jax.jit(
                lambda p, t, c: fam.prefill(p, t, cfg, c, head=False)),
            "extend": jax.jit(
                lambda p, t, c, l: fam.extend(p, t, cfg, c, lengths=l,
                                              head=False)),
            # the chunk_widen rung's separate jit objects: same functions,
            # compiled lazily at the wide chunk shape on first degraded
            # prefill (a first compile per NAME is free under the
            # CompileWatch contract; pushing a wide chunk through
            # "prefill" would journal perf.recompile)
            "prefill_wide": jax.jit(
                lambda p, t, c: fam.prefill(p, t, cfg, c, head=False)),
            "extend_wide": jax.jit(
                lambda p, t, c, l: fam.extend(p, t, cfg, c, lengths=l,
                                              head=False)),
            # an admission is ONE launch (plain, or continuing a prefix;
            # each again at the rung's wide chunk): the same function.  It
            # donates the pool like the tick does, and the frontier logits
            # it writes one row of (never the prefix: a pooled entry is
            # shared by its forks)
            **{name: jax.jit(admission_named(name), donate_argnums=(1, 3))
               for name in ("admit", "admit_prefix", "admit_wide",
                            "admit_prefix_wide")},
            "release": jax.jit(release),
            # the tick donates the slot pool: its one-row writes land in
            # the pool where it lies and ``self.cache`` is rebound from the
            # result on the call's own line, so nothing holds the old
            # buffers (park/migration read a slot out between ticks and
            # keep only what they read)
            "tick": jax.jit(tick, donate_argnums=(1,)),
        })
        if self.spec:
            self._build_spec_programs(config)

    def _build_spec_programs(self, config: ServingConfig) -> None:
        """The speculative round as three chained device programs (plus
        the draft admission mirrors of prefill/extend/write_slot and the
        pending-token seeder) — each registered, each compiled once.  The
        degradation ladder gets its own program sets: the round trio
        again at ``draft_k2`` (the ``draft_k`` rung — K is compiled into
        the scan/window shapes, so a shrunk round is a different
        program), and the pause/resume pair ``spec_flush``/``spec_reseed``
        (the ``spec_pause`` rung)."""
        fam, cfg = self._fam, self._cfg
        dfam, dcfg = self._dfam, self._dcfg
        top_k, top_p = int(config.top_k), float(config.top_p)
        vocab = cfg.vocab_size
        B = self.slots
        rows = jnp.arange(B)

        def make_round(K):
            """The three chained round programs at proposal depth K (the
            scan length and the [B, K+1] verify window compile K in, so
            the shrunk-``draft_k`` rung is a distinct program set)."""

            def draft_step(dparams, dcache, cur, lengths, keys, greedy,
                           temp, active):
                """K ragged draft decodes per slot from its pending
                token.  Splits each slot's key chain once per round; the
                proposal draws fold the draft domain + step index into
                the round key (independent of the accept stream — see
                ``inference/speculative.py``)."""
                ks = jax.vmap(jax.random.split)(keys)      # [B, 2, 2]
                next_keys, round_keys = ks[:, 0], ks[:, 1]

                def dstep(carry, j):
                    tok, dc, l = carry
                    lg, dc = dfam.decode_step(dparams, tok, dcfg, dc,
                                              lengths=l, active=active)
                    lg = lg[:, :vocab].astype(jnp.float32)
                    f = filter_logits(lg, temp[:, None], top_k=top_k,
                                      top_p=top_p)
                    probs = jax.nn.softmax(f, -1)
                    sampled = jax.vmap(jax.random.categorical)(
                        spec_draft_keys(round_keys, j), f)
                    nxt = jnp.where(greedy, jnp.argmax(lg, -1),
                                    sampled).astype(jnp.int32)
                    return (nxt, dc, l + 1), (nxt, probs)

                (last_d, dcache, _), (drafts, d_probs) = lax.scan(
                    dstep, (cur, dcache, lengths), jnp.arange(K))
                # feed d_K too, so the draft cache covers a full acceptance
                _, dcache = dfam.decode_step(dparams, last_d, dcfg, dcache,
                                             lengths=lengths + K,
                                             active=active)
                return drafts, d_probs, dcache, next_keys, round_keys

            def verify_extend(params, cache, cur, drafts, lengths):
                """ONE ragged target pass scoring every slot's
                ``[cur, d_1..d_K]`` window at its own frontier."""
                window = jnp.concatenate([cur[:, None], drafts.T], axis=1)
                vlg, cache = fam.extend(params, window, cfg, cache,
                                        lengths=lengths)
                return window, vlg[..., :vocab].astype(jnp.float32), cache

            def spec_accept(vlg, drafts, d_probs, round_keys, cur, lengths,
                            greedy, temp, active):
                """Batched accept/rollback: greedy rows take the longest
                prefix agreeing with the target argmax chain (plus the
                target's own next token); sampled rows run the rejection
                rule.  Frontiers advance by the accepted count + 1 — the
                rollback IS the arithmetic (rejected K/V sits beyond the
                new frontier, masked and overwritten next round)."""
                g = jnp.argmax(vlg, -1).astype(jnp.int32)    # [B, K+1]
                agree = (drafts.T == g[:, :K]).astype(jnp.int32)
                a_g = jnp.sum(jnp.cumprod(agree, axis=1), axis=1)
                t_f = filter_logits(vlg, temp[:, None, None], top_k=top_k,
                                    top_p=top_p)
                t_probs = jax.nn.softmax(t_f, -1)            # [B, K+1, V]
                a_s, nxt_s = spec_accept_batch(
                    spec_accept_keys(round_keys), drafts.T,
                    jnp.swapaxes(d_probs, 0, 1), t_probs)
                a = jnp.where(greedy, a_g, a_s)
                nxt = jnp.where(greedy, g[rows, a_g],
                                nxt_s).astype(jnp.int32)
                adv = jnp.where(active, a + 1, 0).astype(jnp.int32)
                return adv, lengths + adv, jnp.where(active, nxt, cur)

            out: Dict[str, Any] = {}
            out["draft_step"] = jax.jit(draft_step)
            out["verify_extend"] = jax.jit(verify_extend)
            out["spec_accept"] = jax.jit(spec_accept)
            return out

        def spec_flush(params, cache, cur, lengths, active):
            """Entering the spec_pause rung: the pending token is
            emitted and cache-written through one plain decode step, so
            ``_last`` lands at the frontier and the plain tick program
            can carry the chain (bitwise the same greedy chain; sampled
            rows keep drawing from the exact target distribution)."""
            logits, cache = fam.decode_step(params, cur, cfg, cache,
                                            lengths=lengths, active=active)
            return cur, logits, cache, jnp.where(active, lengths + 1,
                                                 lengths)

        def spec_reseed(last, keys, greedy, temp):
            """Leaving the pause: re-draw every slot's pending token from
            its frontier logits — the same split/sample a plain tick
            would perform, so resuming is a valid continuation."""
            lg = last[:, :vocab]
            ks = jax.vmap(jax.random.split)(keys)
            next_keys, subkeys = ks[:, 0], ks[:, 1]
            f = filter_logits(lg, temp[:, None], top_k=top_k, top_p=top_p)
            sampled = jax.vmap(jax.random.categorical)(subkeys, f)
            cur = jnp.where(greedy, jnp.argmax(lg, -1),
                            sampled).astype(jnp.int32)
            return cur, next_keys

        def spec_seed(cur, keys, row, vec, g, t):
            """Seed a slot's pending token from its admission logits —
            the same split/sample the non-speculative tick would do, so
            the first emitted token matches it bitwise."""
            k2 = jax.random.split(keys[row])
            lg = vec[:vocab]
            f = filter_logits(lg[None].astype(jnp.float32), t,
                              top_k=top_k, top_p=top_p)
            tok = jnp.where(g, jnp.argmax(lg, -1),
                            jax.random.categorical(k2[1], f[0])
                            ).astype(jnp.int32)
            return cur.at[row].set(tok), keys.at[row].set(k2[0])

        progs: Dict[str, Any] = {}
        # the draft's chunks fill its cache and nothing reads their
        # logits (:meth:`_draft_prefill`): no head
        progs["draft_prefill"] = jax.jit(
            lambda p, t, c: dfam.prefill(p, t, dcfg, c, head=False))
        progs["draft_extend"] = jax.jit(
            lambda p, t, c, l: dfam.extend(p, t, dcfg, c, lengths=l,
                                           head=False))
        progs["draft_write_slot"] = jax.jit(
            lambda c, row, src: dfam.write_slot(c, row, src))
        progs["spec_seed"] = jax.jit(spec_seed)
        progs["spec_flush"] = jax.jit(spec_flush)
        progs["spec_reseed"] = jax.jit(spec_reseed)
        progs.update(make_round(self.draft_k))
        if self.draft_k2 != self.draft_k:
            progs.update({f"{name}_k2": prog for name, prog
                          in make_round(self.draft_k2).items()})
        self._p_spec = self.registry.register_all(progs)
        self._p.update(self._p_spec)

    def compile_counts(self) -> Dict[str, int]:
        """Cumulative compiles per program — the no-recompile contract is
        ``all(v <= 1)`` after warmup, asserted by the e2e tests (and a
        re-registered/un-cached program keeps counting: see
        ``CompiledProgramRegistry``)."""
        return self.registry.counts()

    # ------------------------------------------------- degradation ladder

    def set_chunk_wide(self, wide: bool) -> None:
        """Engage/release the ``chunk_widen`` rung: subsequent prefills
        run ``chunk_wide``-token chunks through the wide program pair.
        Admission-path only — a prefill in flight finishes at the width
        it started."""
        self._wide = bool(wide) and self.chunk_wide != self.chunk

    def set_spec_level(self, level: int) -> None:
        """Engage/release the speculative rungs: 0 = full ``draft_k``
        rounds, 1 = shrunk ``draft_k2`` rounds, 2 = paused (plain
        one-token ticks).  No-op on a non-speculative batcher."""
        if level not in (0, 1, 2):
            raise ValueError(f"spec level must be 0, 1, or 2, got {level}")
        if self.spec:
            self.spec_level = int(level)

    @property
    def round_draft_k(self) -> int:
        """Proposals per round at the current ladder level (0 = plain
        one-token ticks: speculation off or paused)."""
        if not self.spec or self.spec_level >= 2:
            return 0
        return self.draft_k2 if self.spec_level == 1 else self.draft_k

    def prewarm(self) -> None:
        """Compile every program a storm can reach BEFORE traffic
        arrives: the admission with and without a prefix and the chunk
        pair that builds one, each at both chunk widths, the tick at every
        speculative ladder level, and release.  The degradation ladder
        exists to shed work under pressure — a rung whose first engage
        pays an XLA compile would add seconds of stall at the worst
        possible moment, so ``serving.warm_start`` front-loads them all
        here.  Runs a throwaway prompt through slot 0 and releases it;
        call before any real admission."""
        key = jax.random.PRNGKey(0)

        def admissions(C):
            # the prompt and the prefix built under it both cross one
            # chunk boundary: prefill and extend run, launch by launch,
            # and the prefix's continuation
            prompt = np.zeros((min(C + 2, self.max_len),), np.int32)
            self.admit(0, prompt, key, True, 1.0)
            if prompt.shape[0] > 1 and not self.unsupported("prefix"):
                self.admit(0, prompt, key, True, 1.0,
                           prefix=self.build_prefix(prompt[:-1]))

        admissions(self.chunk)
        self.tick()
        if self.spec:
            for level in (1, 2, 0):   # shrunk round, pause flush, resume
                self.set_spec_level(level)
                self.tick()
        self.release(0)
        if self.chunk_wide != self.chunk:
            self.set_chunk_wide(True)
            admissions(self.chunk_wide)
            self.set_chunk_wide(False)
            self.release(0)

    # ------------------------------------------------------------- prefill

    def _chunked_prefill(self, tokens: np.ndarray,
                         start_cache=None, start_len: int = 0):
        """Run ``tokens`` [S] through fixed-width chunks, a launch each,
        starting at ``start_len`` of a batch-1 slot-geometry cache (fresh
        unless continuing one) and hand the cache BACK: ``(cache,
        frontier)``.  For what keeps a batch-1 cache on the host's side of
        the API (:meth:`build_prefix`; the fleet's prefill worker, which
        fires its faults between chunks); an admission runs the same
        chunks inside its one program.  Chunk padding sits beyond the
        frontier, masked by per-row visibility and overwritten as decode
        advances."""
        fam, cfg = self._fam, self._cfg
        C, sfx = self._chunk_width()
        S = int(tokens.shape[0])
        n_chunks = -(-S // C)
        with self.tracer.span(SpanName.SERVE_PREFILL, tokens=S,
                              start=start_len, chunk=C, padded=n_chunks * C,
                              chunks=n_chunks, passes=n_chunks, wide=0,
                              narrow=0, in_place=0):
            chunks = self._padded_chunks(tokens, C, n_chunks)
            if start_cache is not None:
                cache = start_cache
            else:
                with self.tracer.span(SpanName.SERVE_CACHE_ALLOC,
                                      bytes=self._row_cache_bytes):
                    cache = fam.init_cache(cfg, 1, self.max_len,
                                           kv_dtype=self._kv_dtype)
            params = self._engine.params
            for i, ch in enumerate(chunks):
                pos = start_len + i * C
                program = ("extend" if pos else "prefill") + sfx
                with self.tracer.span(SpanName.SERVE_PREFILL_CHUNK, index=i,
                                      pos=pos, program=program):
                    _, cache = self._launch(
                        program, params, jnp.asarray(ch[None]), cache,
                        *((jnp.asarray([pos], jnp.int32),) if pos else ()))
        return cache, start_len + S

    def _chunk_width(self) -> Tuple[int, str]:
        """``(C, suffix)``: the chunk width an admission starting now runs
        at, and what its programs' names end in (``"_wide"`` under the
        ``chunk_widen`` rung)."""
        return (self.chunk_wide, "_wide") if self._wide else (self.chunk, "")

    def _launch(self, program: str, *args):
        """Launch a registered program on an admission's behalf: counted
        in ``admit_launches``."""
        self.admit_launches += 1
        return self._p[program](*args)

    @staticmethod
    def _padded_chunks(tokens: np.ndarray, C: int, n_chunks: int):
        """``tokens`` [S] as ``[n_chunks, C]`` int32, zeros past ``S``."""
        out = np.zeros((n_chunks, C), np.int32)
        out.reshape(-1)[:tokens.shape[0]] = tokens
        return out

    def build_prefix(self, tokens: np.ndarray) -> PrefixEntry:
        """Prefill a shared prefix once; forks ride it zero-copy."""
        self.refuse("prefix")
        cache, frontier = self._chunked_prefill(np.asarray(tokens))
        return PrefixEntry(cache=cache, length=frontier)

    # ----------------------------------------------------------- admission

    def admit(self, row: int, tokens: np.ndarray, key, greedy: bool,
              temperature: float,
              prefix: Optional[PrefixEntry] = None,
              fold: Optional[int] = None) -> int:
        """Prefill ``tokens`` and land them in slot ``row``, in ONE
        program launch fed by one hand-over of host arrays (the padded
        prompt and ``meta``); returns the row's frontier (= prompt
        length).  With ``prefix``, only the remainder past
        ``prefix.length`` prefills — the prefix K/V is the pooled cache,
        shared zero-copy, the loop's starting row.  With ``fold``, the
        row samples from ``jax.random.fold_in(key, fold)``, folded inside
        the program: a caller that derives a key per request hands over
        its base key and spares the launches of an eager fold."""
        if int(tokens.shape[0]) > self.max_len:
            raise ValueError(
                f"prompt of {int(tokens.shape[0])} tokens overflows the "
                f"{self.max_len}-token slot")
        start = 0
        if prefix is not None:
            if prefix.length >= tokens.shape[0]:
                raise ValueError(
                    f"prefix ({prefix.length} tokens) must be shorter than "
                    f"the prompt ({tokens.shape[0]})")
            start = int(prefix.length)
        new = np.asarray(tokens[start:], np.int32)
        S = int(new.shape[0])
        C, sfx = self._chunk_width()
        n_chunks = -(-S // C)
        program = ("admit" if prefix is None else "admit_prefix") + sfx
        if self._last is None:
            lg = self._logits_row()
            self._last = jnp.asarray(       # host-made, as the vectors
                np.zeros((self.slots,) + lg.shape, lg.dtype))
        meta = np.array([row, start, S, bool(greedy),
                         np.float32(temperature).view(np.int32),
                         fold is not None,
                         np.uint32(fold or 0).view(np.int32)], np.int32)
        # a fresh row's first chunk is the family's ``prefill`` where the
        # ladder stops at the chunk and the row is a cache of its own
        widths = pass_widths(C, self.max_len)
        in_place = prefix is None and self._in_place
        passes, wide, narrow = ladder_passes(
            S, C, widths,
            first=0 if prefix is not None or widths[-1] < C or in_place
            else 1)
        # what the launch computes, for its host span and its device span:
        # ``padded`` the rows its passes run, a narrow last pass's for the
        # last chunk's; ``in_place``: on the slot's own row of the pool
        work = dict(tokens=S, chunk=C,
                    padded=n_chunks * C - (C - narrow if narrow else 0),
                    passes=passes, wide=wide, narrow=narrow,
                    in_place=int(in_place))
        if self._fam.chunk_form is not None:
            # which of its forms the program's passes take at this chunk
            work["form"] = self._fam.chunk_form(self._cfg, C)
        with self.tracer.span(SpanName.SERVE_PREFILL, start=start,
                              chunks=n_chunks, **work):
            (self.cache, self.lengths, self._last, self.keys, self.greedy,
             self.temp, self.active, vec) = self._launch(
                program,
                self._engine.params, self.cache, self.lengths, self._last,
                self.keys, self.greedy, self.temp, self.active,
                self._padded_chunks(new, C, -(-self.max_len // C)), meta,
                key, *(() if prefix is None else (prefix.cache,)))
            # ``serve.device``: ``vec``, the launch's one logits row, is an
            # output that nothing later donates
            self.registry.watch(program, vec, slot=row, **work)
        if self.spec:
            # lockstep draft admission: the draft prefills the FULL
            # prompt (prefix/readmit shortcuts spare only target work
            # — the draft is small, that is its whole point) and the
            # slot's pending token is seeded from the admission logits
            row_dev = jnp.asarray(row, jnp.int32)
            with self.tracer.span(SpanName.SERVE_SLOT_WRITE, slot=row):
                self.draft_cache = self._launch(
                    "draft_write_slot", self.draft_cache, row_dev,
                    self._draft_prefill(np.asarray(tokens)))
                self.cur, self.keys = self._launch(
                    "spec_seed", self.cur, self.keys, row_dev, vec,
                    jnp.asarray(bool(greedy)),
                    jnp.asarray(float(temperature), jnp.float32))
        return start + S

    def _logits_row(self) -> jax.ShapeDtypeStruct:
        """One position's logits (the family's padded vocabulary, in the
        type its head returns), asked of the family's own embedding and
        head: traced, never run, and without the layers between them (a
        scan hands a position's hidden state on in the type it took it)."""
        fam, cfg = self._fam, self._cfg
        lg = jax.eval_shape(
            lambda p, t: fam.logits(
                p, fam.embed(p, t, cfg, positions=jnp.arange(t.shape[1])),
                cfg),
            self._engine.params, jax.ShapeDtypeStruct((1, 1), jnp.int32))
        return jax.ShapeDtypeStruct(lg.shape[2:], lg.dtype)

    def _draft_prefill(self, tokens: np.ndarray):
        """Chunked prefill of a prompt through the draft's fixed-width
        programs into a fresh batch-1 slot-geometry draft cache."""
        C = self.chunk
        cache = self._dfam.init_cache(self._dcfg, 1, self.max_len)
        chunks = self._padded_chunks(tokens, C, -(-int(tokens.shape[0]) // C))
        for i, ch in enumerate(chunks):
            dev = jnp.asarray(ch[None])
            if i == 0:
                _, cache = self._launch("draft_prefill", self._dparams, dev,
                                        cache)
            else:
                _, cache = self._launch(
                    "draft_extend", self._dparams, dev, cache,
                    jnp.asarray([i * C], jnp.int32))
        return cache

    def release(self, row: int) -> None:
        """Retire a slot: it stops advancing (its tick writes re-hit one
        dead cell, and the decode kernel neither steps nor streams for it)
        until the next admission overwrites the whole row."""
        self.lengths, self.active = self._p["release"](
            self.lengths, self.active, jnp.asarray(row, jnp.int32))

    # ---------------------------------------------------------------- tick

    def sweep_blocks(self, frontiers) -> Tuple[int, int]:
        """``(live, grid)`` cache blocks of one plain tick whose live rows
        stand at ``frontiers`` (host ints: prompt length + tokens out):
        what the decode kernel steps over all layers, and what the whole
        slot grid holds.  Counted from lengths, never read off the
        device."""
        return self._sweep.block_counts(frontiers, self.slots)

    def sweep_by_kind(self, frontiers) -> Dict[str, Tuple[int, int, int]]:
        """``pool -> (live, streamed, calls)`` of the same tick
        (``SweepPlan.by_kind``): the cached tokens its rows' queries see
        over a pool's layers, what the decode kernel's copies move for them
        (``sweep_token_counts``) and the sweep kernel's calls, for the pool
        of whole rows (``"full"``) and, for a family with rings, of those
        (``"window"``)."""
        return self._sweep.by_kind(frontiers)

    @property
    def sweep_plan(self):
        """The plan those counts follow (``decode_attention.SweepPlan``)."""
        return self._sweep

    @hot_path
    def launch(self):
        """Dispatch one decode step for every slot and return its tokens
        UN-PULLED, still on the device: a ``[B]`` int32 array (junk in
        freed slots: the tick hands the kernel its ``active`` mask, so a
        freed slot's attention is neither stepped nor streamed, only its
        one cell re-written), or, with speculation enabled and not paused
        by the ladder, one draft/verify ROUND's ``(window [B, k+1], counts
        [B])``.  Everything the next step reads (``cache``, ``lengths``,
        ``_last``, ``keys``, ``cur``) is rebound here to this step's
        results, so the next ``launch``, ``admit`` or ``release`` queues
        behind it on the device and the host need not :meth:`pull` first:
        the gateway keeps one step in flight that way.  The ``_paused`` /
        ``spec_level`` transitions happen here, at launch."""
        if self._last is None:
            raise RuntimeError("tick() before any admission")
        if not self.spec:
            return self._plain_launch()
        if self.spec_level >= 2:
            return self._paused_launch()
        if self._paused:
            # leaving the pause: re-draw every pending token from the
            # frontier logits before the next round
            self.cur, self.keys = self._p["spec_reseed"](
                self._last, self.keys, self.greedy, self.temp)
            self._paused = False
        return self._spec_launch()

    @hot_path
    def pull(self, pending):
        """The host's wait for what :meth:`launch` returned: ``[B]``
        tokens, or a round's ``(window, counts)`` — row ``b`` emitted
        ``window[b, :counts[b]]`` (0 in freed slots).  Callers dispatch on
        the TYPE (tuple = speculative round), not on config — the
        spec_pause rung switches a speculative gateway to plain ``[B]``
        ticks at runtime."""
        self.registry.note_host_sync("serving.tick")
        # the emitted tokens ARE the tick's output boundary
        with self.tracer.span(SpanName.SERVE_PULL):
            # dslint: disable=host-sync-in-hot-path — one d2h pull per tick
            got = jax.device_get(pending)
        if isinstance(got, _Counted):
            # a family's device counters came with the tokens
            self.device_counts = got.counts.astype(np.int64) + (
                0 if self.device_counts is None else self.device_counts)
            return got.tokens
        return got

    @hot_path
    def tick(self):
        """One continuous-batching decode step, synchronous: launch, then
        pull, in one ``serve.tick`` span.  For callers that want the
        tokens now (``probe_logits``, the fleet's decode worker, tests);
        the gateway's loop calls the halves itself."""
        with self.tracer.span(SpanName.SERVE_TICK):
            return self.pull(self.launch())

    def _plain_launch(self):
        nxt, self._last, self.cache, self.lengths, self.keys = \
            self._p["tick"](
                self._engine.params, self.cache, self.lengths, self._last,
                self.keys, self.greedy, self.temp, self.active)
        # a tick's tokens are pulled, never donated: safe to wait on
        self.registry.watch("tick", nxt)
        return nxt

    def _spec_launch(self):
        """One speculative round for every slot: draft scan → ragged
        verify extend → batched accept/rollback, three chained compiled
        programs and still one host sync, at the pull.  The next round
        needs only ``cur``, ``lengths`` and ``keys``, all on the device.
        At ladder level 1 the round runs the ``draft_k2`` program set
        instead."""
        shrunk = self.spec_level == 1 and self.draft_k2 != self.draft_k
        sfx = "_k2" if shrunk else ""
        with self.tracer.span(SpanName.SERVE_SPEC,
                              draft_k=self.round_draft_k):
            drafts, d_probs, self.draft_cache, next_keys, round_keys \
                = self._p["draft_step" + sfx](
                    self._dparams, self.draft_cache, self.cur,
                    self.lengths, self.keys, self.greedy, self.temp,
                    self.active)
            window, vlg, self.cache = self._p["verify_extend" + sfx](
                self._engine.params, self.cache, self.cur, drafts,
                self.lengths)
            adv, self.lengths, self.cur = self._p["spec_accept" + sfx](
                vlg, drafts, d_probs, round_keys, self.cur,
                self.lengths, self.greedy, self.temp, self.active)
            self.keys = next_keys
        # the round's device time ends with its last program's counts
        self.registry.watch("spec_accept" + sfx, adv,
                            draft_k=self.round_draft_k)
        return window, adv

    def _paused_launch(self):
        """One-token ticking while the spec_pause rung is engaged.  The
        first paused tick FLUSHES the pending token (one decode step
        writes its K/V and leaves ``_last`` at the frontier); later ones
        run the plain tick program.  The draft cache is not advanced
        while paused — rows alive across the pause carry a hole in their
        draft history that only degrades proposal quality after resume
        (the accept rule stays exact); rows admitted later prefill a
        fresh draft cache and are unaffected."""
        if self._paused:
            return self._plain_launch()
        nxt, self._last, self.cache, self.lengths = self._p["spec_flush"](
            self._engine.params, self.cache, self.cur, self.lengths,
            self.active)
        self._paused = True
        self.registry.watch("spec_flush", nxt)
        return nxt
