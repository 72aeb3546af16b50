"""Inference engine: jitted prefill + decode over a TP-sharded GPT.

Counterpart of the reference's ``InferenceEngine`` (``inference/engine.py:32``):
dtype conversion (:447), tensor-parallel weight sharding (kernel-injection
slicing, ``module_inject/replace_module.py:18``), CUDA-graph capture (:464)
→ here, jit compilation of whole prefill/decode programs; ``forward`` (:505)
and a ``generate`` loop.

TP on TPU is declarative: qkv/mlp weights carry head/ffn-dim shardings over
the 'model' mesh axis and XLA inserts the per-layer all-reduce the
reference's ``LinearAllreduce`` issues by hand.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import gpt
from ..parallel.mesh import MODEL_AXIS, MeshManager, get_mesh_manager
from ..utils.compile_watch import CompiledProgramRegistry
from ..utils.logging import logger
from .bucketing import bucket_max_new_tokens, tile_cache_len as _tile_cache_len
from .config import DeepSpeedInferenceConfig

PyTree = Any


def _serving_dtype(config: DeepSpeedInferenceConfig):
    """(compute dtype, weight_int8): dtype="int8" means weight-only int8
    serving (reference pt_binding.cpp int8 gemm paths) — weights stored
    int8 + grouped scales, activations/compute bf16 on the MXU."""
    dtype = config.jnp_dtype
    if dtype == jnp.int8:
        return jnp.bfloat16, True
    return dtype, False


def _validate_tp(config: DeepSpeedInferenceConfig, mesh_manager) -> bool:
    """Shared TP config/mesh validation; returns whether to shard."""
    mesh_tp = (mesh_manager.mesh.shape.get(MODEL_AXIS, 1)
               if mesh_manager is not None else 1)
    want_tp = config.tp.enabled and config.tp_size > 1
    if want_tp and mesh_tp <= 1:
        raise ValueError(
            f"tensor_parallel.tp_size={config.tp_size} requested but the "
            f"mesh has no model axis (model={mesh_tp}); initialize a "
            "mesh with tp first (ParallelDims(tp=...))")
    if want_tp and mesh_tp != config.tp_size:
        raise ValueError(
            f"tensor_parallel.tp_size={config.tp_size} does not match "
            f"the mesh's model axis ({mesh_tp})")
    if mesh_tp > 1 and not want_tp:
        logger.warning(
            f"mesh has model={mesh_tp} but tensor_parallel disabled in "
            "the inference config; serving replicated (unsharded)")
    return want_tp


def _shard_and_quantize(params: PyTree, logical_axes, mesh_manager,
                        want_tp: bool, weight_int8: bool,
                        int8_compute: bool = False) -> PyTree:
    """Shared TP sharding (the reference's ReplaceWithTensorSlicing, done
    declaratively) + int8 conversion (weight-only dequant serving, or the
    true int8-dot compute path when ``int8_compute``)."""
    if want_tp:
        from ..models.partitioning import TP_RULES, tree_shardings
        mesh = mesh_manager.mesh
        shardings = tree_shardings(logical_axes, mesh, TP_RULES)
        params = jax.tree_util.tree_map(jax.device_put, params, shardings)
        logger.info(f"[inference] TP sharding over model axis "
                    f"({mesh.shape[MODEL_AXIS]} ways)")
    if int8_compute:
        from .quantization import quantize_params_int8_compute
        params, n_q = quantize_params_int8_compute(params)
        logger.info(f"[inference] TRUE int8 compute serving: {n_q} weights "
                    "as int8 codes + per-output-channel scales "
                    "(int8xint8->int32 gemms)")
    elif weight_int8:
        from .quantization import quantize_params_int8
        params, n_q = quantize_params_int8(params)
        logger.info(f"[inference] int8 weight-only serving: {n_q} "
                    "weights stored as int8 codes + per-vector scales")
    return params


class InferenceEngine:
    """Wraps (config, params) with jitted prefill/decode/generate."""

    def __init__(self, model_config: gpt.GPTConfig, params: PyTree,
                 config: DeepSpeedInferenceConfig,
                 mesh_manager: Optional[MeshManager] = None):
        self.mesh_manager = mesh_manager or get_mesh_manager(optional=True)
        self._config = config
        dtype, self._weight_int8 = _serving_dtype(config)
        self._int8_compute = bool(config.quantization.int8_compute)
        if self._int8_compute and not self._weight_int8:
            raise ValueError(
                'quant.int8_compute requires dtype="int8" (got '
                f"{config.dtype!r})")
        self.model_config = dataclasses.replace(model_config, dtype=dtype)
        self.params = jax.tree_util.tree_map(
            lambda p: p.astype(dtype) if jnp.issubdtype(p.dtype, jnp.floating)
            else p, params)
        want_tp = _validate_tp(config, self.mesh_manager)
        # model-family dispatch: dense GPT vs MoE (reference MoE inference,
        # ops/transformer/inference/moe_inference.py + engine.py:190 expert
        # groups — here the expert mesh axis shards the expert stacks)
        from ..models import cache_family
        cfg = self.model_config
        self._kv_dtype = ("int8" if config.kv_cache_dtype == "int8"
                          else None)
        self._family = fam = cache_family(cfg)
        self._apply_fn = lambda p, t: fam.apply(p, t, cfg)
        self._logical_axes = fam.logical_axes(cfg)
        self.params = _shard_and_quantize(
            self.params, self._logical_axes, self.mesh_manager, want_tp,
            self._weight_int8, int8_compute=self._int8_compute)
        #: every compiled program this engine drives, by name — the
        #: compile-discipline gate (utils/compile_watch.py) watches it
        self.compile_registry = CompiledProgramRegistry("inference")
        self._forward_jit = self.compile_registry.register(
            "forward", jax.jit(self._apply_fn))
        self._generate_cache: Dict[Tuple, Any] = {}
        # default sampling keys come from a fold-in sequence, not a fixed
        # PRNGKey(0): two sampled generate() calls must not be bitwise
        # identical unless the caller pins the key
        self._key_seq = 0

    def _next_key(self) -> jax.Array:
        key = jax.random.fold_in(jax.random.PRNGKey(0), self._key_seq)
        self._key_seq += 1
        return key

    # -------------------------------------------------------------- forward

    def forward(self, tokens) -> jnp.ndarray:
        """Full-sequence logits (HF-style __call__). tokens [B, S] int32."""
        return self._forward_jit(self.params, jnp.asarray(tokens, jnp.int32))

    __call__ = forward

    # ------------------------------------------------------------- generate

    def _build_generate(self, max_len: int, n_bucket: int, greedy: bool,
                        eos: Optional[int], top_k: int, top_p: float):
        """The raw generate loop for one ``(max_len, n_bucket, ...)``
        shape class; the caller jits it ONCE into ``_generate_cache``
        (jit caches key on the wrapped function object — a fresh jit per
        call here would recompile every request).  ``n_bucket`` is the
        power-of-two reply-budget bucket; the TRUE budget arrives as the
        traced ``n_new`` operand, so nearby budgets share one program and
        the loop just stops early."""
        cfg = self.model_config

        fam = self._family

        def pick(lg, key, temperature):
            lg = lg[:, :cfg.vocab_size]
            if greedy:
                return jnp.argmax(lg, axis=-1).astype(jnp.int32)
            from .sampling import filter_logits
            lg = filter_logits(lg, temperature, top_k=top_k, top_p=top_p)
            return jax.random.categorical(key, lg).astype(jnp.int32)

        kv_dtype = self._kv_dtype

        def run(params, tokens, prompt_len, key, temperature, n_new,
                is_ragged):
            B, S = tokens.shape
            cache = (fam.init_cache(cfg, B, max_len, kv_dtype=kv_dtype)
                     if kv_dtype is not None else
                     fam.init_cache(cfg, B, max_len))
            # where each row's prompt ends inside the padded width: a family
            # that keeps state per slot must not let a recurrence take the
            # padding (attention masks it at read time)
            logits, cache = fam.prefill(params, tokens, cfg, cache,
                                        valid=prompt_len)
            # logits at the last *prompt* token predict the first new token
            last = logits[jnp.arange(B), prompt_len - 1]
            out = jnp.full((B, n_bucket), eos if eos is not None else 0,
                           jnp.int32)
            done0 = jnp.zeros((B,), bool)

            def cond(st):
                i, _, _, _, _, _, done = st
                return jnp.logical_and(i < n_new, ~jnp.all(done))

            def body(st):
                i, out, last, cache, lengths, key, done = st
                key, sub = jax.random.split(key)
                nxt = pick(last, sub, temperature)
                if eos is not None:
                    # rows that already finished keep emitting eos
                    nxt = jnp.where(done, jnp.int32(eos), nxt)
                out = out.at[:, i].set(nxt)
                if eos is not None:
                    done = jnp.logical_or(done, nxt == eos)
                if is_ragged:
                    logits, cache = fam.decode_step(params, nxt, cfg, cache,
                                                    lengths=lengths)
                else:
                    logits, cache = fam.decode_step(params, nxt, cfg, cache)
                return i + 1, out, logits, cache, lengths + 1, key, done

            _, out, _, cache, _, _, _ = lax.while_loop(
                cond, body,
                (jnp.int32(0), out, last, cache, prompt_len, key, done0))
            return out

        return run

    def generate(self, tokens, max_new_tokens: int = 32,
                 do_sample: bool = False, temperature: float = 1.0,
                 eos_token_id: Optional[int] = None,
                 top_k: int = 0, top_p: float = 1.0,
                 prompt_lens=None,
                 key: Optional[jax.Array] = None) -> jnp.ndarray:
        """Autoregressive generation; the whole loop is one XLA program.

        tokens: [B, S] prompt.  Unequal-length prompts: RIGHT-pad to S and
        pass the true lengths as ``prompt_lens`` [B] — each row continues
        from its own last real token, with per-row visibility masking in
        the decode kernel (all served families, MoE included — dropless
        gating keeps ragged rows' routing independent).
        ``eos_token_id`` stops early once every row has emitted it
        (finished rows keep emitting eos); ``top_k``/``top_p`` shape the
        sampling distribution.  Returns [B, max_new_tokens].
        """
        tokens = jnp.asarray(tokens, jnp.int32)
        B, S = tokens.shape
        is_ragged = prompt_lens is not None
        if is_ragged:
            lens_np = np.asarray(prompt_lens)
            if lens_np.shape != (B,):
                raise ValueError(f"prompt_lens shape {lens_np.shape} != ({B},)")
            if (lens_np < 1).any() or (lens_np > S).any():
                raise ValueError(
                    f"prompt_lens must be in [1, {S}] (the padded width); "
                    f"got {lens_np.tolist()} — out-of-range lengths would "
                    "silently condition on the wrong tokens")
        if S + max_new_tokens > self.model_config.max_seq_len:
            raise ValueError(
                f"prompt ({S}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"max_seq_len ({self.model_config.max_seq_len}); decoding "
                "past it would silently overwrite the last cache slot")
        # bucket the reply budget: budgets of 5, 6, and 8 share one
        # program (the true budget is a traced operand of the loop), and
        # the cache length tiles off the BUCKET so geometry shares too
        n_bucket = bucket_max_new_tokens(max_new_tokens)
        max_len = _tile_cache_len(S + n_bucket,
                                  self.model_config.max_seq_len)
        sig = (max_len, n_bucket, not do_sample, eos_token_id,
               top_k, top_p)
        if sig not in self._generate_cache:
            self._generate_cache[sig] = self.compile_registry.register(
                f"generate:{sig}",
                jax.jit(self._build_generate(
                    max_len, n_bucket, greedy=not do_sample,
                    eos=eos_token_id, top_k=top_k, top_p=top_p),
                    static_argnums=(6,)))
        key = key if key is not None else self._next_key()
        lens = jnp.asarray(prompt_lens, jnp.int32) if is_ragged \
            else jnp.full((B,), S, jnp.int32)
        out = self._generate_cache[sig](
            self.params, tokens, lens,
            key, jnp.asarray(temperature, jnp.float32),
            jnp.asarray(max_new_tokens, jnp.int32), is_ragged)
        return out[:, :max_new_tokens]

    # ---------------------------------------------------------- speculative

    def generate_speculative(self, tokens, draft, max_new_tokens: int = 32,
                             draft_k: int = 7, temperature: float = 0.0,
                             top_k: int = 0, top_p: float = 1.0,
                             key=None):
        """Generation with draft-model speculation
        (``inference/speculative.py``): fewer target forwards, exact
        output semantics.  ``temperature=0`` (default) is greedy —
        bit-identical tokens to ``generate(greedy)``; ``temperature>0``
        is speculative SAMPLING (rejection rule) — tokens distributed
        exactly as target sampling at that temperature, seeded by
        ``key``.  ``draft`` is a ``(GPTConfig, params)`` tuple or another
        :class:`InferenceEngine` over the same vocabulary.  The TARGET
        may be dense GPT or MoE (the verify pass rides each family's
        chunked ``extend``); the draft must be dense — its whole point
        is being small.  Greedy speculation is BATCHED: ``tokens`` may be
        [B, S]; rows accept different draft counts per round, so their
        frontiers diverge and the draft/verify steps run ragged
        (sampling and MoE targets serve batch 1).  Returns
        ``(tokens [B, N], n_target_forwards)``.  ``draft_k + 1`` should
        be a multiple of 8 so the verify pass rides the chunk kernel
        (default 7).
        """
        from ..models.gpt_moe import GPTMoEConfig
        from .speculative import speculative_generate
        if temperature <= 0 and (top_k > 0 or top_p < 1.0):
            raise ValueError(
                "top_k/top_p only apply to speculative SAMPLING — set "
                "temperature > 0 (temperature=0 is greedy and would "
                "silently ignore the filters)")
        if isinstance(draft, InferenceEngine):
            if draft._family.unsupported.get("draft"):
                raise NotImplementedError(
                    "the draft must be a dense GPT-family engine")
            dcfg, dparams = draft.model_config, draft.params
        else:
            dcfg, dparams = draft
        if not isinstance(dcfg, gpt.GPTConfig) or \
                isinstance(dcfg, GPTMoEConfig):
            raise TypeError(
                "draft must be (gpt.GPTConfig, params) or a dense "
                f"GPT-family InferenceEngine (got config {type(dcfg)})")
        tokens = jnp.asarray(tokens, jnp.int32)
        # the budget is baked into the draft/verify round structure
        # (rounds accept variable token counts); bucketing it would run
        # dead verify forwards, so speculative programs are per-budget:
        # dslint: disable=unbucketed-static-arg — deliberate per-budget jit
        sig = ("spec", tokens.shape, int(max_new_tokens), int(draft_k),
               float(temperature), int(top_k), float(top_p),
               str(dcfg))  # draft ARCH baked in
        if sig not in self._generate_cache:
            cfg, kv = self.model_config, self._kv_dtype

            def run(tp, dp, t, k):
                return speculative_generate(tp, cfg, dp, dcfg, t,
                                            max_new_tokens, draft_k,
                                            kv_dtype=kv,
                                            temperature=temperature,
                                            top_k=top_k, top_p=top_p, key=k)

            self._generate_cache[sig] = self.compile_registry.register(
                f"speculative:{sig}", jax.jit(run))
        key = key if key is not None else self._next_key()
        return self._generate_cache[sig](self.params, dparams, tokens, key)

    # -------------------------------------------------------------- session

    def start_session(self, batch: int = 1,
                      max_len: Optional[int] = None) -> "InferenceSession":
        """A stateful multi-turn session over one persistent KV cache:
        ``append`` prefills/extends with each turn's tokens (chunked
        prefill — the conversation is never re-prefilled), ``generate``
        decodes a reply that stays in the cache.  Serves every family —
        MoE sessions ride their family's ``extend`` the same way.

        ``max_len`` is bucketed to a power of two (clamped to the model
        context), so sessions with nearby budgets share one cache
        geometry — and therefore every compiled prefill/extend/decode
        program.
        """
        from .bucketing import bucket_cache_len
        cap = self.model_config.max_seq_len
        return InferenceSession(self, batch,
                                bucket_cache_len(max_len or cap, cap))

    # -------------------------------------------------------------- serving

    def serve(self, config=None, journal=None, autostart: bool = True,
              tracer=None, draft=None):
        """A continuous-batching serving gateway over this engine: an
        async request scheduler packing heterogeneous prompts into one
        fixed-geometry ragged-decode slot batch (``serving/``).  ``config``
        is a :class:`~deepspeed_tpu.serving.ServingConfig` or its dict;
        ``journal`` an optional supervision ``EventJournal``; ``tracer``
        an optional telemetry ``Tracer`` recording the serve.* spans.
        ``draft`` (with ``serving.speculative.enabled``) is the proposal
        model for speculative tick rounds — a ``(gpt.GPTConfig, params)``
        tuple or a dense GPT-family :class:`InferenceEngine` sharing this
        engine's vocabulary; see ``docs/serving.md`` "Speculative tick"."""
        from ..serving import ServingGateway
        return ServingGateway(self, config=config, journal=journal,
                              autostart=autostart, tracer=tracer,
                              draft=draft)

    def _session_programs(self):
        """Jitted prefill/extend/decode shared by ALL of this engine's
        sessions (jit caches key on the wrapped function object, so fresh
        per-session lambdas would recompile per conversation)."""
        if not hasattr(self, "_session_progs"):
            fam = self._family
            cfg = self.model_config
            reg = self.compile_registry
            self._session_progs = {
                **reg.register_all({
                    "prefill": jax.jit(
                        lambda p, t, c: fam.prefill(p, t, cfg, c)),
                    "extend": jax.jit(
                        lambda p, t, c: fam.extend(p, t, cfg, c)),
                    "decode": jax.jit(
                        lambda p, t, c: fam.decode_step(p, t, cfg, c)),
                }, prefix="session."),
                "reply": {},   # fused reply loops, keyed by
                               # (n_tokens, sample, top_k, top_p)
            }
        return self._session_progs

    def compile_counts(self) -> Dict[str, int]:
        """jit-cache entries per registered program — the no-recompile
        contract is ``all(v <= 1)`` for shape-stable programs (same
        contract ``serving.SlotBatcher.compile_counts`` exposes)."""
        return self.compile_registry.counts()

    # ----------------------------------------------------------- checkpoint

    def save_16bit_model(self, path: str) -> None:
        _save_16bit(self.params, self.model_config.dtype, path)


class InferenceSession:
    """One conversation's cache + the jitted programs that advance it.

    The reference keeps no session state (each ``forward`` re-consumes the
    whole history); here the KV cache persists across turns, so each turn
    costs only its own tokens — with ``kv_cache_dtype: "int8"`` at half
    the cache bytes.
    """

    def __init__(self, engine: InferenceEngine, batch: int, max_len: int):
        fam = engine._family
        cfg = engine.model_config
        self._engine = engine
        self._progs = engine._session_programs()
        max_len = _tile_cache_len(max_len, cfg.max_seq_len)
        self.cache = fam.init_cache(cfg, batch, max_len,
                                    kv_dtype=engine._kv_dtype)
        self._last_logits = None
        self._key_seq = 0

    @property
    def length(self) -> int:
        return int(jax.device_get(self.cache.length))

    def _check_room(self, n: int) -> None:
        if self.length + n > self.cache.max_len:
            raise ValueError(
                f"session cache full: {self.length} + {n} tokens exceeds "
                f"max_len {self.cache.max_len}")

    def fork(self) -> "InferenceSession":
        """A new session continuing from this one's exact state (prefix
        caching): process a shared system prompt ONCE, then fork one
        session per conversation.  ZERO-copy — jax arrays are immutable
        and no inference program donates its cache buffers, so parent
        and forks share the prefix K/V until each one's next
        append/generate produces its own updated tree.  Compiled
        programs stay shared too."""
        new = object.__new__(InferenceSession)
        new._engine = self._engine
        new._progs = self._progs
        new.cache = self.cache
        new._last_logits = self._last_logits
        new._key_seq = self._key_seq
        return new

    def append(self, tokens) -> jnp.ndarray:
        """Feed one turn's tokens [B, S]; returns its logits
        [B, S, padded_vocab] (fp32)."""
        tokens = jnp.asarray(tokens, jnp.int32)
        self._check_room(tokens.shape[1])
        run = (self._progs["prefill"] if self.length == 0
               else self._progs["extend"])
        logits, self.cache = run(self._engine.params, tokens, self.cache)
        self._last_logits = logits[:, -1]
        return logits

    def _reply_prog(self, n_bucket: int, sample: bool, top_k: int,
                    top_p: float):
        """One fused reply loop (lax.scan) per BUCKET signature: a
        128-token reply is ONE dispatch, not 256 — and replies of 5, 6,
        and 8 tokens share one program (``bucketing.bucket_max_new_tokens``)
        instead of compiling three.  The true token budget ``n`` is a
        traced operand; steps past it are skipped by ``lax.cond`` (a
        branch, not a forward) and never advance the cache."""
        sig = (n_bucket, sample, top_k, top_p)
        if sig not in self._progs["reply"]:
            cfg = self._engine.model_config
            fam = self._engine._family
            from .sampling import filter_logits

            def reply(params, last, cache, key, temperature, n):
                def step(carry, xs):
                    k, i = xs

                    def live(c):
                        last, cache = c
                        lg = last[:, :cfg.vocab_size]
                        if sample:
                            lg = filter_logits(lg, temperature, top_k=top_k,
                                               top_p=top_p)
                            nxt = jax.random.categorical(k, lg).astype(
                                jnp.int32)
                        else:
                            nxt = jnp.argmax(lg, -1).astype(jnp.int32)
                        lg2, cache = fam.decode_step(params, nxt, cfg, cache)
                        return (lg2, cache), nxt

                    def dead(c):
                        return c, jnp.zeros((c[0].shape[0],), jnp.int32)

                    return lax.cond(i < n, live, dead, carry)

                (last, cache), toks = lax.scan(
                    step, (last, cache),
                    (jax.random.split(key, n_bucket), jnp.arange(n_bucket)))
                return toks.swapaxes(0, 1), last, cache

            self._progs["reply"][sig] = \
                self._engine.compile_registry.register(
                    f"session.reply:{sig}", jax.jit(reply))
        return self._progs["reply"][sig]

    def generate(self, max_new_tokens: int = 32, do_sample: bool = False,
                 temperature: float = 1.0, top_k: int = 0,
                 top_p: float = 1.0, key=None) -> jnp.ndarray:
        """Decode a reply in one fused XLA program (greedy, or sampled
        through the shared logit filter); the reply's K/V stays in the
        session cache, so the next ``append`` continues the
        conversation."""
        if self._last_logits is None:
            raise ValueError("append() a prompt before generate()")
        if not do_sample and (top_k > 0 or top_p < 1.0):
            raise ValueError(
                "top_k/top_p only apply with do_sample=True (greedy "
                "would silently ignore the filters)")
        B = self.cache.batch
        if max_new_tokens <= 0:
            return jnp.zeros((B, 0), jnp.int32)
        self._check_room(max_new_tokens)
        if key is None:
            key = jax.random.fold_in(jax.random.PRNGKey(0), self._key_seq)
            self._key_seq += 1
        from .bucketing import bucket_max_new_tokens
        toks, self._last_logits, self.cache = self._reply_prog(
            bucket_max_new_tokens(max_new_tokens), bool(do_sample),
            int(top_k) if do_sample else 0,
            float(top_p) if do_sample else 1.0)(
            self._engine.params, self._last_logits, self.cache, key,
            jnp.asarray(temperature, jnp.float32),
            jnp.asarray(max_new_tokens, jnp.int32))
        return jnp.asarray(np.asarray(toks)[:, :max_new_tokens])


def _save_16bit(params, dtype, path: str) -> None:
    from ..ops.int8 import Int8ComputeParam
    from .quantization import Int8Param
    # int8 engines dequantize to the compute dtype first: the contract
    # is a 16-bit weight per leaf under the leaf's own key
    _q = (Int8Param, Int8ComputeParam)
    params = jax.tree_util.tree_map(
        lambda p: p.astype(dtype) if isinstance(p, _q) else p,
        params, is_leaf=lambda p: isinstance(p, _q))
    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    arrays = {jax.tree_util.keystr(k): np.asarray(v) for k, v in flat}
    np.savez(path, **arrays)


class BertInferenceEngine:
    """Encoder-family serving: one jitted full-sequence forward (no KV
    cache).  The reference injects BERT through the same replace_module
    path as the decoder families (``module_inject/replace_policy.py:143``
    HFBertLayerPolicy → ``DeepSpeedTransformerInference`` in encoder
    mode); here the native ``models/bert.py`` encoder serves, with the
    same dtype / TP-sharding / weight-only-int8 treatment as
    :class:`InferenceEngine`."""

    def __init__(self, model_config, params: PyTree,
                 config: DeepSpeedInferenceConfig,
                 mesh_manager: Optional[MeshManager] = None):
        from ..models import bert
        self.mesh_manager = mesh_manager or get_mesh_manager(optional=True)
        self._config = config
        dtype, self._weight_int8 = _serving_dtype(config)
        if config.quantization.int8_compute:
            raise NotImplementedError(
                "quant.int8_compute serves the GPT decoder families; the "
                "encoder engine uses weight-only int8 (dtype='int8')")
        if config.kv_cache_dtype != "auto":
            raise NotImplementedError(
                "kv_cache_dtype applies to autoregressive decode; the "
                "encoder engine has no KV cache")
        self.model_config = dataclasses.replace(model_config, dtype=dtype)
        self.params = jax.tree_util.tree_map(
            lambda p: p.astype(dtype) if jnp.issubdtype(p.dtype, jnp.floating)
            else p, params)
        want_tp = _validate_tp(config, self.mesh_manager)
        self.params = _shard_and_quantize(
            self.params, bert.logical_axes(self.model_config),
            self.mesh_manager, want_tp, self._weight_int8)
        cfg = self.model_config
        # separate compiled programs for the masked/unmasked shapes (the
        # concrete-mask fast path in bert.encode must see None statically)
        self._fwd = jax.jit(
            lambda p, t, tt: bert.apply(p, t, cfg, tt))
        self._fwd_masked = jax.jit(
            lambda p, t, tt, am: bert.apply(p, t, cfg, tt, am))
        self._enc = jax.jit(
            lambda p, t, tt: bert.encode(p, t, cfg, tt))
        self._enc_masked = jax.jit(
            lambda p, t, tt, am: bert.encode(p, t, cfg, tt, am))
        self._pool = jax.jit(
            lambda p, t, tt: bert.pooled_output(
                p, bert.encode(p, t, cfg, tt), cfg))
        self._pool_masked = jax.jit(
            lambda p, t, tt, am: bert.pooled_output(
                p, bert.encode(p, t, cfg, tt, am), cfg))

    def _args(self, tokens, token_type_ids, attention_mask):
        """Normalized (tokens, type ids, mask-or-None); an all-ones mask
        collapses to None so the unmasked program serves it."""
        tokens = jnp.asarray(tokens, jnp.int32)
        tt = jnp.zeros_like(tokens) if token_type_ids is None \
            else jnp.asarray(token_type_ids, jnp.int32)
        if attention_mask is not None and np.asarray(attention_mask).all():
            attention_mask = None
        return tokens, tt, attention_mask

    def forward(self, tokens, token_type_ids=None, attention_mask=None):
        """tokens [B, S] → MLM logits [B, S, padded_vocab] fp32."""
        tokens, tt, am = self._args(tokens, token_type_ids, attention_mask)
        if am is not None:
            return self._fwd_masked(self.params, tokens, tt, jnp.asarray(am))
        return self._fwd(self.params, tokens, tt)

    __call__ = forward

    def encode(self, tokens, token_type_ids=None, attention_mask=None):
        """tokens [B, S] → hidden states [B, S, d]."""
        tokens, tt, am = self._args(tokens, token_type_ids, attention_mask)
        if am is not None:
            return self._enc_masked(self.params, tokens, tt, jnp.asarray(am))
        return self._enc(self.params, tokens, tt)

    def pooled(self, tokens, token_type_ids=None, attention_mask=None):
        """tokens [B, S] → [CLS] pooler output [B, d]."""
        tokens, tt, am = self._args(tokens, token_type_ids, attention_mask)
        if am is not None:
            return self._pool_masked(self.params, tokens, tt, jnp.asarray(am))
        return self._pool(self.params, tokens, tt)

    def save_16bit_model(self, path: str) -> None:
        _save_16bit(self.params, self.model_config.dtype, path)
