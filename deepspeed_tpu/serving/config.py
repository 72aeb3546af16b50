"""The ``"serving"`` config section, typed.

Same validated dataclass-model style as ``supervision/config.py``:

.. code-block:: json

    {"serving": {
        "slots": 4,
        "max_len": null,
        "prefill_chunk": 16,
        "queue_capacity": 64,
        "default_max_new_tokens": 64,
        "default_deadline_s": null,
        "top_k": 0, "top_p": 1.0,
        "seed": 0,
        "max_cached_prefixes": 8,
        "prefix_ttl_s": 600.0,
        "journal_every_ticks": 0,
        "eos_token_id": null,
        "paging": {"enabled": false, "block_tokens": 16,
                   "pool_blocks": null, "park_capacity": 64,
                   "park_dir": null, "park_ttl_s": 600.0,
                   "park_verify": true, "hbm_high_watermark": null},
        "speculative": {"enabled": false, "draft_k": 3, "draft": null},
        "transport": {"enabled": true, "port_base": 0,
                      "connect_timeout_s": 1.0, "send_timeout_s": 2.0,
                      "retries": 2, "backoff_s": 0.02,
                      "backoff_jitter": 0.25, "fallback": true,
                      "failures_to_open": 3, "probe_interval_s": 0.5}
    }}

``max_len`` is the per-slot cache length — bucketed to a power of two and
clamped to the model context (``null`` = the whole context).  Full
reference: ``docs/serving.md``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

from ..runtime.config_utils import DeepSpeedConfigModel

SERVING = "serving"


@dataclasses.dataclass
class PagingConfig(DeepSpeedConfigModel):
    """The ``"serving"."paging"`` subsection: paged KV blocks + session
    tiering (``serving/paging.py``, ``docs/serving.md``)."""

    #: switch the gateway from slot-pinned conversations to paged KV +
    #: session tiering (park finished conversations, re-admit follow-ups)
    enabled: bool = False
    #: KV rows per block — a power of two so blocks tile the bucketed
    #: slot length exactly (clamped to ``max_len`` at gateway build)
    block_tokens: int = 16
    #: device block-pool size (the warm tier); None = one slot-cache
    #: worth of blocks (``slots * max_len / block_tokens``)
    pool_blocks: Optional[int] = None
    #: RAM-parked sessions kept before spilling to ``park_dir`` (or
    #: dropping, when no park_dir is set)
    park_capacity: int = 64
    #: disk spill directory for cold parked sessions (atomic npz writes);
    #: None disables the disk tier
    park_dir: Optional[str] = None
    #: a parked session idle longer than this is dropped by the sweep
    park_ttl_s: float = 600.0
    #: verify the park-time SHA-256 on re-admission (corrupt KV is
    #: rejected and re-prefilled, never decoded)
    park_verify: bool = True
    #: HBM pressure watermark in bytes: when the telemetry live-buffer
    #: census exceeds it, the pager proactively parks pool-LRU sessions
    #: (journaled ``serve.page_evict`` with the observed pressure) instead
    #: of waiting for static pool exhaustion.  None disables the census
    #: path (exhaustion-driven eviction still runs)
    hbm_high_watermark: Optional[int] = None

    def __post_init__(self):
        bt = self.block_tokens
        if bt < 1 or (bt & (bt - 1)):
            raise ValueError(
                f"serving.paging.block_tokens must be a power of two "
                f">= 1, got {bt}")
        if self.pool_blocks is not None and self.pool_blocks < 1:
            raise ValueError(
                f"serving.paging.pool_blocks must be >= 1, got "
                f"{self.pool_blocks}")
        if self.park_capacity < 0:
            raise ValueError(
                f"serving.paging.park_capacity must be >= 0, got "
                f"{self.park_capacity}")
        if self.park_ttl_s <= 0:
            raise ValueError(
                f"serving.paging.park_ttl_s must be > 0, got "
                f"{self.park_ttl_s}")
        if self.hbm_high_watermark is not None and \
                self.hbm_high_watermark < 1:
            raise ValueError(
                f"serving.paging.hbm_high_watermark must be >= 1 byte, "
                f"got {self.hbm_high_watermark}")


#: keys a ``"overload"."classes"`` entry may carry
_PRIORITY_CLASS_KEYS = ("name", "min_priority", "ttft_slo_ms",
                        "queue_share")


@dataclasses.dataclass(frozen=True)
class PriorityClass:
    """One admission priority class (``docs/serving.md`` "Overload &
    admission").  A request belongs to the class with the highest
    ``min_priority`` not exceeding its priority."""

    name: str
    #: lowest request priority that lands in this class
    min_priority: int
    #: the class's TTFT SLO budget; None = best-effort (never sheds on
    #: the SLO estimate, only on its queue share)
    ttft_slo_ms: Optional[float]
    #: fraction of ``queue_capacity`` this class may fill before its
    #: submissions shed (1.0 = only the hard queue_full bound applies)
    queue_share: float


@dataclasses.dataclass
class OverloadConfig(DeepSpeedConfigModel):
    """The ``"serving"."overload"`` subsection: SLO-driven admission
    (priority shedding) + the hysteretic degradation ladder."""

    #: turn on the admission controller and degradation ladder
    enabled: bool = False
    #: priority classes, highest ``min_priority`` first after sorting;
    #: None = two defaults (interactive ≥1 w/ 2000ms SLO, batch ≥0
    #: best-effort at half the queue)
    classes: Optional[list] = None
    #: shed on the SLO estimate only past ``est_ttft > factor * slo``
    shed_slo_factor: float = 1.0
    #: EWMA smoothing for the queue-wait/prefill/first-token samples
    #: feeding the TTFT estimate and the dominant-phase attribution
    ewma_alpha: float = 0.3
    #: ladder hysteresis: consecutive scheduler iterations above/below
    #: the pressure watermarks before a rung engages/releases
    engage_ticks: int = 3
    release_ticks: int = 6
    #: queue pressure (depth / queue_capacity) watermarks
    pressure_high: float = 0.5
    pressure_low: float = 0.1
    #: reply-budget cap while the ``max_tokens`` rung is engaged
    #: (applied to NEW admissions only — accepted requests are never
    #: dropped, they just finish sooner)
    max_new_tokens_cap: int = 16

    def __post_init__(self):
        from ..runtime.config import DeepSpeedConfigError
        if self.classes is None:
            self.classes = [
                {"name": "interactive", "min_priority": 1,
                 "ttft_slo_ms": 2000.0, "queue_share": 1.0},
                {"name": "batch", "min_priority": 0,
                 "ttft_slo_ms": None, "queue_share": 0.5},
            ]
        if not isinstance(self.classes, list) or not self.classes:
            raise DeepSpeedConfigError(
                "serving.overload.classes must be a non-empty list of "
                f"class specs with keys {_PRIORITY_CLASS_KEYS}")
        for spec in self.classes:
            if not isinstance(spec, dict):
                raise DeepSpeedConfigError(
                    "serving.overload.classes entries must be dicts, got "
                    f"{type(spec).__name__}")
            unknown = sorted(set(spec) - set(_PRIORITY_CLASS_KEYS))
            if unknown:
                raise DeepSpeedConfigError(
                    f"serving.overload.classes: unknown keys {unknown} "
                    f"(known: {_PRIORITY_CLASS_KEYS})")
            share = spec.get("queue_share", 1.0)
            if not 0.0 < float(share) <= 1.0:
                raise DeepSpeedConfigError(
                    "serving.overload.classes queue_share must be in "
                    f"(0, 1], got {share!r}")
        if not 0.0 < self.ewma_alpha <= 1.0:
            raise DeepSpeedConfigError(
                f"serving.overload.ewma_alpha must be in (0, 1], got "
                f"{self.ewma_alpha}")
        if self.engage_ticks < 1 or self.release_ticks < 1:
            raise DeepSpeedConfigError(
                "serving.overload engage_ticks/release_ticks must be "
                f">= 1, got {self.engage_ticks}/{self.release_ticks}")
        if not 0.0 <= self.pressure_low < self.pressure_high:
            raise DeepSpeedConfigError(
                "serving.overload needs 0 <= pressure_low < "
                f"pressure_high, got {self.pressure_low}/"
                f"{self.pressure_high}")
        if self.max_new_tokens_cap < 1:
            raise DeepSpeedConfigError(
                "serving.overload.max_new_tokens_cap must be >= 1, got "
                f"{self.max_new_tokens_cap}")
        if self.shed_slo_factor <= 0:
            raise DeepSpeedConfigError(
                "serving.overload.shed_slo_factor must be > 0, got "
                f"{self.shed_slo_factor}")

    def priority_classes(self) -> tuple:
        """Typed classes, highest ``min_priority`` first."""
        return tuple(sorted(
            (PriorityClass(
                name=str(s["name"]), min_priority=int(s["min_priority"]),
                ttft_slo_ms=(float(s["ttft_slo_ms"])
                             if s.get("ttft_slo_ms") is not None else None),
                queue_share=float(s.get("queue_share", 1.0)))
             for s in self.classes),
            key=lambda c: -c.min_priority))


@dataclasses.dataclass
class TransportConfig(DeepSpeedConfigModel):
    """The ``"serving"."transport"`` subsection: the streamed fleet
    transport (``docs/serving.md`` "Streamed transport").  Framed TCP
    channels accelerate the spool's three flows — orders, bundles,
    results; the spool stays the durable record, so every knob here
    trades latency, never correctness."""

    #: stream frames alongside the spool writes (False: spool-only, the
    #: pre-transport behavior — what the bitwise-parity e2e compares
    #: against)
    enabled: bool = True
    #: fixed port layout base (supervisor at ``port_base``, workers
    #: stacked above it); 0 = ephemeral ports announced via
    #: ``spool/transport/<role><rank>.json`` — the default, safe for
    #: parallel runs on one host
    port_base: int = 0
    #: per-attempt TCP connect deadline, seconds
    connect_timeout_s: float = 1.0
    #: per-attempt frame write deadline, seconds
    send_timeout_s: float = 2.0
    #: retries after a failed send attempt (total attempts = retries + 1)
    retries: int = 2
    #: exponential backoff base between retries, seconds (doubles per
    #: retry)
    backoff_s: float = 0.02
    #: multiplicative jitter fraction on each backoff sleep
    backoff_jitter: float = 0.25
    #: degrade to the filesystem spool when a peer's breaker opens
    #: (False: keep attempting every send — still never fatal, the spool
    #: write has already happened either way)
    fallback: bool = True
    #: consecutive send failures that open a (peer, flow) breaker
    failures_to_open: int = 3
    #: seconds between auto-probe pings of an open breaker
    probe_interval_s: float = 0.5

    def __post_init__(self):
        from ..runtime.config import DeepSpeedConfigError
        if not isinstance(self.port_base, int) \
                or isinstance(self.port_base, bool) \
                or not 0 <= self.port_base <= 65000:
            raise DeepSpeedConfigError(
                f"serving.transport.port_base must be an int in "
                f"[0, 65000], got {self.port_base!r}")
        for key in ("connect_timeout_s", "send_timeout_s", "backoff_s",
                    "probe_interval_s"):
            val = getattr(self, key)
            if not isinstance(val, (int, float)) \
                    or isinstance(val, bool) or val <= 0:
                raise DeepSpeedConfigError(
                    f"serving.transport.{key} must be a number > 0, "
                    f"got {val!r}")
        if not isinstance(self.retries, int) \
                or isinstance(self.retries, bool) \
                or not 0 <= self.retries <= 16:
            raise DeepSpeedConfigError(
                f"serving.transport.retries must be an int in [0, 16], "
                f"got {self.retries!r}")
        if not isinstance(self.backoff_jitter, (int, float)) \
                or isinstance(self.backoff_jitter, bool) \
                or not 0.0 <= self.backoff_jitter <= 1.0:
            raise DeepSpeedConfigError(
                f"serving.transport.backoff_jitter must be in [0, 1], "
                f"got {self.backoff_jitter!r}")
        if not isinstance(self.failures_to_open, int) \
                or isinstance(self.failures_to_open, bool) \
                or self.failures_to_open < 1:
            raise DeepSpeedConfigError(
                f"serving.transport.failures_to_open must be an int >= 1, "
                f"got {self.failures_to_open!r}")


#: keys a ``"speculative"."draft"`` geometry spec may carry
_DRAFT_SPEC_KEYS = ("n_layer", "d_model", "n_head", "seed")


@dataclasses.dataclass
class SpeculativeConfig(DeepSpeedConfigModel):
    """The ``"serving"."speculative"`` subsection: batched draft/verify
    speculation in the continuous-batching tick loop (``docs/serving.md``
    "Speculative tick").  Misconfiguration here raises the named
    :class:`~deepspeed_tpu.runtime.config.DeepSpeedConfigError` — a wrong
    draft spec must fail at config time, not as a silently slow (or
    recompiling) gateway."""

    #: switch the tick loop from one-token decode_step rounds to
    #: draft_k-token draft/verify rounds (exact output semantics)
    enabled: bool = False
    #: draft proposals per round; bucketed so the k+1 verify window is a
    #: power of two (``bucket_draft_k``)
    draft_k: int = 3
    #: draft-model geometry spec ``{"n_layer", "d_model", "n_head",
    #: "seed"}`` — builds a random-init dense GPT draft over the target's
    #: vocabulary when no trained draft is passed to ``engine.serve(
    #: draft=...)``.  None: a draft engine/params MUST be passed.
    draft: Optional[Dict] = None

    def __post_init__(self):
        # lazy: runtime.config imports nothing from serving/, but keep
        # the error type importable without risking a module cycle here
        from ..runtime.config import DeepSpeedConfigError
        if not isinstance(self.draft_k, int) or isinstance(self.draft_k, bool) \
                or not 1 <= self.draft_k <= 64:
            raise DeepSpeedConfigError(
                f"serving.speculative.draft_k must be an int in [1, 64], "
                f"got {self.draft_k!r}")
        if self.draft is None:
            return
        if not isinstance(self.draft, dict):
            raise DeepSpeedConfigError(
                "serving.speculative.draft must be a dict draft-model "
                f"spec with keys {_DRAFT_SPEC_KEYS}, got "
                f"{type(self.draft).__name__}")
        unknown = sorted(set(self.draft) - set(_DRAFT_SPEC_KEYS))
        if unknown:
            raise DeepSpeedConfigError(
                f"serving.speculative.draft: unknown keys {unknown} "
                f"(known: {_DRAFT_SPEC_KEYS})")
        for k in ("n_layer", "d_model", "n_head"):
            if k in self.draft and (
                    not isinstance(self.draft[k], int)
                    or isinstance(self.draft[k], bool)
                    or self.draft[k] < 1):
                raise DeepSpeedConfigError(
                    f"serving.speculative.draft.{k} must be an int >= 1, "
                    f"got {self.draft[k]!r}")


@dataclasses.dataclass
class ServingConfig(DeepSpeedConfigModel):
    """Continuous-batching gateway knobs (see ``docs/serving.md``)."""

    #: decode-batch width B: how many requests decode concurrently.  The
    #: slot cache is [L, B, max_len, H*D] — sized once, never resized.
    slots: int = 4
    #: per-slot cache length (prompt + reply budget); None = model context.
    #: Bucketed to a power of two so nearby deployments share programs.
    max_len: Optional[int] = None
    #: admission prefill chunk width: prompts pad up to a multiple and
    #: prefill through fixed-shape chunks, so admission NEVER compiles a
    #: per-prompt-length program
    prefill_chunk: int = 16
    #: bounded admission queue; submit() past this rejects loudly
    queue_capacity: int = 64
    #: reply budget when a request doesn't name one
    default_max_new_tokens: int = 64
    #: seconds from submit to completion before a request times out
    #: (None = no deadline unless the request carries one)
    default_deadline_s: Optional[float] = None
    #: static sampling-filter shape for the shared decode tick program
    #: (per-request temperature/greediness are traced; the filter shape
    #: is compiled in — one program, not one per sampling config)
    top_k: int = 0
    top_p: float = 1.0
    #: base seed for per-request key derivation (requests may pin their own)
    seed: int = 0
    #: LRU-bounded pool of shared-prefix sessions (system prompts,
    #: deduplicated through zero-copy ``InferenceSession.fork``); 0
    #: disables the pool
    max_cached_prefixes: int = 8
    #: a pooled prefix idle longer than this is evicted on the next sweep
    prefix_ttl_s: float = 600.0
    #: journal a ``serve.tick`` snapshot every N ticks (0 = off)
    journal_every_ticks: int = 0
    #: default eos: rows emitting it finish early (None = run the budget)
    eos_token_id: Optional[int] = None
    #: scheduler idle wait between queue polls, seconds
    idle_wait_s: float = 0.02
    #: compile every serving program (both prefill chunk widths, every
    #: speculative ladder level) at construction instead of lazily on
    #: first use — overload robustness: a degradation rung engaging
    #: mid-storm must never stall the tick loop behind its first XLA
    #: compile
    warm_start: bool = False
    #: raw "paging" subsection (typed view: ``paging_config``) — paged
    #: KV blocks + session tiering; see :class:`PagingConfig`
    paging: Optional[Dict] = None
    #: raw "speculative" subsection (typed view: ``speculative_config``) —
    #: batched draft/verify in the tick loop; see :class:`SpeculativeConfig`
    speculative: Optional[Dict] = None
    #: raw "overload" subsection (typed view: ``overload_config``) —
    #: SLO-driven admission + degradation ladder; see
    #: :class:`OverloadConfig`
    overload: Optional[Dict] = None
    #: raw "transport" subsection (typed view: ``transport_config``) —
    #: streamed fleet transport; see :class:`TransportConfig`
    transport: Optional[Dict] = None

    paging_config: PagingConfig = dataclasses.field(
        default_factory=PagingConfig)
    speculative_config: SpeculativeConfig = dataclasses.field(
        default_factory=SpeculativeConfig)
    overload_config: OverloadConfig = dataclasses.field(
        default_factory=OverloadConfig)
    transport_config: TransportConfig = dataclasses.field(
        default_factory=TransportConfig)

    def __post_init__(self):
        if isinstance(self.paging, dict):
            self.paging_config = PagingConfig.from_dict(self.paging)
        elif isinstance(self.paging, PagingConfig):
            self.paging_config = self.paging
            self.paging = self.paging_config.to_dict()
        if isinstance(self.overload, dict):
            self.overload_config = OverloadConfig.from_dict(self.overload)
        elif isinstance(self.overload, OverloadConfig):
            self.overload_config = self.overload
            self.overload = self.overload_config.to_dict()
        if isinstance(self.speculative, dict):
            self.speculative_config = SpeculativeConfig.from_dict(
                self.speculative)
        elif isinstance(self.speculative, SpeculativeConfig):
            self.speculative_config = self.speculative
            self.speculative = self.speculative_config.to_dict()
        if isinstance(self.transport, dict):
            self.transport_config = TransportConfig.from_dict(self.transport)
        elif isinstance(self.transport, TransportConfig):
            self.transport_config = self.transport
            self.transport = self.transport_config.to_dict()
        if self.slots < 1:
            raise ValueError(f"serving.slots must be >= 1, got {self.slots}")
        if self.prefill_chunk < 1:
            raise ValueError(
                f"serving.prefill_chunk must be >= 1, got "
                f"{self.prefill_chunk}")
        if self.queue_capacity < 1:
            raise ValueError(
                f"serving.queue_capacity must be >= 1, got "
                f"{self.queue_capacity}")
        if self.default_max_new_tokens < 1:
            raise ValueError(
                f"serving.default_max_new_tokens must be >= 1, got "
                f"{self.default_max_new_tokens}")
        if not (0.0 < self.top_p <= 1.0):
            raise ValueError(
                f"serving.top_p must be in (0, 1], got {self.top_p}")
        if self.top_k < 0:
            raise ValueError(f"serving.top_k must be >= 0, got {self.top_k}")
        if self.max_cached_prefixes < 0:
            raise ValueError(
                f"serving.max_cached_prefixes must be >= 0, got "
                f"{self.max_cached_prefixes}")
        if self.default_deadline_s is not None and self.default_deadline_s <= 0:
            raise ValueError(
                f"serving.default_deadline_s must be > 0, got "
                f"{self.default_deadline_s}")
        if self.max_len is not None and self.max_len < 2:
            raise ValueError(
                f"serving.max_len must be >= 2 (a prompt token and a reply "
                f"token), got {self.max_len}")
        if self.journal_every_ticks < 0:
            raise ValueError(
                f"serving.journal_every_ticks must be >= 0, got "
                f"{self.journal_every_ticks}")
        if self.idle_wait_s <= 0:
            raise ValueError(
                f"serving.idle_wait_s must be > 0, got {self.idle_wait_s}")
