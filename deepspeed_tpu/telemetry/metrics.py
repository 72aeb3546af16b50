"""The metrics stream: counters/gauges/histograms + a JSONL sampler.

Instruments are deliberately dumb and thread-safe (the ``ServingMetrics``
discipline, generalized): counters only go up, gauges hold the last value,
histograms keep count/sum plus a bounded reservoir so percentile math is
exact at bench scale and bounded at fleet scale.  Every instrument name is
a :class:`MetricName` constant — the ``EventKind`` pattern — validated at
creation time and statically by dslint's ``unregistered-telemetry-name``
rule, so the metric table in ``docs/telemetry.md`` can't drift from the
emit sites.

:class:`MetricsSampler` appends one ``metrics.sample`` JSON object per
line to a ``metrics.jsonl`` sidecar (same torn-line-tolerant append/read
contract as the supervision ``events.jsonl``: a killed process loses at
most the line being written, and :func:`read_metrics` skips torn trailing
records instead of raising).  The goodput fleet points each rank's sampler
at the shared run dir, so telemetry breakage under restarts is a scored
observable, not a silent gap.

Online MFU rides on the same analytic FLOPs model the benchmarks use
(``models/gpt.py::flops_per_token`` + the per-generation peak table
below, the chip benchmark's ``benchmarks/chip/peaks.py``): :func:`analytic_mfu` is pure arithmetic, unit-tested
against a hand-computed fixture.
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Any, Callable, Dict, List, Optional

from ..utils import lock_watch
from ..utils.jsonl import read_jsonl
from ..utils.lock_watch import LockName, TrackedLock
from ..utils.logging import logger

__all__ = [
    "MetricName", "METRIC_NAMES", "Counter", "Gauge", "Histogram",
    "MetricsRegistry", "MetricsSampler", "read_metrics", "analytic_mfu",
    "peak_flops_per_chip", "host_rss_bytes", "live_buffer_bytes",
    "lock_watch_metrics",
]


class MetricName:
    """Single source of truth for every metric name.

    Register new names HERE first, then document them in the metric table
    in ``docs/telemetry.md`` (dslint's ``unregistered-telemetry-name``
    rule and ``telemetry-name-drift`` project check enforce both ends).
    """

    #: histogram of optimizer-step wall seconds (boundary to boundary)
    STEP_TIME_S = "train.step_time_s"
    #: tokens trained per second, over the sampler window
    TOKENS_PER_S = "train.tokens_per_s"
    #: online model-FLOPs utilization (0 when the chip peak is unknown)
    MFU = "train.mfu"
    #: achieved model TFLOP/s (tokens/s × analytic FLOPs/token)
    TFLOPS = "train.tflops"
    #: engine.global_steps at sample time
    STEPS = "train.steps"
    #: engine.skipped_steps (overflow-skipped) at sample time
    SKIPPED_STEPS = "train.skipped_steps"
    #: score sub-tiles the flash kernels' forward and backward sweeps compute
    #: (``flash_attention.causal_tile_plan``: one head of every kernel call
    #: site in the step, counted when the step is traced)
    FLASH_CAUSAL_TILES_VISITED = "flash.causal_tiles_visited"
    #: the sub-tiles of the whole blocks those sweeps computed before a
    #: diagonal block was walked in causal strips; visited / square is how
    #: far the strips engage (1.0: not at all)
    FLASH_CAUSAL_TILES_SQUARE = "flash.causal_tiles_square"
    #: flash kernel call sites in the traced step (forward; the dense
    #: fallbacks count nothing)
    FLASH_CALLS = "flash.calls"
    #: those of them handed the packed qkv product in token-major rows:
    #: nothing is re-laid around the call, forward, re-forward or backward
    #: (packed / calls is 1.0 where every layer's attention engages)
    FLASH_CALLS_TOKEN_MAJOR_PACKED = "flash.calls_token_major_packed"
    #: vocabulary-sized products the traced step's loss head holds, forward
    #: and backward (``models.gpt.tally_head``: 3 a head; over chunks of the
    #: sequence its own backward rule fixes them, of whole logits the
    #: compiler may re-make them)
    HEAD_LOGIT_PRODUCTS = "head.logit_products"
    #: chunks of the sequence that head walks (1: the plain single pass)
    HEAD_ROW_CHUNKS = "head.row_chunks"
    #: host process resident set size, bytes (0 without psutil)
    HOST_RSS_BYTES = "mem.host_rss_bytes"
    #: sum of live jax device-buffer bytes (the HBM census)
    HBM_LIVE_BYTES = "mem.hbm_live_bytes"
    #: cumulative compiles across the owner's CompiledProgramRegistry
    COMPILES = "compile.count"
    #: cumulative sanctioned host syncs noted on the registry
    HOST_SYNCS = "compile.host_syncs"
    #: admission queue depth at sample time
    SERVE_QUEUE_DEPTH = "serve.queue_depth"
    #: lifetime mean slot occupancy (active slot-ticks / slot-ticks)
    SERVE_OCCUPANCY = "serve.occupancy"
    #: lifetime share of the slot grid's cache blocks that were live, i.e.
    #: that the decode kernel stepped (live blocks / grid blocks)
    SERVE_LIVE_BLOCK_SHARE = "serve.live_block_share"
    #: lifetime cached tokens the decode kernel streamed per token a query
    #: saw (1.0: no dead tail in any row's last block)
    SERVE_KV_STREAMED_OVER_LIVE = "serve.kv_streamed_over_live"
    #: cached rows a slot holds over what one geometry for every layer
    #: would hold (1.0: one pool; a family with rings beside whole rows
    #: holds less)
    SERVE_KV_POOL_SHARE = "serve.kv_pool_share"
    #: lifetime share of the tokens the single-token sweeps streamed that
    #: the rings' sweeps streamed (0.0 for a family with one pool)
    SERVE_KV_WINDOW_STREAMED_SHARE = "serve.kv_window_streamed_share"
    #: histogram of time-to-first-token seconds
    SERVE_TTFT_S = "serve.ttft_s"
    #: decode tokens emitted per second over the gateway lifetime
    SERVE_TOKENS_PER_S = "serve.tokens_per_s"
    #: serving HBM footprint (slot cache + block pool) per concurrently
    #: held conversation (decoding + pooled + parked) — the paged-KV
    #: capacity lever the serve bench gates
    SERVE_HBM_BYTES_PER_CONVERSATION = "serve.hbm_bytes_per_conversation"
    #: histogram of re-admission wall seconds for parked sessions
    SERVE_READMIT_S = "serve.readmit_s"
    #: histogram of per-round speculative acceptance rate (accepted
    #: drafts / proposed drafts across the live slots of one tick)
    SERVE_SPEC_ACCEPT_RATE = "serve.spec_accept_rate"
    #: histogram of tokens emitted per speculative tick (all live slots;
    #: 1..draft_k+1 each — the tokens/s lever speculation buys)
    SERVE_SPEC_TOKENS_PER_TICK = "serve.spec_tokens_per_tick"
    #: requests shed by the admission controller (cumulative)
    SERVE_SHED_TOTAL = "serve.shed_total"
    #: currently engaged degradation-ladder rungs (bitmask gauge; 0 = the
    #: gateway is running at full quality)
    SERVE_DEGRADE_RUNGS = "serve.degrade_rungs"
    #: streamed-transport bytes pushed on the order flow (supervisor →
    #: worker order/park frames)
    TRANSPORT_BYTES_ORDERS = "transport.bytes_orders"
    #: streamed-transport bytes pushed on the bundle flow (KV page /
    #: migration bundle frames, blob included)
    TRANSPORT_BYTES_BUNDLES = "transport.bytes_bundles"
    #: streamed-transport bytes pushed on the result flow (worker →
    #: supervisor manifests, results, nacks, migration acks)
    TRANSPORT_BYTES_RESULTS = "transport.bytes_results"
    #: streamed-transport bytes pushed on the activation flow (MPMD
    #: pipeline boundary activations/grads + reduce frames, blob included)
    TRANSPORT_BYTES_ACTIVATIONS = "transport.bytes_activations"
    #: transport frames successfully sent from this endpoint (all flows)
    TRANSPORT_FRAMES_SENT = "transport.frames_sent"
    #: inbound frames rejected by the integrity check (torn / truncated /
    #: digest mismatch) — the spool copy remains authoritative
    TRANSPORT_FRAME_REJECTS = "transport.frame_rejects"
    #: connections re-established after a previous one existed
    TRANSPORT_RECONNECTS = "transport.reconnects"
    #: sends that fell back to the filesystem spool (breaker open or
    #: retry budget spent)
    TRANSPORT_FALLBACKS = "transport.fallbacks"
    #: circuit-breaker open transitions (per peer × flow episode)
    TRANSPORT_BREAKER_OPENS = "transport.breaker_opens"
    #: circuit-breaker close transitions (probe or live send succeeded)
    TRANSPORT_BREAKER_CLOSES = "transport.breaker_closes"
    #: cumulative bytes the explicit grad-reduce collectives WOULD have
    #: moved at full precision (fp32 payload, both directions)
    COMM_LOGICAL_BYTES = "comm.logical_bytes"
    #: cumulative bytes those collectives actually put on the wire
    #: (quantized codes + per-block fp32 scales; == logical for fp32 mean)
    COMM_WIRE_BYTES = "comm.wire_bytes"
    #: divergence rollbacks performed by the run supervisor
    ROLLBACKS = "elastic.rollbacks"
    #: fleet incarnation index (how many whole-group restarts preceded us)
    RESTARTS = "elastic.restarts"
    #: contended tracked-lock acquisitions, all locks, cumulative
    #: (``utils/lock_watch.py`` — see docs/static-analysis.md)
    CONCURRENCY_LOCK_CONTENTION = "concurrency.lock_contention"
    #: cumulative seconds threads spent blocked on contended tracked locks
    CONCURRENCY_LOCK_WAIT_S = "concurrency.lock_wait_s"
    #: histogram block over tracked-lock hold times (bounded per-lock
    #: reservoirs, maxima-preserving past the bound)
    CONCURRENCY_LOCK_HOLD_S = "concurrency.lock_hold_s"
    #: per-lock-name stats table {name: {acquisitions, contentions,
    #: wait_s, hold_p99_s}} — what the dump_run_events concurrency
    #: footer ranks top contended locks from
    CONCURRENCY_LOCKS = "concurrency.locks"


#: every registered metric name, as a frozenset of strings
METRIC_NAMES = frozenset(
    v for k, v in vars(MetricName).items()
    if not k.startswith("_") and isinstance(v, str))


def _require_registered(name: str) -> str:
    if name not in METRIC_NAMES:
        raise ValueError(
            f"metric name '{name}' is not registered in MetricName "
            "(telemetry/metrics.py) — register it (and its "
            "docs/telemetry.md row) first")
    return name


class Counter:
    """Monotonically increasing count."""

    def __init__(self, name: str):
        self.name = name
        self._lock = TrackedLock(LockName.TELEMETRY_METRIC)
        self._value = 0

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        with self._lock:
            return self._value


class Gauge:
    """Last-value-wins scalar."""

    def __init__(self, name: str):
        self.name = name
        self._lock = TrackedLock(LockName.TELEMETRY_METRIC)
        self._value: Optional[float] = None

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)

    @property
    def value(self) -> Optional[float]:
        with self._lock:
            return self._value


class Histogram:
    """count/sum plus a bounded sample reservoir (oldest dropped).

    The reservoir keeps percentile math exact for bench-scale runs (the
    ``ServingMetrics`` TTFT discipline) while bounding memory for endless
    ones; ``count``/``sum`` stay exact regardless.
    """

    def __init__(self, name: str = "", cap: int = 4096):
        self.name = name
        self.cap = int(cap)
        self._lock = TrackedLock(LockName.TELEMETRY_METRIC)
        self._samples: List[float] = []
        self._count = 0
        self._sum = 0.0

    def observe(self, value: float) -> None:
        v = float(value)
        with self._lock:
            self._count += 1
            self._sum += v
            self._samples.append(v)
            if len(self._samples) > self.cap:
                del self._samples[:len(self._samples) - self.cap]

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def values(self) -> List[float]:
        """The raw reservoir (newest ``cap`` observations)."""
        with self._lock:
            return list(self._samples)

    def percentile(self, q: float) -> Optional[float]:
        """Nearest-rank percentile (``ceil(q/100 · n)``-th sample) over the
        reservoir; None when empty.

        Defined for every reservoir size: one sample answers every ``q``
        with itself, two samples split at the median (p50 → lower, p99 →
        upper) — no index errors and no banker's-rounding surprises on the
        tiny per-phase histograms critical-path stats are built from.
        """
        with self._lock:
            if not self._samples:
                return None
            s = sorted(self._samples)
        rank = math.ceil(min(100.0, max(0.0, float(q))) / 100.0 * len(s))
        return s[min(len(s) - 1, max(0, rank - 1))]

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            n, total = self._count, self._sum
        return {
            "count": n,
            "mean": (total / n) if n else None,
            "p50": self.percentile(50),
            "p99": self.percentile(99),
        }


class MetricsRegistry:
    """Named instruments, created on first use, names validated against
    :data:`METRIC_NAMES`.  One registry per owner (engine, gateway)."""

    def __init__(self, name: str = "telemetry"):
        self.name = name
        self._lock = TrackedLock(LockName.TELEMETRY_REGISTRY)
        self._counters: Dict[str, Counter] = {}
        self._gauges: Dict[str, Gauge] = {}
        self._histograms: Dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        _require_registered(name)
        with self._lock:
            if name not in self._counters:
                self._counters[name] = Counter(name)
            return self._counters[name]

    def gauge(self, name: str) -> Gauge:
        _require_registered(name)
        with self._lock:
            if name not in self._gauges:
                self._gauges[name] = Gauge(name)
            return self._gauges[name]

    def histogram(self, name: str, cap: int = 4096) -> Histogram:
        _require_registered(name)
        with self._lock:
            if name not in self._histograms:
                self._histograms[name] = Histogram(name, cap=cap)
            return self._histograms[name]

    def snapshot(self) -> Dict[str, Any]:
        """One flat dict: counters/gauges by name, histograms as
        ``{count, mean, p50, p99}`` blocks."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            histograms = dict(self._histograms)
        out: Dict[str, Any] = {}
        for name, c in counters.items():
            out[name] = c.value
        for name, g in gauges.items():
            out[name] = g.value
        for name, h in histograms.items():
            out[name] = h.snapshot()
        return out


def lock_watch_metrics() -> Dict[str, Any]:
    """Sampler source feeding tracked-lock telemetry into ``metrics.sample``
    rows: total contended acquisitions, total wait seconds, a hold-time
    histogram block, and the per-lock table the ``dump_run_events.py``
    concurrency footer ranks.  Returns ``{}`` before any tracked lock has
    been acquired, so runs that never touch one emit no extra keys.

    Attach with ``sampler.attach_source(lock_watch_metrics)`` (the serving
    gateway does).
    """
    stats = lock_watch.lock_stats()
    if not stats:
        return {}
    holds: List[float] = []
    table: Dict[str, Any] = {}
    contentions = 0
    wait_s = 0.0
    for name, s in stats.items():
        holds.extend(s["holds"])
        contentions += s["contentions"]
        wait_s += s["wait_s"]
        hs = sorted(s["holds"])
        table[name] = {
            "acquisitions": s["acquisitions"],
            "contentions": s["contentions"],
            "wait_s": round(s["wait_s"], 6),
            "hold_p99_s": round(
                hs[min(len(hs) - 1, math.ceil(0.99 * len(hs)) - 1)], 6)
            if hs else None,
        }
    holds.sort()
    n = len(holds)
    return {
        MetricName.CONCURRENCY_LOCK_CONTENTION: contentions,
        MetricName.CONCURRENCY_LOCK_WAIT_S: round(wait_s, 6),
        MetricName.CONCURRENCY_LOCK_HOLD_S: {
            "count": n,
            "mean": round(sum(holds) / n, 6) if n else None,
            "p50": round(holds[min(n - 1, math.ceil(0.50 * n) - 1)], 6)
            if n else None,
            "p99": round(holds[min(n - 1, math.ceil(0.99 * n) - 1)], 6)
            if n else None,
        },
        MetricName.CONCURRENCY_LOCKS: table,
    }


# ---------------------------------------------------------------- sampler
class MetricsSampler:
    """Appends ``metrics.sample`` rows to a JSONL sidecar.

    Sources are zero-arg callables returning ``{metric_name: value}``
    dicts merged into every sample (names validated against
    :data:`METRIC_NAMES`; a source raising is logged and skipped — a
    broken gauge must not take down the run it measures).  A first row is
    written at :meth:`start` so the file exists (and is parseable) from
    the moment the run does — the goodput fleet's per-rank telemetry
    check depends on that.
    """

    def __init__(self, registry: MetricsRegistry, path: Optional[str],
                 rank: int = 0, interval_steps: int = 1, journal=None):
        self.registry = registry
        self.path = str(path) if path else None
        self.rank = int(rank)
        self.interval_steps = max(1, int(interval_steps))
        self._journal = journal
        self._lock = TrackedLock(LockName.TELEMETRY_SAMPLER)
        self._seq = 0
        self._sources: List[Callable[[], Dict[str, Any]]] = []
        if self.path:
            d = os.path.dirname(self.path)
            if d:
                os.makedirs(d, exist_ok=True)

    @property
    def enabled(self) -> bool:
        return self.path is not None

    def attach_source(self, fn: Callable[[], Dict[str, Any]]) -> None:
        with self._lock:
            self._sources.append(fn)

    def start(self) -> None:
        """Write the run's first sample (existence marker)."""
        self.sample(step=None)

    def should_sample(self, step: int) -> bool:
        return self.enabled and step % self.interval_steps == 0

    def sample(self, step: Optional[int] = None,
               **extra: Any) -> Optional[Dict[str, Any]]:
        """Append one sample row; returns the record written (None when
        the sampler has no path)."""
        if not self.enabled:
            return None
        m = self.registry.snapshot()
        with self._lock:
            sources = list(self._sources)
        for fn in sources:
            try:
                fields = fn() or {}
            except Exception as e:
                logger.warning(f"[telemetry] metrics source failed: {e!r}")
                continue
            for name, value in fields.items():
                _require_registered(name)
                m[name] = value
        with self._lock:
            self._seq += 1
            rec: Dict[str, Any] = {
                "ts": time.time(), "seq": self._seq, "rank": self.rank,
                "kind": "metrics.sample", "m": m,
            }
            if step is not None:
                rec["step"] = int(step)
            rec.update(extra)
            try:
                line = json.dumps(rec, default=str)
                with open(self.path, "a") as f:
                    f.write(line + "\n")
                    f.flush()
            except (OSError, TypeError, ValueError) as e:
                # telemetry loss must never take down the run it measures
                logger.warning(f"[telemetry] metrics write failed: {e}")
        return rec


def read_metrics(path: str) -> List[Dict[str, Any]]:
    """Parse a ``metrics.jsonl``; torn/garbage lines are skipped, not
    fatal (the ``read_events`` contract)."""
    return read_jsonl(path)


# ------------------------------------------------------------- online MFU
#: peak dense bf16 FLOP/s per chip by device generation (the figures of
#: ``benchmarks/chip/peaks.py``)
_PEAK_BY_KIND = (("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
                 ("v5", 459e12), ("v6", 918e12), ("v4", 275e12),
                 ("v3", 123e12), ("v2", 45e12))


def peak_flops_per_chip(device_kind: str) -> Optional[float]:
    """Peak FLOP/s for a jax ``device_kind`` string; None when unknown
    (CPU, exotic backends) — callers then report MFU as 0."""
    kind = (device_kind or "").lower()
    for pat, peak in _PEAK_BY_KIND:
        if pat in kind:
            return peak
    return None


def analytic_mfu(tokens_per_s: float, flops_per_token: float,
                 peak_flops: Optional[float],
                 n_chips: int = 1) -> Dict[str, float]:
    """The benchmarks' MFU arithmetic, online: achieved model FLOP/s =
    tokens/s × analytic FLOPs/token; MFU = achieved / (peak × chips).

    Returns ``{"tflops": ..., "mfu": ...}`` (mfu 0.0 when the peak is
    unknown)."""
    achieved = float(tokens_per_s) * float(flops_per_token)
    mfu = achieved / (float(peak_flops) * max(1, int(n_chips))) \
        if peak_flops else 0.0
    return {"tflops": achieved / 1e12, "mfu": mfu}


# ------------------------------------------------------- memory sampling
def host_rss_bytes() -> int:
    """Resident set size of this process (0 without psutil)."""
    try:
        import psutil

        return int(psutil.Process().memory_info().rss)
    except Exception:  # pragma: no cover  # dslint: disable=swallowed-exception — optional dependency probe
        return 0


def live_buffer_bytes() -> int:
    """Sum of live jax array bytes (the device-memory census).  Costs a
    walk over the live-array list — sampled at the metrics cadence, never
    on the hot path."""
    try:
        import jax

        return int(sum(int(getattr(a, "nbytes", 0) or 0)
                       for a in jax.live_arrays()))
    except Exception:  # pragma: no cover  # dslint: disable=swallowed-exception — census is best-effort off-device
        return 0
