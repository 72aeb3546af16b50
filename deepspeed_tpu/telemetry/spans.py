"""Span tracing: nestable, thread-aware wall-clock spans over the hot paths.

One :class:`Tracer` per owner (the train engine, the serving gateway); the
instrumented code wraps each phase in ``with tracer.span(SpanName.X):`` and
the tracer records ``(name, start, duration, thread, depth)`` rows.  The
rows feed three consumers:

- the ``wall_clock_breakdown`` log lines (the old
  ``SynchronizedWallClockTimer`` path — same numbers, now from spans);
- the per-step timeline exported as Chrome/Perfetto ``trace_event`` JSON
  (``telemetry/export.py``), where nesting falls out of ts/dur on a tid;
- the span-inventory + coverage gates in ``scripts/run_report.py``.

Design constraints, in order:

1. **Near-zero cost when disabled, and still visible to a profiler.**
   ``span()`` always opens a ``jax.profiler.TraceAnnotation`` of the same
   name and args, so whatever profiler is attached to the process (the
   chip benchmark's traced slice, ``telemetry.trace``, an operator's
   ``jax.profiler.start_server``) shows the program's phases on the host
   thread lines of the same ``.xplane.pb`` as the device ops, on one
   clock, with nothing to enable first.  With no profiler running an
   annotation is an atomic flag read (~0.5 us a site).  ``enabled``
   decides only whether :class:`SpanRecord` rows are kept: a disabled
   tracer reads no clock, takes no lock and keeps nothing.
2. **Dispatch-time by default.**  JAX calls return at *dispatch*; a span
   measures host-side wall time unless the tracer was built with
   ``synced=True``, which blocks on a device barrier at both edges (the
   calibration mode) and notes each barrier through the owning
   ``CompiledProgramRegistry`` as a sanctioned host sync.
3. **One request, one id.**  Every span that works for one serving
   request carries ``rid=<request id>`` in its args.  Children are found
   by nesting (same ``tid``, interval inside the parent's, ``depth`` one
   greater).  A wait that begins on one thread or tick and ends on
   another (queue, first token) cannot be a ``with`` block: its owner
   notes the start and calls :meth:`Tracer.record` when it ends, and the
   row lands on the synthetic :data:`WAIT_THREAD` line, apart from every
   real thread's phases.
4. **Single-source names.**  Every span name is a :class:`SpanName`
   constant (the ``EventKind`` pattern); dslint's
   ``unregistered-telemetry-name`` rule checks emit sites statically and
   :meth:`Tracer.span` validates at runtime, so the inventory in
   ``docs/telemetry.md`` and ``BENCH_TELEMETRY.json`` can't drift.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from ..utils.lock_watch import LockName, TrackedLock

__all__ = ["SpanName", "SPAN_NAMES", "SpanRecord", "Tracer", "WAIT_THREAD"]

#: the synthetic thread that recorded waits (:meth:`Tracer.record`) land on.
#: A wait is a phase of no thread: consumers that ask what a thread was
#: doing (idle-gap naming, nesting) skip rows on this line; consumers that
#: read a wait's duration find it by name.
WAIT_THREAD = "waits"
_WAIT_TID = 1


class SpanName:
    """Single source of truth for every span name.

    Register new names HERE first, then document them in the span table in
    ``docs/telemetry.md`` — dslint's ``unregistered-telemetry-name`` rule
    checks ``.span(...)`` call sites against this class and its
    ``telemetry-name-drift`` project check keeps the docs table in sync.
    """

    #: one optimizer step end-to-end (the fused whole-batch path); the
    #: coverage gate in run_report measures trace completeness against it
    TRAIN_STEP = "train.step"
    #: pulling the next batch from the data iterator (elastic runner loop)
    TRAIN_DATA_FETCH = "train.data_fetch"
    #: micro-batch forward+backward dispatch (fused value_and_grad program)
    TRAIN_FWD = "train.fwd"
    #: backward-side accumulation bookkeeping (grads were produced in fwd)
    TRAIN_BWD = "train.bwd"
    #: cross-slice gradient collapse at the gas boundary (DCN mean/onebit)
    TRAIN_GRAD_SYNC = "train.grad_sync"
    #: one explicit gradient-reduce collective dispatch (mode, axis,
    #: logical/wire bytes in args) — nested inside train.grad_sync
    COMM_REDUCE = "comm.reduce"
    #: gas-boundary optimizer apply (unscale/clip/step/recast dispatch)
    TRAIN_OPTIMIZER = "train.optimizer"
    #: a sanctioned device→host pull on the step path (label in args)
    TRAIN_HOST_SYNC = "train.host_sync"
    #: fused path: reshaping the host batch to [gas, ...] and placing it
    #: on the mesh (device_put)
    TRAIN_BATCH_PUT = "train.batch_put"
    #: fused path: the one call of the fused step program (dispatch time;
    #: a launch that blocks on the device shows here)
    TRAIN_DISPATCH = "train.dispatch"
    #: engine.save_checkpoint end-to-end (shard writes + manifest)
    CKPT_SAVE = "ckpt.save"
    #: the two-phase commit barrier + marker publish (multi-host protocol)
    CKPT_COMMIT = "ckpt.commit"
    #: engine.load_checkpoint end-to-end (consensus + fallback walk + load)
    CKPT_LOAD = "ckpt.load"
    #: ElasticTrainRunner.resume (sweep + consensus + checkpoint load)
    ELASTIC_RESUME = "elastic.resume"
    #: divergence rollback: reload verified tag + quarantine install
    ELASTIC_ROLLBACK = "elastic.rollback"
    #: one continuous-batching decode tick (all live slots, one token)
    SERVE_TICK = "serve.tick"
    #: the host blocked on the device for the tick's tokens (the one d2h
    #: pull per tick; nested in serve.tick)
    SERVE_PULL = "serve.pull"
    #: the gateway's per-row bookkeeping after a tick returned: append
    #: tokens, first-token stamps, finishes and releases (live in args)
    SERVE_HARVEST = "serve.harvest"
    #: one speculative draft/verify/accept round (nested in serve.tick;
    #: draft_k in args) — all live slots advance 1..draft_k+1 tokens
    SERVE_SPEC = "serve.spec"
    #: a request's wait in the admission queue: submit -> the instant its
    #: serve.admit opens (recorded; rid, priority, depth in args)
    SERVE_QUEUE = "serve.queue"
    #: admission of one request into a free slot (incl. prefill)
    SERVE_ADMIT = "serve.admit"
    #: allocating the fresh batch-1 cache a prefix build fills (bytes in
    #: args; not entered when a cache is continued, nor by an admission:
    #: its row cache is a temporary of its one program)
    SERVE_CACHE_ALLOC = "serve.cache_alloc"
    #: chunked prefill of a prompt/prefix through the fixed-width chunks
    #: (tokens = real, padded = the rows its passes computed, chunks in
    #: args; narrow = the width of a last pass narrower than chunk, else
    #: 0): around an admission's ONE launch (chunk loop, slot write and
    #: bind), or around a prefix build's launch a chunk
    SERVE_PREFILL = "serve.prefill"
    #: one prefill/extend chunk dispatch of a prefix build (index, pos,
    #: program in args)
    SERVE_PREFILL_CHUNK = "serve.prefill_chunk"
    #: under speculation, the draft's lockstep admission after the
    #: target's one launch: its slot write + pending-token seed
    SERVE_SLOT_WRITE = "serve.slot_write"
    #: an expert family's pair counts as of one harvested tick, cumulative
    #: since the server started (recorded, zero length; held, routed,
    #: visits and per_expert in args): counted on the device, pulled with the tokens
    SERVE_MOE_PAIRS = "serve.moe_pairs"
    #: the single-token sweep's counts as of one harvested tick, cumulative
    #: over plain ticks and by pool (recorded, zero length; in args, for
    #: ``full`` (whole rows) and, where the family has rings, ``window``:
    #: ``<pool>_tokens_live``, ``<pool>_tokens_streamed``, ``<pool>_calls``):
    #: counted on the host from the sweep's plan
    SERVE_KV_SWEEP = "serve.kv_sweep"
    #: a per-slot-state family's step counts as of one harvested tick,
    #: cumulative (recorded, zero length; in args ssm_rows_stepped: live
    #: slot x state-space layer of the ticks; scan_tokens_real and
    #: scan_tokens_padded: token x state-space layer of the admissions'
    #: chunk scans): counted on the device, pulled with the tokens
    SERVE_STATE_STEPS = "serve.state_steps"
    #: a selecting family's counts as of one harvested tick, cumulative over
    #: its single-token passes (recorded, zero length; in args eligible:
    #: cached tokens a full layer's query could have chosen from; selected:
    #: those it attended to; streamed: latent rows the sweep's copies moved
    #: for it; ring_live: ring cells a window layer's query saw): counted on
    #: the device, pulled with the tokens
    SERVE_SPARSE_SELECT = "serve.sparse_select"
    #: end of admission -> the tick that harvested the request's first
    #: token (recorded; rid in args)
    SERVE_FIRST_TOKEN = "serve.first_token"
    #: one admission's or one tick's launch ON THE DEVICE (recorded by the
    #: registry's watcher thread, ``CompiledProgramRegistry.watch``; every
    #: other span is the host's): from the later of the launch's dispatch
    #: and its predecessor's completion to its own completion, on this
    #: clock.  program and waited (seconds queued behind its predecessors)
    #: in args; of an admission also serve.prefill's tokens, padded, passes,
    #: wide, narrow and chunk, and slot.  Launches of one registry never
    #: overlap; what ran unwatched between two of them (release) falls to
    #: the later
    SERVE_DEVICE = "serve.device"
    #: restoring a tiered session's KV for a follow-up turn (gather or
    #: host rehydrate + remainder prefill)
    SERVE_READMIT = "serve.readmit"
    #: retiring a finished session's KV out of its slot (pool scatter or
    #: host park)
    SERVE_PARK = "serve.park"
    #: one remote prefill order end-to-end on a prefill worker (chunk loop
    #: through the fixed-width programs; trace_id/parent_span_id in args)
    SERVE_FLEET_PREFILL = "serve.fleet.prefill"
    #: publishing one KV page bundle + manifest into the spool (host bank
    #: pull + npz write + digest)
    SERVE_FLEET_PUBLISH = "serve.fleet.publish"
    #: decode-side bundle verification (digest + prefix agreement) and
    #: page rebuild before re-admission
    SERVE_FLEET_VERIFY = "serve.fleet.verify"
    #: one streamed-transport frame send (connect + retries + write) from
    #: a worker endpoint; flow/peer/bytes in args
    SERVE_TRANSPORT_SEND = "serve.transport.send"
    #: one MPMD pipeline step on a stage process: full 1F1B tick walk +
    #: grad reduce + optimizer apply (step/stage in args)
    PIPE_STEP = "pipe.step"
    #: one 1F1B schedule tick (fwd, bwd or idle op in args)
    PIPE_TICK = "pipe.tick"
    #: blocking receive of one boundary activation/grad frame (kind,
    #: micro, from_stage, spooled in args)
    PIPE_EXCHANGE_RECV = "pipe.exchange_recv"
    #: shared-grad star reduce at the step boundary (stage 0 sums stage
    #: contributions in stage order and broadcasts the total)
    PIPE_GRAD_REDUCE = "pipe.grad_reduce"
    #: quiesce-to-resume window on a surviving stage (epoch bump observed
    #: → consensus resume complete)
    PIPE_REQUIESCE = "pipe.requiesce"


#: every registered span name, as a frozenset of strings
SPAN_NAMES = frozenset(
    v for k, v in vars(SpanName).items()
    if not k.startswith("_") and isinstance(v, str))


@dataclass(frozen=True)
class SpanRecord:
    """One finished span."""

    name: str
    t0: float        # tracer clock (monotonic seconds) at entry
    dur: float       # seconds
    tid: int         # thread ident
    thread: str      # thread name (Perfetto track label)
    depth: int       # nesting depth within this thread (0 = top level)
    args: Optional[Dict[str, Any]] = None

    @property
    def wait(self) -> bool:
        """A recorded wait (``Tracer.record``), not a phase of a thread."""
        return self.thread == WAIT_THREAD


def _check_registered(name: str) -> None:
    if name not in SPAN_NAMES:
        raise ValueError(
            f"span name '{name}' is not registered in SpanName "
            "(telemetry/spans.py) — register it (and its "
            "docs/telemetry.md row) first")


def _device_barrier() -> None:
    """Block until all dispatched JAX work finishes (calibration mode)."""
    try:
        import jax

        jax.block_until_ready(jax.device_put(0))
    except Exception:  # pragma: no cover  # dslint: disable=swallowed-exception — calibration barrier is best-effort off-device
        pass


class _Span:
    """A live span of an enabled tracer: the profiler annotation around
    the recorded interval."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_depth", "_note")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._args = args or None
        self._note = TraceAnnotation(name, **args)

    def __enter__(self) -> "_Span":
        tr = self._tracer
        if tr.synced:
            tr._sync()
        self._depth = tr._enter_thread()
        self._t0 = tr._clock()
        # last, so that nothing that can raise runs with the TraceMe open
        self._note.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        tr = self._tracer
        tr._exit_thread()
        try:
            if tr.synced:
                tr._sync()
            dur = tr._clock() - self._t0
            tr._record(self._name, self._t0, dur, self._depth, self._args)
        finally:
            self._note.__exit__(*exc)
        return False


class Tracer:
    """Collects spans; thread-safe, bounded, cheap to leave disabled.

    Args:
      enabled: keep :class:`SpanRecord` rows.  A disabled tracer's
        :meth:`span` is the bare profiler annotation and :meth:`record`
        keeps nothing.
      capacity: raw records kept for export; past it new records are
        DROPPED (counted in :attr:`dropped`) — the per-name aggregates keep
        counting, so breakdown logs and inventories stay exact while the
        exportable timeline stays bounded.
      synced: block on a device barrier at span entry and exit
        (calibration mode: spans then measure execution, not dispatch).
        Each barrier is noted on ``sync_registry`` as a ``span.sync`` host
        sync, so calibration runs are visible to the compile/host-sync
        discipline gates.
      sync_registry: a ``CompiledProgramRegistry`` (duck-typed
        ``note_host_sync``) the synced mode reports its barriers to.
    """

    def __init__(self, enabled: bool = True, capacity: int = 65536,
                 synced: bool = False, sync_registry: Any = None,
                 name: str = "run"):
        self.name = name
        self.enabled = bool(enabled)
        self.capacity = int(capacity)
        self.synced = bool(synced)
        self._sync_registry = sync_registry
        self._clock = time.monotonic
        self._lock = TrackedLock(LockName.TELEMETRY_SPANS)
        self._records: List[SpanRecord] = []
        self._agg: Dict[str, Tuple[int, float]] = {}
        self._local = threading.local()
        self.dropped = 0

    # ------------------------------------------------------------- tracing
    def span(self, name: str, **args: Any):
        """Context manager timing one phase.  ``name`` must be a
        registered :class:`SpanName`; extra kwargs land in the exported
        trace event's ``args`` and, as stats, on the annotation an
        attached profiler sees."""
        _check_registered(name)
        if not self.enabled:
            return TraceAnnotation(name, **args)
        return _Span(self, name, args)

    def record(self, name: str, t0: float, dur: float, **args: Any) -> None:
        """Keep a finished span whose start was noted earlier: ``t0`` on
        this tracer's clock (``time.monotonic``), ``dur`` seconds.  For
        waits that begin on one thread or tick and end on another.  The
        row lands on the synthetic :data:`WAIT_THREAD` line at depth 0, not
        on the caller's: a wait overlaps the calling thread's phases and
        other waits, and is nobody's child.  Nothing is kept, and no
        profiler annotation written, when disabled."""
        _check_registered(name)
        if self.enabled:
            self._record(name, t0, dur, 0, args or None, wait=True)

    def _enter_thread(self) -> int:
        depth = getattr(self._local, "depth", 0)
        self._local.depth = depth + 1
        return depth

    def _exit_thread(self) -> None:
        self._local.depth = getattr(self._local, "depth", 1) - 1

    def _sync(self) -> None:
        _device_barrier()
        if self._sync_registry is not None:
            self._sync_registry.note_host_sync("span.sync")

    def _record(self, name: str, t0: float, dur: float, depth: int,
                args: Optional[Dict[str, Any]], wait: bool = False) -> None:
        if wait:
            tid, thread = _WAIT_TID, WAIT_THREAD
        else:
            th = threading.current_thread()
            tid, thread = th.ident or 0, th.name
        with self._lock:
            count, total = self._agg.get(name, (0, 0.0))
            self._agg[name] = (count + 1, total + dur)
            if len(self._records) >= self.capacity:
                self.dropped += 1
                return
            self._records.append(SpanRecord(
                name=name, t0=t0, dur=dur, tid=tid, thread=thread,
                depth=depth, args=args))

    # ------------------------------------------------------------- queries
    def spans(self) -> List[SpanRecord]:
        """All recorded spans, in completion order."""
        with self._lock:
            return list(self._records)

    def aggregates(self) -> Dict[str, Dict[str, float]]:
        """Per-name ``{"count": n, "total_s": s}`` — exact even when the
        raw record list hit capacity."""
        with self._lock:
            return {name: {"count": c, "total_s": t}
                    for name, (c, t) in sorted(self._agg.items())}

    def span_inventory(self) -> List[str]:
        """Sorted distinct span names observed (the pinned inventory)."""
        with self._lock:
            return sorted(self._agg)

    def clear(self) -> None:
        with self._lock:
            self._records.clear()
            self._agg.clear()
            self.dropped = 0
