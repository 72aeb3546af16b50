"""The serving gateway: an async request scheduler over the slot batcher.

``submit()`` is thread-safe and non-blocking: requests land in a bounded
priority queue (FIFO within a priority class) and a daemon scheduler
thread — the same stdlib ``threading`` idiom as the async checkpoint
engine — runs the serve loop:

1. expire queued requests whose deadline already passed;
2. admit while slots are free: pop the best queued request, prefill its
   prompt (through the LRU prefix pool when it declares a shared prefix)
   into a freed slot;
3. one continuous-batching decode tick for every live slot, with exactly
   one tick in flight: LAUNCH tick n+1 from the device-resident slot
   state, then PULL tick n's tokens (the device has been running n+1
   since) and HARVEST them: finish rows that hit eos / budget / deadline
   / cancellation, and free their slots for step 2 of the next
   iteration.  A tick carries the ``row -> request`` snapshot of its
   launch and its harvest walks that snapshot: a row that finished at
   harvest n was still live in tick n+1 on the device (its release is one
   tick late), and tick n+1's token for it is dropped, whoever holds the
   row by then.  When the last row finishes the tick in flight is pulled
   before the loop waits; an idle server launches nothing.

Every decision lands in the supervision ``EventJournal`` (``serve.*``
kinds) and in :class:`ServingMetrics`; the ``serve.request`` /
``serve.admit`` / ``serve.decode_tick`` fault points make the loop a chaos
surface (slow clients, failed admissions, wedged ticks) tests drive
without monkeypatching.
"""

from __future__ import annotations

import heapq
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

import jax

from ..moe.held_experts import read_pair_counts
from ..runtime.supervision.events import EventJournal, EventKind
from ..telemetry.metrics import MetricName, lock_watch_metrics
from ..telemetry.propagate import mint_context
from ..telemetry.spans import SpanName, Tracer
from ..utils import fault_injection
from ..utils.compile_watch import CompileWatch
from ..utils.lock_watch import LockName, TrackedRLock, install_journal
from ..utils.logging import logger
from .batcher import PrefixEntry, SlotBatcher
from .config import ServingConfig
from .metrics import ServingMetrics
from .overload import AdmissionController, DegradationLadder, ShedDecision
from .paging import SessionPager, cache_bank_bytes
from .request import (QueueFullError, RequestCancelled, RequestFailed,
                      RequestHandle, RequestShed, RequestState,
                      RequestTimedOut, ServeRequest)


class _PooledPrefix:
    """One pooled shared prefix.  Unpaged gateways hold the batch-1
    cache (``entry``) directly; paged ones hold a pool block ``table``
    instead — N conversations over one system prompt then share the
    prefix's *blocks* (refcounted, copy-on-write), not just the whole
    pooled cache."""

    def __init__(self, entry: Optional[PrefixEntry] = None,
                 table=None, length: int = 0, nbytes: int = 0):
        self.entry = entry
        self.table = table
        self.length = int(length if entry is None else entry.length)
        self.nbytes = int(nbytes)
        self.last_used = time.monotonic()


class _InFlight(NamedTuple):
    """One launched, un-pulled decode tick."""

    #: what ``SlotBatcher.launch`` returned, still on the device
    pending: Any
    #: ``row -> request`` as bound at the launch: the only rows this
    #: tick's tokens may be handed to
    rows: List[Tuple[int, ServeRequest]]
    #: proposals per row of the round (0: a plain tick), at the launch
    draft_k: int
    #: launched while the tick before it was un-pulled
    overlapped: bool


class ServingGateway:
    """Continuous-batching front half over one :class:`InferenceEngine`."""

    def __init__(self, engine, config=None, journal: Optional[EventJournal]
                 = None, autostart: bool = True,
                 tracer: Optional[Tracer] = None, draft=None):
        if config is None:
            config = ServingConfig()
        elif isinstance(config, dict):
            config = ServingConfig.from_dict(config)
        self.config = config
        #: telemetry tracer (shared with the batcher): the serve.* spans
        #: of docs/telemetry.md, per-request ones keyed by ``rid``.
        #: Callers pass one to keep records; the default keeps none and
        #: still annotates an attached profiler's trace.
        self.tracer = tracer if tracer is not None else Tracer(
            enabled=False, name="serving")
        #: speculative decoding in the tick loop (docs/serving.md
        #: "Speculative tick"); ``draft`` is the proposal model —
        #: resolved/validated by the batcher
        self._spec = bool(config.speculative_config.enabled)
        self._batcher = SlotBatcher(engine, config, tracer=self.tracer,
                                    draft=draft)
        self._journal = journal
        self.metrics = ServingMetrics()
        self.metrics.kv_pool_share = \
            self._batcher.sweep_plan.share_of_one_geometry
        #: paged KV + session tiering (serving/paging.py) — None keeps
        #: the PR 6 slot-pinned behavior byte for byte
        self._pager: Optional[SessionPager] = None
        if config.paging_config.enabled:
            self._pager = SessionPager(self._batcher, config.paging_config,
                                       emit=self._emit,
                                       metrics=self.metrics)
        # compile-discipline gate: serving programs are shape-stable by
        # construction, so each program's FIRST compile is warmup and any
        # later one is a regression — journaled as perf.recompile and
        # surfaced through metrics.recompiles / snapshot().  The
        # degradation ladder's rungs switch between REGISTERED programs
        # (wide-chunk / shrunk-draft_k / pause sets), so degrading under
        # load never trips this gate.
        self._watch = CompileWatch(self._batcher.registry, journal=journal,
                                   first_compile_free=True).open()
        if config.warm_start:
            # every serving program (both chunk widths, every spec
            # ladder level) compiles NOW: an overload burst must never
            # stall behind a first XLA compile, least of all when a
            # degradation rung engages mid-storm
            self._batcher.prewarm()
        #: overload robustness (docs/serving.md "Overload & admission"):
        #: SLO-driven admission shedding + the hysteretic degradation
        #: ladder, both disabled unless serving.overload.enabled
        self._overload: Optional[AdmissionController] = None
        self._ladder: Optional[DegradationLadder] = None
        if config.overload_config.enabled:
            self._overload = AdmissionController(config.overload_config,
                                                 config.queue_capacity)
            rungs = ["max_tokens", "chunk_widen"]
            if self._spec:
                rungs += ["draft_k", "spec_pause"]
            self._ladder = DegradationLadder(config.overload_config,
                                            available=rungs)
        # RLock: submit() rejects (journal + depth read) while already
        # holding the condition for the queue-capacity check.  Tracked at
        # SERVE_GATEWAY (outermost in LOCK_ORDER): the scheduler holds it
        # while touching the pager, request handles, metrics, and the
        # journal — the lock-order watchdog proves those nestings stay
        # acyclic on every e2e run.
        self._cond = threading.Condition(TrackedRLock(LockName.SERVE_GATEWAY))
        if journal is not None:
            # route concurrency.lock_cycle / .contention to this run's
            # journal (process-global: last journal-carrying gateway wins)
            install_journal(journal)
        self._queue: list = []               # heap of (sort_key, request)
        self._active: Dict[int, ServeRequest] = {}   # row -> request
        self._free_rows = list(range(config.slots))
        self._prefixes: "OrderedDict[bytes, _PooledPrefix]" = OrderedDict()
        self._seq = 0
        self._ticks = 0
        #: the tick in flight (scheduler thread only)
        self._in_flight: Optional[_InFlight] = None
        self._closed = False
        self._stopped = threading.Event()
        self._base_key = jax.random.PRNGKey(int(config.seed))
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="serving-gateway")
        if autostart:
            self._thread.start()

    # ------------------------------------------------------------- public

    def start(self) -> None:
        """Start the scheduler thread (for gateways built with
        ``autostart=False`` — deterministic queue-pressure tests)."""
        if not self._thread.is_alive():
            self._thread.start()

    def submit(self, tokens, *, max_new_tokens: Optional[int] = None,
               priority: int = 0, deadline_s: Optional[float] = None,
               seed: Optional[int] = None, do_sample: bool = False,
               temperature: float = 1.0,
               eos_token_id: Optional[int] = None,
               prefix_len: int = 0,
               session_id: Optional[str] = None) -> RequestHandle:
        """Enqueue one generation request; returns immediately with a
        :class:`RequestHandle`.

        ``tokens``: the prompt [S] (or [1, S]) int32.  ``prefix_len``
        marks the leading tokens as a shared prefix (system prompt):
        requests agreeing on it share one pooled prefill through
        zero-copy ``fork`` semantics.  ``seed`` pins the request's
        sampling key; unset, the gateway derives one from its seed
        sequence — two identical sampled requests do NOT return identical
        replies unless they pin the same seed.

        ``session_id`` (paged gateways only) names the conversation:
        ``tokens`` must then be the FULL history (previous prompt + reply
        + the new turn).  The finished conversation's KV is retained
        (block pool → host RAM → disk) and the follow-up turn re-admits
        it, prefilling only the new tokens — ``serve.readmit`` journals
        the hit and its latency.
        """
        cfg = self.config
        if session_id is not None and self._pager is None:
            raise ValueError(
                "submit(session_id=...) needs session tiering — enable "
                'serving config {"paging": {"enabled": true}}')
        seq = self._seq_next()
        rid = f"req-{seq}"
        fault_injection.fire("serve.request", request_id=rid)
        tokens = np.asarray(tokens, np.int32)
        if tokens.ndim == 2 and tokens.shape[0] == 1:
            tokens = tokens[0]
        if tokens.ndim != 1 or tokens.shape[0] < 1:
            raise ValueError(
                f"submit wants a [S>=1] prompt, got shape {tokens.shape}")
        n_new = int(max_new_tokens if max_new_tokens is not None
                    else cfg.default_max_new_tokens)
        if n_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {n_new}")
        if not 0 <= prefix_len < tokens.shape[0]:
            raise ValueError(
                f"prefix_len {prefix_len} must be in [0, prompt_len"
                f"={tokens.shape[0]})")
        if prefix_len and cfg.max_cached_prefixes > 0:
            self._batcher.refuse("prefix")
        handle = RequestHandle(rid)
        # every request is a trace root: workers stitch their spans to it
        ctx = mint_context()
        # a speculative round may write draft_k positions past the last
        # emission (rejected overshoot K/V) — the whole overshoot must
        # fit the slot, or edge writes would clamp and corrupt
        margin = self._batcher.spec_overshoot
        if tokens.shape[0] + n_new + margin > self._batcher.max_len:
            self._reject(rid, handle, "too_long")
            raise ValueError(
                f"prompt ({tokens.shape[0]}) + max_new_tokens ({n_new})"
                + (f" + speculative overshoot ({margin})" if margin else "")
                + f" exceeds the {self._batcher.max_len}-token slot; raise "
                "serving.max_len or shorten the request")
        deadline_s = deadline_s if deadline_s is not None \
            else cfg.default_deadline_s
        req = ServeRequest(
            rid=rid, seq=seq, tokens=tokens, prefix_len=int(prefix_len),
            max_new_tokens=n_new, priority=int(priority),
            deadline=(handle.t_submit + deadline_s
                      if deadline_s is not None else None),
            # the jax key is derived at ADMISSION (scheduler thread): a
            # shed submission must never pay a device dispatch
            key=int(seed) if seed is not None else seq,
            greedy=not do_sample, temperature=float(temperature),
            eos_token_id=(eos_token_id if eos_token_id is not None
                          else cfg.eos_token_id),
            handle=handle,
            session_id=str(session_id) if session_id is not None else None)
        self.metrics.count("submitted")
        decision = None
        full = False
        with self._cond:
            if self._closed:
                self._reject(rid, handle, "gateway_closed")
                raise QueueFullError(f"gateway is shut down ({rid})")
            if self._overload is not None:
                # shed BEFORE the heap: the request is never accepted,
                # so the lost == 0 invariant over accepted requests is
                # untouched
                decision = self._overload.should_shed(req.priority,
                                                      len(self._queue))
            if decision is None:
                full = len(self._queue) >= cfg.queue_capacity
            if decision is None and not full:
                heapq.heappush(self._queue, (req.sort_key(), req))
                self._emit(EventKind.SERVE_REQUEST, request_id=rid,
                           prompt_len=req.prompt_len, max_new_tokens=n_new,
                           priority=req.priority,
                           queue_depth=len(self._queue),
                           t_submit=time.time(), trace=ctx.fields())
                self._cond.notify_all()
        if decision is not None:
            # journal + handle bookkeeping OUTSIDE the scheduler's lock:
            # under an open-loop storm sheds/rejects are the common case,
            # and saying no must never contend with the decode loop
            self._shed(rid, handle, req.priority, decision)
            raise RequestShed(
                f"{rid} shed ({decision.reason}, class "
                f"{decision.cls.name})", reason=decision.reason,
                cls=decision.cls.name)
        if full:
            self._reject(rid, handle, "queue_full")
            raise QueueFullError(
                f"admission queue full ({cfg.queue_capacity}); "
                f"rejected {rid}")
        return handle

    def cancel(self, handle: RequestHandle) -> bool:
        """Convenience mirror of ``handle.cancel()`` (honored at the next
        tick boundary)."""
        ok = handle.cancel()
        with self._cond:
            self._cond.notify_all()
        return ok

    def snapshot(self) -> dict:
        """Metrics snapshot + live scheduler state (queue depth, active
        slots, pooled prefixes, compile counts)."""
        self._pull_compile_stats()
        with self._cond:
            depth, active = len(self._queue), len(self._active)
            prefixes = len(self._prefixes)
        snap = self.metrics.snapshot(queue_depth=depth)
        snap.update(active_slots=active, slots=self.config.slots,
                    cached_prefixes=prefixes,
                    compile_counts=self._batcher.compile_counts())
        if self._pager is not None:
            snap["paging"] = self._pager.stats()
        return snap

    def probe_logits(self, prompts, ticks: int):
        """``(replies, logits)`` of chunked prefill and ``ticks`` greedy
        decode ticks through this gateway's own slot path (its compiled
        programs, its slot cache): for each prompt the tokens it replied
        and the float32 logits ``[1 + ticks, padded vocab]`` after the
        prefill and after each tick.  The public entry for checking the
        serving path's numerics against a reference.

        It takes the slots over (rows ``0..len(prompts)-1``, whatever
        they held is released), so it is for a gateway whose scheduler is
        not running: after :meth:`shutdown`, or before :meth:`start`."""
        if self._thread.is_alive():
            raise RuntimeError(
                "probe_logits drives the slot batch itself: shut the "
                "gateway down first")
        if self._spec:
            raise NotImplementedError(
                "probe_logits reads one token per tick; a speculative "
                "gateway emits windows")
        b = self._batcher
        n = len(prompts)
        if n > b.slots:
            raise ValueError(f"{n} prompts for {b.slots} slots")
        for row in range(b.slots):
            b.release(row)
        for row, p in enumerate(prompts):
            b.admit(row, np.asarray(p, np.int32), jax.random.PRNGKey(0),
                    True, 1.0)
        frontier = lambda: np.asarray(b._last[:n], np.float32)
        logits, replies = [frontier()], []
        for _ in range(ticks):
            replies.append(b.tick()[:n])
            logits.append(frontier())
        for row in range(n):
            b.release(row)
        return ([[int(t[i]) for t in replies] for i in range(n)],
                [np.stack([l[i] for l in logits]) for i in range(n)])

    def attach_metrics(self, sampler) -> None:
        """Stream this gateway's gauges through a telemetry
        :class:`~deepspeed_tpu.telemetry.metrics.MetricsSampler`: every
        sample row then carries queue depth, slot occupancy, TTFT
        percentiles, and decode tokens/s next to the train-side fields.
        Tracked-lock contention/hold stats ride along (the gateway is the
        most lock-dense owner, so it carries the concurrency feed)."""
        sampler.attach_source(self._metrics_source)
        sampler.attach_source(lock_watch_metrics)

    def _metrics_source(self) -> dict:
        snap = self.snapshot()
        out = {
            MetricName.SERVE_QUEUE_DEPTH: snap["queue_depth"],
            MetricName.SERVE_OCCUPANCY: snap["slot_occupancy"],
            MetricName.SERVE_LIVE_BLOCK_SHARE: snap["live_block_share"],
            MetricName.SERVE_KV_STREAMED_OVER_LIVE:
                snap["streamed_over_live"],
            MetricName.SERVE_KV_POOL_SHARE:
                snap["kv_pool_share_of_one_geometry"],
            MetricName.SERVE_KV_WINDOW_STREAMED_SHARE:
                snap["kv_window_streamed_share"],
            MetricName.SERVE_TOKENS_PER_S: snap["tokens_per_s"],
            MetricName.SERVE_TTFT_S: self.metrics.ttft.snapshot(),
        }
        if self._pager is not None:
            out[MetricName.SERVE_HBM_BYTES_PER_CONVERSATION] = \
                snap["hbm_bytes_per_conversation"]
            out[MetricName.SERVE_READMIT_S] = \
                self.metrics.readmit.snapshot()
        if self._spec:
            out[MetricName.SERVE_SPEC_ACCEPT_RATE] = \
                self.metrics.spec_accept_rate.snapshot()
            out[MetricName.SERVE_SPEC_TOKENS_PER_TICK] = \
                self.metrics.spec_tokens_per_tick.snapshot()
        if self._overload is not None:
            out[MetricName.SERVE_SHED_TOTAL] = snap["shed"]
            out[MetricName.SERVE_DEGRADE_RUNGS] = snap["degrade_rungs"]
        return out

    def _pull_compile_stats(self) -> None:
        """Fold the CompileWatch's view into the metrics: new post-warmup
        recompiles (also journaled as ``perf.recompile`` by the watch) and
        the tick loop's sanctioned host-sync total."""
        new = self._watch.check()
        if new:
            self.metrics.count("recompiles", len(new))
        self.metrics.set_value("host_syncs", self._watch.total_host_syncs())

    def shutdown(self, drain: bool = True,
                 timeout: Optional[float] = None) -> None:
        """Stop accepting work; optionally serve out the backlog first,
        then stop the scheduler thread.  Requests still pending after a
        non-drain shutdown fail with :class:`RequestFailed`."""
        deadline = time.monotonic() + timeout if timeout is not None else None
        with self._cond:
            self._closed = True
            if not drain:
                self._fail_pending(RequestFailed("gateway shut down"))
            self._cond.notify_all()
        if self._thread.is_alive():
            while True:
                with self._cond:
                    idle = not self._queue and not self._active
                if idle or not drain:
                    break
                if deadline is not None and time.monotonic() > deadline:
                    break
                time.sleep(0.01)
            self._stopped.set()
            with self._cond:
                self._cond.notify_all()
            # bounded join: honor what is left of the caller's deadline
            # (a wedged tick must not hang shutdown forever either way)
            join_s = 30.0 if deadline is None \
                else max(0.1, deadline - time.monotonic())
            self._thread.join(timeout=join_s)
            if self._thread.is_alive():
                logger.warning("[serving] scheduler thread did not stop "
                               f"within {join_s:.1f}s")
        # the last launches' ``serve.device`` spans, for whoever reads the
        # tracer next, and the watcher's end (nothing while the tracer is off)
        self._batcher.registry.watch_stop(timeout=10.0)
        self._pull_compile_stats()
        self._watch.close()   # journals perf.host_sync totals

    def __enter__(self) -> "ServingGateway":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown(drain=exc == (None, None, None))

    # ------------------------------------------------------------ internal

    def _seq_next(self) -> int:
        with self._cond:
            self._seq += 1
            return self._seq

    def _emit(self, kind: str, **fields) -> None:
        if self._journal is not None:
            self._journal.emit(kind, **fields)

    def _reject(self, rid: str, handle: RequestHandle, reason: str) -> None:
        self.metrics.count("rejected")
        with self._cond:
            depth = len(self._queue)
        self._emit(EventKind.SERVE_REJECT, request_id=rid, reason=reason,
                   queue_depth=depth)
        handle._finish(RequestState.REJECTED,
                       error=QueueFullError(f"{rid} rejected: {reason}"))

    def _shed(self, rid: str, handle: RequestHandle, priority: int,
              d: ShedDecision) -> None:
        """Journals the decision made under the lock (``d`` carries the
        depth the check saw); runs free of the scheduler cond so shed
        storms cost the decode loop nothing.  Not literally lock-free:
        it takes serve.metrics, journal.emit, and serve.request — all
        ranked below serve.gateway in LOCK_ORDER, so the path stays
        legal even from callers holding the cond.  The journal emit is
        one ``os.write`` per record: a shed storm from N submitter
        threads can never tear lines."""
        self.metrics.count("shed")
        self.metrics.count("rejected")
        self._emit(EventKind.SERVE_SHED, request_id=rid,
                   priority=priority, cls=d.cls.name,
                   reason=d.reason, phase=d.phase,
                   est_ttft_ms=round(d.est_ttft_ms, 3), slo_ms=d.slo_ms,
                   queue_depth=d.queue_depth)
        handle._finish(RequestState.REJECTED, error=RequestShed(
            f"{rid} shed: {d.reason}", reason=d.reason, cls=d.cls.name))

    def _fail_pending(self, error: Exception) -> None:
        """cond must be held."""
        while self._queue:
            _, req = heapq.heappop(self._queue)
            self.metrics.count("failed")
            req.handle._finish(RequestState.FAILED, error=error)
        for row, req in list(self._active.items()):
            self.metrics.count("failed")
            req.handle._finish(RequestState.FAILED, error=error)
            self._release_row(row)

    def _release_row(self, row: int) -> None:
        self._active.pop(row, None)
        self._free_rows.append(row)
        self._batcher.release(row)
        if self._pager is not None:
            # no-op when a retire already took the ledger; frees the
            # block references of cancelled/timed-out/failed rows
            self._pager.row_released(row)

    # ---------------------------------------------------------- scheduler

    def _loop(self) -> None:
        try:
            while not self._stopped.is_set():
                self._expire_queued()
                # the ladder steps every iteration — idle ones included,
                # which is what lets rungs RELEASE once the burst drains
                self._overload_step()
                self._admit_ready()
                self._sweep_prefixes()
                if self._active or self._in_flight is not None:
                    self._decode_tick()
                else:
                    with self._cond:
                        if self._stopped.is_set():
                            break
                        if not self._queue:
                            self._cond.wait(self.config.idle_wait_s)
            if self._in_flight is not None:
                self._decode_tick()
        except BaseException as e:  # the loop dying must fail loudly,
            # not leave every caller blocked on a handle forever
            logger.exception(f"[serving] scheduler loop died: {e}")
            self._discard_in_flight()
            with self._cond:
                self._closed = True
                self._fail_pending(RequestFailed(f"scheduler loop died: {e}"))
            raise

    def _overload_step(self) -> None:
        """One degradation-ladder evaluation: queue pressure + the
        dominant decomposed-TTFT phase pick the rung; each transition is
        applied to the batcher/admission path and journaled."""
        if self._ladder is None:
            return
        with self._cond:
            depth = len(self._queue)
        pressure = depth / max(1, self.config.queue_capacity)
        phase = self._overload.dominant_phase(depth)
        for rung, action, level in self._ladder.step(pressure, phase):
            self._apply_rung(rung)
            self.metrics.set_value("degrade_rungs", self._ladder.bitmask())
            self.metrics.count("degrade_transitions")
            self._emit(EventKind.SERVE_DEGRADE, rung=rung, action=action,
                       phase=phase, pressure=round(pressure, 4),
                       dwell_ticks=self._ladder.dwell_ticks[rung],
                       level=level)

    def _apply_rung(self, rung: str) -> None:
        """Reconcile the batcher with the ladder's engaged-rung state
        (the ``max_tokens`` rung needs no batcher change — admissions
        read it directly)."""
        eng = self._ladder.engaged
        if rung in ("draft_k", "spec_pause"):
            self._batcher.set_spec_level(
                2 if eng.get("spec_pause") else
                (1 if eng.get("draft_k") else 0))
        elif rung == "chunk_widen":
            self._batcher.set_chunk_wide(bool(eng.get("chunk_widen")))

    def _expire_queued(self) -> None:
        now = time.monotonic()
        with self._cond:
            keep = []
            expired = []
            while self._queue:
                item = heapq.heappop(self._queue)
                req = item[1]
                if req.handle.cancel_requested:
                    expired.append((req, "cancel"))
                elif req.deadline is not None and now > req.deadline:
                    expired.append((req, "deadline"))
                else:
                    keep.append(item)
            for item in keep:
                heapq.heappush(self._queue, item)
        for req, why in expired:
            if why == "cancel":
                self.metrics.count("cancelled")
                self._emit(EventKind.SERVE_CANCEL, request_id=req.rid,
                           slot=None, tokens_out=0)
                req.handle._finish(
                    RequestState.CANCELLED,
                    error=RequestCancelled(f"{req.rid} cancelled in queue"))
            else:
                self.metrics.count("timeouts")
                self._emit(EventKind.SERVE_TIMEOUT, request_id=req.rid,
                           slot=None,
                           deadline_s=req.deadline - req.handle.t_submit,
                           tokens_out=0, queued=True)
                req.handle._finish(
                    RequestState.TIMEOUT,
                    error=RequestTimedOut(
                        f"{req.rid} deadline passed while queued"))

    def _admit_ready(self) -> None:
        while True:
            with self._cond:
                if not self._queue or not self._free_rows:
                    return
                _, req = heapq.heappop(self._queue)
                row = self._free_rows.pop(0)
                left = len(self._queue)
            try:
                self._admit_one(row, req, left)
            except BaseException as e:
                with self._cond:
                    self._active.pop(row, None)
                    self._free_rows.append(row)
                if self._pager is not None:
                    self._pager.row_released(row)
                self.metrics.count("failed")
                self._emit(EventKind.SERVE_REJECT, request_id=req.rid,
                           reason=f"admission_error: {e}", queue_depth=0)
                err = RequestFailed(f"{req.rid} admission failed: {e}")
                err.__cause__ = e
                req.handle._finish(RequestState.FAILED, error=err)

    def _admit_one(self, row: int, req: ServeRequest, left: int) -> None:
        """``left``: the queue length the pop left behind."""
        if self.tracer.enabled:
            t_submit = req.handle.t_submit
            self.tracer.record(SpanName.SERVE_QUEUE, t_submit,
                               time.monotonic() - t_submit, rid=req.rid,
                               priority=req.priority, depth=left)
        with self.tracer.span(SpanName.SERVE_ADMIT, rid=req.rid, slot=row,
                              prompt_len=req.prompt_len):
            self._admit_one_inner(row, req)

    def _admit_one_inner(self, row: int, req: ServeRequest) -> None:
        launches = self._batcher.admit_launches
        prefix_hit = False
        prefix = None
        readmit = None
        shared_prefix: Optional[_PooledPrefix] = None
        t0 = time.monotonic()
        if req.session_id is not None:
            readmit = self._try_readmit(req)
        if readmit is not None:
            # the tier copy IS a prefix of the new turn's full history:
            # re-admission rides the exact prefix-resume admission path.
            # The row ledger takes the table NOW so a faulted admission
            # frees the blocks through row_released instead of leaking
            prefix = PrefixEntry(cache=readmit.cache, length=readmit.reused)
            self._pager.begin_row(row, req.session_id, readmit.reused,
                                  table=readmit.table,
                                  immutable_upto=readmit.immutable_upto)
        elif req.prefix_len > 0 and self.config.max_cached_prefixes > 0:
            key = np.asarray(req.tokens[:req.prefix_len]).tobytes()
            with self._cond:
                pooled = self._prefixes.get(key)
            if pooled is not None:
                prefix_hit = True
                self.metrics.count("prefix_hits")
                pooled.last_used = time.monotonic()
                with self._cond:
                    self._prefixes.move_to_end(key)
                if pooled.table is not None:
                    prefix = PrefixEntry(
                        cache=self._pager.gather_prefix(pooled.table,
                                                        pooled.length),
                        length=pooled.length)
                    shared_prefix = pooled
                else:
                    prefix = pooled.entry
            else:
                entry = self._batcher.build_prefix(req.tokens[:req.prefix_len])
                self.metrics.count("prefix_builds")
                table = None
                if self._pager is not None:
                    # paged pool: hold the prefix as refcounted blocks —
                    # the batch-1 build cache is dropped, sessions share
                    # the blocks copy-on-write
                    table = self._pager.pool_prefix(entry.cache,
                                                    entry.length)
                pooled = _PooledPrefix(
                    entry=entry if table is None else None, table=table,
                    length=entry.length,
                    nbytes=(len(table) * self._pager.pool.block_bytes
                            if table is not None
                            else cache_bank_bytes(entry.cache)))
                with self._cond:
                    while len(self._prefixes) >= self.config.max_cached_prefixes:
                        self._evict_prefix(reason="lru")
                    self._prefixes[key] = pooled
                prefix = entry
                if table is not None:
                    shared_prefix = pooled
        elif req.prefix_len > 0:
            # pool disabled: the prefix is just part of the prompt
            prefix = None
        # degradation: the max_tokens rung caps the reply budget of NEW
        # admissions only — an accepted request is degraded (it finishes
        # sooner), never dropped
        if self._ladder is not None and self._ladder.engaged.get(
                "max_tokens"):
            req.max_new_tokens = min(
                req.max_new_tokens,
                self.config.overload_config.max_new_tokens_cap)
        # fires between the tier/prefix restore and the slot prefill, so
        # chaos covers the widest admission window (a faulted admission
        # after a readmit must free the re-admitted blocks via the ledger)
        fault_injection.fire("serve.admit", request_id=req.rid, slot=row)
        t_prefill = time.monotonic()
        # the per-request PRNG key is derived inside the admission's one
        # program: identical fold, identical sampling, no launch of its
        # own on the scheduler thread
        req.frontier = self._batcher.admit(row, req.tokens, self._base_key,
                                           req.greedy, req.temperature,
                                           prefix=prefix, fold=req.key)
        if self._overload is not None:
            self._overload.note_prefill(
                (time.monotonic() - t_prefill) * 1e3)
        if req.session_id is not None:
            self._begin_session_row(row, req, readmit, shared_prefix, t0)
        req.handle.t_admit = time.monotonic()
        req.handle.state = RequestState.DECODING
        queued_ms = round((req.handle.t_admit
                           - req.handle.t_submit) * 1e3, 3)
        with self._cond:
            self._active[row] = req
            depth = len(self._queue)
        if self._overload is not None:
            self._overload.note_admit(queued_ms, depth)
        self._emit(EventKind.SERVE_ADMIT, request_id=req.rid, slot=row,
                   queued_ms=queued_ms, prefix_hit=prefix_hit)
        self.metrics.count("admitted")
        self.metrics.count("admit_launches",
                           self._batcher.admit_launches - launches)

    def _try_readmit(self, req: ServeRequest):
        """Attempt the tiered-KV restore for a session follow-up; any
        failure (fault point, corrupt park, device error) costs a full
        re-prefill, never the request."""
        with self.tracer.span(SpanName.SERVE_READMIT,
                              session=req.session_id):
            try:
                return self._pager.readmit(req.session_id, req.tokens)
            except Exception as e:
                logger.warning(
                    f"[serving] readmit of session {req.session_id!r} "
                    f"failed ({e}); falling back to a full re-prefill")
                self._pager.drop_session(req.session_id,
                                         reason="readmit_failed")
                return None

    def _begin_session_row(self, row: int, req: ServeRequest, readmit,
                           shared_prefix: Optional[_PooledPrefix],
                           t0: float) -> None:
        """Start block accounting for the session now decoding in
        ``row`` and journal the readmit outcome + latency (admission
        wall, including the remainder prefill — the number the bench
        compares against re-prefill)."""
        if readmit is not None:
            # ledger opened at readmit time; grow it to the full prompt
            self._pager.on_tick(row, req.frontier)
        elif shared_prefix is not None and shared_prefix.table is not None:
            table, upto = self._pager.share_prefix(shared_prefix.table,
                                                   shared_prefix.length)
            self._pager.begin_row(row, req.session_id, req.frontier,
                                  table=table, immutable_upto=upto)
        else:
            self._pager.begin_row(row, req.session_id, req.frontier)
        ms = round((time.monotonic() - t0) * 1e3, 3)
        if readmit is not None:
            self.metrics.count("readmits")
            self.metrics.record_readmit(ms / 1e3)
            self._emit(EventKind.SERVE_READMIT, session=req.session_id,
                       tokens_reused=readmit.reused,
                       tokens_new=req.prompt_len - readmit.reused,
                       tier=readmit.tier, readmit_ms=ms, hit=True)
        else:
            self.metrics.count("readmit_misses")
            self._emit(EventKind.SERVE_READMIT, session=req.session_id,
                       tokens_reused=0, tokens_new=req.prompt_len,
                       tier=None, readmit_ms=ms, hit=False)
        self._push_tier_gauges()

    def _evict_prefix(self, reason: str) -> None:
        """cond must be held; pops the LRU entry and journals the HBM it
        reclaims (paged prefixes free refcounted blocks — bytes count
        only the last-reference releases, blocks still shared by live
        sessions survive)."""
        key, pooled = self._prefixes.popitem(last=False)
        self.metrics.count("evictions")
        if pooled.table is not None and self._pager is not None:
            freed = self._pager.free_table(pooled.table)
        else:
            freed = pooled.nbytes
        self._emit(EventKind.SERVE_EVICT, prefix=key.hex()[:16],
                   session=None, reason=reason,
                   idle_s=round(time.monotonic() - pooled.last_used, 3),
                   bytes=freed)

    def _sweep_prefixes(self) -> None:
        """TTL sweep — runs from the scheduler tick path every loop
        iteration (idle gateways included), so pooled HBM and parked
        host memory are released without waiting for the next admission."""
        ttl = self.config.prefix_ttl_s
        now = time.monotonic()
        with self._cond:
            stale = [k for k, p in self._prefixes.items()
                     if now - p.last_used > ttl]
            for k in stale:
                self._prefixes.move_to_end(k, last=False)
                self._evict_prefix(reason="ttl")
        if self._pager is not None:
            self._pager.sweep(now)

    def _decode_tick(self) -> None:
        """One pass of the decode loop: launch tick n+1, then pull and
        harvest tick n.  Nothing tick n+1 reads comes from the host, so
        the pull, the harvest and the loop's next admissions run under
        the device's work on n+1.  With no row live, or the loop
        stopping, tick n is only finished."""
        fault_injection.fire("serve.decode_tick", tick=self._ticks,
                             active=len(self._active))
        prev = self._in_flight
        if prev is None:
            # a busy period's first tick has no predecessor to pull
            self._in_flight = self._launch_tick(overlapped=False)
            return
        with self.tracer.span(SpanName.SERVE_TICK):
            # nothing live at the launch: tick n is the busy period's
            # last, pulled before the loop waits
            self._in_flight = self._launch_tick(overlapped=True) \
                if self._active and not self._stopped.is_set() else None
            res = self._batcher.pull(prev.pending)
        with self.tracer.span(SpanName.SERVE_HARVEST, live=len(prev.rows)):
            self._harvest(prev, res)

    def _launch_tick(self, overlapped: bool) -> _InFlight:
        with self._cond:
            rows = list(self._active.items())
        return _InFlight(self._batcher.launch(), rows,
                         self._batcher.round_draft_k, overlapped)

    def _discard_in_flight(self) -> None:
        """The loop died: wait out the tick in flight and drop its
        tokens (every request is about to fail)."""
        prev, self._in_flight = self._in_flight, None
        if prev is not None:
            try:
                jax.block_until_ready(prev.pending)
            except Exception as e:
                logger.warning(f"[serving] tick in flight lost: {e}")

    def _harvest(self, tick: _InFlight, res) -> None:
        """Hand one tick's tokens to the requests bound at its launch:
        append, stamp first tokens, finish rows that hit eos / budget /
        deadline / cancellation and free their slots.  A row whose
        request has finished since the launch ran this tick for nothing
        (a late row): its token is dropped, whoever holds the row now."""
        if isinstance(res, tuple):
            tokens, counts = res
        else:
            tokens, counts = res, None
        self._ticks += 1
        now = time.monotonic()
        live = tick.rows
        with self._cond:
            late = {row for row, req in live
                    if self._active.get(row) is not req}
        n_live = len(live)
        harvested = 0
        accepted = 0
        # the decode kernel's blocks, from where each row's token of this
        # tick was decoded, late rows included: what the kernel stepped (a
        # speculative round's target pass is the chunk kernel's: nothing
        # to count)
        kv_blocks, kv_by_kind = (0, 0), {}
        if counts is None:
            frontiers = [req.frontier + len(req.out) for _, req in live]
            kv_blocks = self._batcher.sweep_blocks(frontiers)
            kv_by_kind = self._batcher.sweep_by_kind(frontiers)
        for row, req in live:
            if row in late:
                continue
            h = req.handle
            if h.cancel_requested:
                self._finish_row(
                    row, req, RequestState.CANCELLED,
                    error=RequestCancelled(
                        f"{req.rid} cancelled mid-decode",
                        partial=np.asarray(req.out, np.int32)))
                continue
            if counts is None:
                toks = [int(tokens[row])]
            else:
                toks = [int(t) for t in tokens[row, :int(counts[row])]]
                accepted += max(int(counts[row]) - 1, 0)
            finished = False
            for tok in toks:
                # eos/budget cut a speculative window short: the tokens
                # past the cut are discarded (their K/V sits beyond the
                # retired frontier, never decoded again)
                req.out.append(tok)
                harvested += 1
                h.tokens_out = len(req.out)
                if h.t_first_token is None:
                    h.t_first_token = now
                    self.metrics.record_ttft(h.ttft_s)
                    if self.tracer.enabled:
                        self.tracer.record(SpanName.SERVE_FIRST_TOKEN,
                                           h.t_admit, now - h.t_admit,
                                           rid=req.rid)
                    if self._overload is not None:
                        self._overload.note_first_token(
                            (now - (h.t_admit or h.t_submit)) * 1e3)
                if (req.eos_token_id is not None
                        and tok == req.eos_token_id) \
                        or len(req.out) >= req.max_new_tokens:
                    finished = True
                    break
            if req.session_id is not None and self._pager is not None:
                # frontier-crossing block accounting: the tokens just
                # harvested wrote KV through frontier+len(out)-1 — a
                # multi-token speculative advance may cross one or more
                # block boundaries, all allocated inside this call
                self._pager.on_tick(row, req.frontier + len(req.out))
            if finished:
                self._finish_row(row, req, RequestState.DONE)
            elif req.deadline is not None and now > req.deadline:
                self._finish_row(
                    row, req, RequestState.TIMEOUT,
                    error=RequestTimedOut(
                        f"{req.rid} deadline passed mid-decode",
                        partial=np.asarray(req.out, np.int32)))
        self.metrics.record_tick(active=n_live, slots=self.config.slots,
                                 tokens=harvested, kv_blocks=kv_blocks,
                                 kv_by_kind=kv_by_kind,
                                 overlapped=tick.overlapped,
                                 late_rows=len(late))
        # a family with two pools: the sweep's counts by pool (one pool's
        # are ``kv_tokens_live`` / ``kv_tokens_streamed`` themselves)
        if len(kv_by_kind) > 1 and self.tracer.enabled:
            self.tracer.record(SpanName.SERVE_KV_SWEEP, now, 0.0,
                               **self.metrics.kv_sweep_counters())
        # the family's device counters, each group by its name (the family
        # owns where a group lies; a family has either, both or neither)
        moe = self._batcher.counts("moe_pairs")
        if moe is not None:
            self.metrics.record_moe_pairs(moe)
            if self.tracer.enabled:
                self.tracer.record(SpanName.SERVE_MOE_PAIRS, now, 0.0,
                                   **read_pair_counts(moe))
        state = self._batcher.counts("state_steps")
        if state is not None:
            state = {k: int(c) for k, c in
                     zip(self._batcher.state_counters, state)}
            self.metrics.record_state_steps(state)
            if self.tracer.enabled:
                self.tracer.record(SpanName.SERVE_STATE_STEPS, now, 0.0,
                                   **state)
        select = self._batcher.counts("sparse_select")
        if select is not None:
            select = {k: int(c) for k, c in
                      zip(self._batcher.select_counters, select)}
            self.metrics.record_sparse_select(select)
            if self.tracer.enabled:
                self.tracer.record(SpanName.SERVE_SPARSE_SELECT, now, 0.0,
                                   **select)
        round_k = tick.draft_k
        n_fed = n_live - len(late)
        if counts is not None and n_fed:
            proposed = n_fed * max(1, round_k)
            self.metrics.record_spec_round(accepted=accepted,
                                           proposed=proposed,
                                           emitted=harvested)
        every = self.config.journal_every_ticks
        if every and self._ticks % every == 0:
            with self._cond:
                depth = len(self._queue)
            snap = self.metrics.snapshot()
            self._emit(EventKind.SERVE_TICK, tick=self._ticks,
                       active=n_live, queue_depth=depth,
                       tok_per_s=round(snap["tokens_per_s"], 3),
                       overlap_share=round(snap["overlap_share"], 4),
                       late_row_share=round(snap["late_row_share"], 4),
                       launches_per_admission=round(
                           snap["launches_per_admission"], 4))
            if counts is not None and n_fed:
                self._emit(EventKind.SERVE_SPEC_ROUND, tick=self._ticks,
                           active=n_fed, draft_k=round_k,
                           accepted=accepted, emitted=harvested,
                           accept_rate=round(
                               accepted / max(1, proposed), 4))

    def _finish_row(self, row: int, req: ServeRequest, state: str,
                    error: Optional[Exception] = None) -> None:
        h = req.handle
        if state == RequestState.DONE and req.session_id is not None \
                and self._pager is not None:
            # retire BEFORE the slot frees: the row's KV must be
            # scattered/parked while no new tenant can overwrite it
            self._retire_session(row, req)
        with self._cond:
            self._release_row(row)
            self._cond.notify_all()
        if state == RequestState.DONE:
            self.metrics.count("completed")
            dt = max(time.monotonic() - (h.t_admit or h.t_submit), 1e-9)
            self._emit(EventKind.SERVE_DONE, request_id=req.rid, slot=row,
                       tokens_out=len(req.out),
                       ttft_ms=round((h.ttft_s or 0.0) * 1e3, 3),
                       tok_per_s=round(len(req.out) / dt, 3))
            h._finish(state, tokens=np.asarray(req.out, np.int32))
        elif state == RequestState.CANCELLED:
            self.metrics.count("cancelled")
            self._emit(EventKind.SERVE_CANCEL, request_id=req.rid, slot=row,
                       tokens_out=len(req.out))
            h._finish(state, error=error)
        elif state == RequestState.TIMEOUT:
            self.metrics.count("timeouts")
            self._emit(EventKind.SERVE_TIMEOUT, request_id=req.rid, slot=row,
                       deadline_s=(req.deadline - h.t_submit
                                   if req.deadline else None),
                       tokens_out=len(req.out), queued=False)
            h._finish(state, error=error)
        else:
            self.metrics.count("failed")
            h._finish(state, error=error)

    def _retire_session(self, row: int, req: ServeRequest) -> None:
        """Keep a finished conversation's KV for the follow-up turn:
        scatter into pool blocks, or park to host when the pool can't
        hold it.  Failure costs only the retention — the reply already
        belongs to the caller."""
        full = np.concatenate([np.asarray(req.tokens, np.int32),
                               np.asarray(req.out, np.int32)])
        with self.tracer.span(SpanName.SERVE_PARK, slot=row,
                              session=req.session_id,
                              tokens=int(full.shape[0])):
            try:
                self._pager.retire(row, full)
            except Exception as e:
                logger.warning(
                    f"[serving] retiring session {req.session_id!r} "
                    f"failed ({e}); its next turn re-prefills")
                self._pager.row_released(row)
        self._push_tier_gauges()

    def _push_tier_gauges(self) -> None:
        """Refresh the tiering gauges after any tier change: held
        conversations (decoding + pooled + parked), pool occupancy, and
        the headline serving-HBM-per-conversation number."""
        p = self._pager
        if p is None:
            return
        st = p.stats()
        convs = p.conversations()
        with self._cond:
            convs += sum(1 for r in self._active.values()
                         if r.session_id is None)
        m = self.metrics
        m.set_max("peak_concurrent_conversations", convs)
        m.set_value("pool_blocks_used", st["pool_blocks_used"])
        m.set_value("serving_hbm_bytes", p.hbm_bytes())
        m.set_value("hbm_bytes_per_conversation",
                    p.hbm_bytes() / max(1, convs))
