"""Compile & host-sync discipline: the runtime half of the gate.

The serving gateway's "zero recompiles after warmup" contract and the MFU
work's "no hidden host syncs in the step loop" contract are enforced two
ways.  Statically, ``tools/dslint``'s compile-discipline rules catch the
*construction* bugs (a fresh ``jax.jit`` per call, an un-bucketed shape
scalar keying a program cache).  This module catches what static analysis
cannot: a *stable, correctly-cached* program whose jit cache still grows
after warmup — shape churn from an unpadded batch, dtype drift, a config
scalar that varies per request.

Three pieces:

- :func:`hot_path` — a no-op decorator marking a function as part of the
  steady-state step/tick loop.  dslint's ``host-sync-in-hot-path`` rule
  flags device→host transfers (``.item()``, ``np.asarray``,
  ``jax.device_get``, ``block_until_ready``, ``float()/int()/bool()`` on
  device values) inside marked functions; sanctioned syncs carry an
  inline ``# dslint: disable=...`` with a reason.
- :class:`CompiledProgramRegistry` — the engine, the inference engine,
  and the serving ``SlotBatcher`` register every jitted program by name
  (generalizing serving's ``compile_counts()``).  Registered programs are
  thin pass-through wrappers that record a :class:`CompileEvent` (name,
  arg shape/dtype signature, wall seconds) whenever a call grows the
  underlying jit cache.  Re-registering a name folds the old program's
  compiles into a retired counter, so "un-caching" a program (rebuilding
  it per call) cannot hide from the count.  A registry also keeps the
  description of each program's last compile (shapes, no memory) and can
  lower it again into its **op map**: the named scope of each optimised-HLO
  instruction, published for ``telemetry.device_time`` while the owner's
  span tracer is on, or on demand (``telemetry/op_maps.py``).  While that
  tracer is on it also stamps when the device finished each launch its owner
  asks it to :meth:`~CompiledProgramRegistry.watch`, from one daemon thread,
  and records the launch's time ON THE DEVICE as a ``serve.device`` span
  (:func:`device_span`).
- :class:`CompileWatch` — a context manager over one or more registries:
  snapshot, warm up, then any further compile is a *recompile* — reported
  by :meth:`CompileWatch.check`, journaled as a ``perf.recompile`` event
  (program name + arg-shape signature), and fatal via
  :meth:`CompileWatch.assert_no_recompiles`.  Host-sync counters noted by
  the hot paths ride along and are journaled as ``perf.host_sync`` debug
  events on close.

``scripts/compile_report.py`` drives the tiny CPU train-loop and serving
fixtures under a watch and writes ``BENCH_COMPILE.json``, so per-program
compile counts/seconds are a diffable per-PR artifact.
"""

from __future__ import annotations

import queue
import threading
import time
import weakref
from dataclasses import dataclass
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple,
                    Union)

from .lock_watch import LockName, TrackedLock

__all__ = [
    "hot_path", "CompileEvent", "CompiledProgramRegistry", "CompileWatch",
    "RecompileError", "registries", "device_span",
]

#: every live registry (weak: a registry dies with its owner)
_REGISTRIES: "weakref.WeakSet[CompiledProgramRegistry]" = weakref.WeakSet()


def registries() -> List["CompiledProgramRegistry"]:
    """The registries alive in this process: what an operator's capture
    window (``telemetry.profiler_trace``) asks for their op maps."""
    return list(_REGISTRIES)


def hot_path(fn: Callable) -> Callable:
    """Mark ``fn`` as steady-state hot-path code (train micro/apply loop,
    pipe schedule, serving decode tick).  Pure marker — no wrapping, no
    overhead; the contract is enforced by dslint's
    ``host-sync-in-hot-path`` rule and documented in
    ``docs/static-analysis.md``."""
    fn.__hot_path__ = True
    return fn


class RecompileError(RuntimeError):
    """A registered program compiled past warmup (see the message for the
    program name and the triggering arg-shape signature)."""


#: leaves rendered into a shape signature before truncating
_SIG_MAX_LEAVES = 16


def _shape_sig(args: tuple, kwargs: dict) -> str:
    """Compact ``dtype[shape]`` signature of a call's arguments — the
    post-mortem breadcrumb for *which shape class* triggered a compile."""
    try:
        import jax
        leaves = jax.tree_util.tree_leaves((args, kwargs))
    except Exception:  # registry must work even if jax is mid-teardown
        leaves = list(args) + list(kwargs.values())
    parts = []
    for leaf in leaves[:_SIG_MAX_LEAVES]:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{dtype}[{','.join(str(d) for d in shape)}]")
        elif isinstance(leaf, (bool, int, float, str)):
            parts.append(repr(leaf))
        else:
            parts.append(type(leaf).__name__)
    if len(leaves) > _SIG_MAX_LEAVES:
        parts.append(f"...+{len(leaves) - _SIG_MAX_LEAVES}")
    return " ".join(parts)


def _described(args: tuple, kwargs: dict):
    """A call's arguments with every array replaced by its description
    (shape, dtype, weak type and, of a committed array, its sharding: what
    a donated array still has); static arguments and Python scalars stay as
    they were.  Enough to lower the SAME module again, and nothing of the
    call's memory is held.  An uncommitted array is described without a
    sharding: with one, the lowering annotates the argument, the module is
    another, and so is every compiler-made name in it."""
    import jax

    def describe(leaf):
        if isinstance(leaf, jax.Array) and not isinstance(
                leaf, jax.core.Tracer):
            return jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, weak_type=leaf.weak_type,
                sharding=leaf.sharding if leaf.committed else None)
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
        return leaf
    return jax.tree_util.tree_map(describe, (args, kwargs))


@dataclass(frozen=True)
class CompileEvent:
    """One observed compilation of a registered program."""

    registry: str   # owning registry's name
    program: str    # program name within the registry
    count: int      # cumulative compiles of this NAME (retired + live)
    shapes: str     # arg shape/dtype signature of the triggering call
    seconds: float  # wall seconds of the compiling call (compile + run)
    ts: float


class _WrappedProgram:
    """Pass-through wrapper for a registered jitted program.

    Overhead per call is two C-level cache-size reads and one monotonic
    clock read; the shape signature is only rendered when a compile
    actually happened."""

    __slots__ = ("_prog", "_reg", "name")

    def __init__(self, prog, reg: "CompiledProgramRegistry", name: str):
        self._prog = prog
        self._reg = reg
        self.name = name

    def _cache_size(self) -> int:
        return self._prog._cache_size()

    def __getattr__(self, name):
        # full pjit surface passthrough (.lower(), .trace(), ...) — the
        # wrapper only interposes on __call__
        return getattr(self._prog, name)

    def __call__(self, *args, **kwargs):
        before = self._prog._cache_size()
        t0 = time.monotonic()
        out = self._prog(*args, **kwargs)
        after = self._prog._cache_size()
        if after > before:
            self._reg._on_compile(self.name, args, kwargs, after,
                                  time.monotonic() - t0)
        return out


def device_span(t_dispatch: float, completed: float,
                prev_completed: Optional[float]) -> Tuple[float, float, float]:
    """``(start, dur, waited)`` of one launch on the device, on the clock of
    its three stamps.  The device runs a process's launches in order, so a
    launch begins at the later of its own dispatch (``t_dispatch``: the
    host's clock when the call returned) and its predecessor's completion
    (``prev_completed``; None for the first), and ends at ``completed``;
    ``waited`` is how long it stood queued behind its predecessors.  A
    completion stamped late (the watcher waits for the interpreter's lock)
    lengthens its launch and shortens the next by as much, so launches of
    one chain never overlap and their sum holds.  What the stamps cannot
    tell apart: while the whole process stands still (the host's scheduler
    took its cores away for a tenth of a second) the launch in flight
    reads that much longer, whether the device worked or waited."""
    start = t_dispatch if prev_completed is None \
        else max(t_dispatch, prev_completed)
    return start, completed - start, start - t_dispatch


def _stamp_launches(registry_ref, launches: "queue.SimpleQueue") -> None:
    """The watcher thread of one registry: waits for each handed-over
    launch's output, in launch order, and has the registry record its
    ``serve.device`` span.  It holds the registry only while it stamps, and
    ends when handed None (the registry was collected)."""
    import jax
    while True:
        item = launches.get()
        if item is None:
            return
        program, t_dispatch, out, args = item
        try:
            jax.block_until_ready(out)
            done = time.monotonic()
        except Exception:  # dslint: disable=swallowed-exception — a lost or deleted output drops its stamp (counted by _stamp), never a run
            done = None
        del item, out
        reg = registry_ref()
        if reg is None:
            return
        reg._stamp(program, t_dispatch, done, args)
        del reg


class CompiledProgramRegistry:
    """Every jitted program an owner drives, by name.

    ``register`` returns the wrapped program the owner must call through;
    ``counts()`` is the generalized ``compile_counts()`` contract (the
    no-recompile invariant is ``all(v <= 1)`` for shape-stable programs).
    Thread-safe: the serving scheduler thread and the submitting threads
    both touch it.
    """

    def __init__(self, name: str = "programs", tracer=None):
        self.name = name
        #: the owner's span tracer: while it is enabled, each compile's op
        #: map is built and published (``telemetry/op_maps.py``); the
        #: one switch the spans have, no other
        self.tracer = tracer
        #: per program, the description of its last compile's arguments
        self._last_call: Dict[str, Any] = {}
        _REGISTRIES.add(self)
        self._lock = TrackedLock(LockName.PERF_COMPILE_REGISTRY)
        self._programs: Dict[str, _WrappedProgram] = {}
        #: compiles owned by programs later re-registered under the same
        #: name — an un-cached (rebuilt-per-call) program keeps counting
        self._retired: Dict[str, int] = {}
        self._events: List[CompileEvent] = []
        self._compile_s: Dict[str, float] = {}
        self._host_syncs: Dict[str, int] = {}
        #: launches handed to the watcher thread, which is started by the
        #: first :meth:`watch` under an enabled tracer and never otherwise
        self._launches: Optional["queue.SimpleQueue"] = None
        self._watcher: Optional[threading.Thread] = None
        self._watcher_end: Optional[weakref.finalize] = None
        #: watched launches whose output was lost or deleted before its
        #: completion could be stamped (beside ``Tracer.dropped``)
        self.device_spans_lost = 0
        #: when the device finished the last stamped launch
        self._prev_done: Optional[float] = None

    # ---------------------------------------------------------- programs
    def register(self, name: str, prog) -> _WrappedProgram:
        """Wrap ``prog`` (a ``jax.jit`` result) under ``name``; call the
        returned wrapper in place of the raw program."""
        with self._lock:
            prev = self._programs.get(name)
            if prev is not None:
                self._retired[name] = (self._retired.get(name, 0)
                                       + prev._prog._cache_size())
            wrapped = _WrappedProgram(prog, self, name)
            self._programs[name] = wrapped
            self._last_call.pop(name, None)
            return wrapped

    def register_all(self, programs: Dict[str, Any],
                     prefix: str = "") -> Dict[str, _WrappedProgram]:
        return {k: self.register(prefix + k, v) for k, v in programs.items()}

    def _on_compile(self, name: str, args, kwargs, live: int,
                    seconds: float) -> None:
        sig = _shape_sig(args, kwargs)
        described = _described(args, kwargs)
        with self._lock:
            count = self._retired.get(name, 0) + live
            self._compile_s[name] = self._compile_s.get(name, 0.0) + seconds
            self._events.append(CompileEvent(
                registry=self.name, program=name, count=count, shapes=sig,
                seconds=seconds, ts=time.time()))
            self._last_call[name] = described
        if self.tracer is not None and self.tracer.enabled:
            self._publish(name)

    # ------------------------------------------------------------ op maps
    def op_map(self, name: str) -> Optional[List[dict]]:
        """The op map of program ``name`` as last compiled
        (``telemetry.op_maps.parse_op_map`` of its optimised HLO): the
        program lowered again from the description of that call's
        arguments.  It grows no jit cache, and compiles nothing where the
        process or the persistent cache holds the executable.  None for a
        program that has not compiled yet."""
        from ..telemetry.op_maps import parse_op_map
        with self._lock:
            wrapped = self._programs.get(name)
            described = self._last_call.get(name)
        if wrapped is None or described is None:
            return None
        args, kwargs = described
        # the compiled object goes as soon as its text is read
        return parse_op_map(
            wrapped._prog.lower(*args, **kwargs).compile().as_text())

    def _publish(self, name: str) -> bool:
        from ..telemetry.op_maps import publish
        try:
            rows = self.op_map(name)
        except Exception as e:  # an op map is telemetry: it never stops a run
            from .logging import logger
            logger.warning(f"[compile_watch] no op map of {self.name}/{name}:"
                           f" {e!r}")
            return False
        if rows is None:
            return False
        publish(self.name, name, rows)
        return True

    def publish_op_maps(self) -> List[str]:
        """Build and publish the op map of every program that has compiled
        (for an operator who captures a trace from a process whose tracers
        are off); returns the names published."""
        with self._lock:
            names = list(self._last_call)
        return [n for n in names if self._publish(n)]

    # ------------------------------------------------------- device spans
    def watch(self, program: str, out, **args: Any) -> None:
        """Note that ``program`` was just launched (call this as its call
        returns) and have its time on the device recorded as a
        ``serve.device`` span of the owner's tracer, with ``args``.  ``out``
        is an output of the launch that NO later launch donates: the owner
        says which, the registry does not guess.  Call in launch order from
        the thread that launches (call order is taken for device order).
        The wait for ``out`` is the watcher thread's, not a host sync of the
        caller's.  With the tracer disabled this is one attribute read: no
        thread, no clock, nothing kept."""
        tracer = self.tracer
        if tracer is None or not tracer.enabled:
            return
        t_dispatch = time.monotonic()
        launches = self._launches
        if launches is None:
            launches = self._start_watcher()
        launches.put((program, t_dispatch, out, args))

    def _start_watcher(self) -> "queue.SimpleQueue":
        with self._lock:
            if self._launches is None:
                launches: "queue.SimpleQueue" = queue.SimpleQueue()
                self._watcher = threading.Thread(
                    target=_stamp_launches, daemon=True,
                    name=f"device-spans:{self.name}",
                    args=(weakref.ref(self), launches))
                self._watcher.start()
                # the thread outlives no registry: it holds this one weakly
                self._watcher_end = weakref.finalize(self, launches.put, None)
                self._launches = launches
            return self._launches

    def watch_stop(self, timeout: float = 10.0) -> bool:
        """Have every launch watched so far stamped (or counted lost), then
        end the watcher thread, waiting at most ``timeout`` seconds for it;
        False if it still runs.  For the owner's shutdown and for readers of
        the spans after the last launch (call it once the launching thread
        has stopped), never for a hot path; a later :meth:`watch` starts
        another thread."""
        with self._lock:
            launches, thread = self._launches, self._watcher
            self._launches = self._watcher = None
        if launches is None:
            return True
        self._watcher_end.detach()
        launches.put(None)      # behind what is pending
        thread.join(timeout=timeout)
        return not thread.is_alive()

    def _stamp(self, program: str, t_dispatch: float,
               done: Optional[float], args: Dict[str, Any]) -> None:
        """Record the ``serve.device`` span of the launch dispatched at
        ``t_dispatch`` that the device finished at ``done`` (the watcher
        thread's call, in launch order).  ``done`` None: its output was
        lost; what it took falls to the next watched launch, as an
        unwatched program's does."""
        from ..telemetry.spans import SpanName
        if done is None:
            self.device_spans_lost += 1
            return
        start, dur, waited = device_span(t_dispatch, done, self._prev_done)
        self._prev_done = done
        self.tracer.record(SpanName.SERVE_DEVICE, start, dur,
                           program=program, waited=waited, **args)

    # ------------------------------------------------------------ queries
    def counts(self) -> Dict[str, int]:
        """Cumulative compiles per program name (retired + live cache)."""
        with self._lock:
            return {name: self._retired.get(name, 0) + w._prog._cache_size()
                    for name, w in self._programs.items()}

    def compile_seconds(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._compile_s)

    @property
    def events(self) -> List[CompileEvent]:
        with self._lock:
            return list(self._events)

    # --------------------------------------------------------- host syncs
    def note_host_sync(self, label: str, n: int = 1) -> None:
        """Record ``n`` sanctioned device→host syncs at ``label`` (called
        from the ``@hot_path`` sites whose syncs are by design)."""
        with self._lock:
            self._host_syncs[label] = self._host_syncs.get(label, 0) + n

    def host_syncs(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._host_syncs)

    def total_host_syncs(self) -> int:
        with self._lock:
            return sum(self._host_syncs.values())


class CompileWatch:
    """Watch one or more registries for post-warmup compiles.

    Two warmup conventions:

    - explicit: run the warmup iterations, call :meth:`mark_warm`; every
      compile after the mark is a recompile (the train-loop shape);
    - ``first_compile_free=True``: each program's first-ever compile is
      warmup, anything beyond (``count > 1``) is a recompile (the serving
      shape, where programs are shape-stable by construction).

    ``check()`` returns (and journals, as ``perf.recompile``) the
    recompiles seen since the last check; ``close()``/``__exit__`` does a
    final check and journals the hot paths' ``perf.host_sync`` counters.
    """

    def __init__(self, registries: Union[CompiledProgramRegistry,
                                         Sequence[CompiledProgramRegistry]],
                 journal=None, first_compile_free: bool = False):
        if isinstance(registries, CompiledProgramRegistry):
            registries = [registries]
        self._regs: List[CompiledProgramRegistry] = list(registries)
        self._journal = journal
        self._first_free = bool(first_compile_free)
        self._base: Optional[List[int]] = None
        self._warm: Optional[List[int]] = None
        self._emitted: Optional[List[int]] = None
        self._sync_base: Optional[List[Dict[str, int]]] = None
        self._closed = False

    # ------------------------------------------------------------ lifecycle
    def open(self) -> "CompileWatch":
        self._base = [len(r.events) for r in self._regs]
        self._emitted = list(self._base)
        self._sync_base = [r.host_syncs() for r in self._regs]
        return self

    def __enter__(self) -> "CompileWatch":
        return self.open()

    def mark_warm(self) -> None:
        """End of warmup: compiles past this point are regressions."""
        self._warm = [len(r.events) for r in self._regs]

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.check()
        if self._journal is not None:
            for label, n in sorted(self.host_syncs().items()):
                if n:
                    self._journal.emit("perf.host_sync", label=label,
                                       count=n)

    def __exit__(self, *exc) -> None:
        self.close()

    # -------------------------------------------------------------- events
    def _require_open(self) -> None:
        if self._base is None:
            raise RuntimeError("CompileWatch used before open()/__enter__")

    def _boundary(self, i: int) -> int:
        """Index into registry ``i``'s event list where warmup ends."""
        if self._warm is not None:
            return self._warm[i]
        if self._first_free:
            return self._base[i]
        # neither convention chosen yet: still warming up
        return None  # type: ignore[return-value]

    def _events_past(self, cursors: List[int]) -> List[CompileEvent]:
        out: List[CompileEvent] = []
        for i, reg in enumerate(self._regs):
            boundary = self._boundary(i)
            if boundary is None:
                continue
            events = reg.events
            start = max(boundary, cursors[i])
            for e in events[start:]:
                if self._first_free and e.count <= 1:
                    continue
                out.append(e)
        return sorted(out, key=lambda e: e.ts)

    @property
    def recompiles(self) -> List[CompileEvent]:
        """Every post-warmup compile observed so far."""
        self._require_open()
        if self._warm is not None:
            cursors = self._warm
        else:
            cursors = self._base
        return self._events_past(cursors)

    @property
    def warmup_events(self) -> List[CompileEvent]:
        """Compiles between open() and the warmup boundary."""
        self._require_open()
        out: List[CompileEvent] = []
        for i, reg in enumerate(self._regs):
            events = reg.events
            end = self._warm[i] if self._warm is not None else len(events)
            for e in events[self._base[i]:end]:
                if self._first_free and e.count > 1:
                    continue
                out.append(e)
        return out

    def check(self) -> List[CompileEvent]:
        """Recompiles since the last ``check()``; journals each as a
        ``perf.recompile`` event."""
        self._require_open()
        new: List[CompileEvent] = []
        for i, reg in enumerate(self._regs):
            boundary = self._boundary(i)
            if boundary is None:
                continue
            events = reg.events
            start = max(boundary, self._emitted[i])
            for e in events[start:]:
                if self._first_free and e.count <= 1:
                    continue
                new.append(e)
            self._emitted[i] = max(self._emitted[i], len(events))
        new.sort(key=lambda e: e.ts)
        if self._journal is not None:
            for e in new:
                self._journal.emit("perf.recompile", program=e.program,
                                   registry=e.registry, count=e.count,
                                   shapes=e.shapes,
                                   compile_s=round(e.seconds, 4))
        return new

    def assert_no_recompiles(self, context: str = "") -> None:
        rcs = self.recompiles
        if rcs:
            detail = "; ".join(
                f"program '{e.program}' ({e.registry}) compiled "
                f"{e.count}x, triggered by shapes [{e.shapes}]"
                for e in rcs[:8])
            where = f" in {context}" if context else ""
            raise RecompileError(
                f"{len(rcs)} post-warmup recompile(s){where}: {detail}")

    # ---------------------------------------------------------- host syncs
    def host_syncs(self) -> Dict[str, int]:
        """Per-label host-sync counts accumulated since open()."""
        self._require_open()
        out: Dict[str, int] = {}
        for i, reg in enumerate(self._regs):
            base = self._sync_base[i]
            for label, n in reg.host_syncs().items():
                d = n - base.get(label, 0)
                if d:
                    out[label] = out.get(label, 0) + d
        return out

    def total_host_syncs(self) -> int:
        return sum(self.host_syncs().values())
