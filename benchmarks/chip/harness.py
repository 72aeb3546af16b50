"""What every cell shares: reading the cell's files, the device checks, the
compile counter, the traced slice, the metric readers and the result line.

The harness holds no cell's name.  A cell is an entry of ``workloads`` in
``BENCHMARK.json``; its configuration is ``configs/<config>.json``, its
traffic ``traffic/<traffic>.json`` (whose ``kind`` names the module under
``kinds/`` that drives it), and each of its metrics ``metrics/<name>.json``
(whose ``reader`` names the module under ``metrics/readers/`` that computes
it).  A later PR adds files and entries; it edits nothing here.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))

#: seconds of compilation events seen by JAX's monitoring hook
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(phase: str, **fields) -> None:
    """An earlier line of standard output (the last line is the result)."""
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with the files it names."""
    name: str
    chips: int
    config: dict            # the configuration file
    traffic: dict           # the traffic or job file
    end_to_end: List[dict]  # the cell's entries of BENCHMARK.json
    per_layer: List[dict]
    root: str               # directory that holds BENCHMARK.json


def load_cell(root: str, workload: str) -> Cell:
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise SystemExit(f"BENCHMARK.json has no workload {workload!r} "
                         f"(it has {[w['name'] for w in bench['workloads']]})")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    mine = lambda m: "workloads" not in m or workload in m["workloads"]
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=read_json(os.path.join(root, config["file"])),
        traffic=read_json(os.path.join(HERE, "traffic",
                                       entry["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if mine(m)],
        per_layer=[m for m in bench["per_layer"] if mine(m)],
        root=root)


@dataclasses.dataclass
class Span:
    """A host span on ``time.monotonic``'s clock: the benchmark's own, or
    one of the program's tracer."""
    name: str
    t0: float
    dur: float
    thread: str = ""
    args: Optional[dict] = None


@dataclasses.dataclass
class Context:
    """What a kind fills and the metric readers read."""
    cell: Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float                    # perf_counter at process start
    devices: list
    peaks: dict
    rehearsal: bool = False             # the tests' CPU run at a tiny size
    samples: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    scalars: Dict[str, float] = dataclasses.field(default_factory=dict)
    spans: List[Span] = dataclasses.field(default_factory=list)
    checks: Dict[str, bool] = dataclasses.field(default_factory=dict)
    #: each number a check compared: name -> (the number, its limit)
    compared: Dict[str, tuple] = dataclasses.field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    reduced: Any = None                 # trace.reduce.Reduced of the slice
    model_config: Any = None
    reference_params: Any = None        # the weights the benchmark drew
    #: a negative control of the logits check: the precision lowered on
    #: purpose (``reference/control.py`` and the tests; ``run.py`` never)
    control: dict = dataclasses.field(default_factory=dict)
    _phase_t: float = 0.0
    _compiles: int = 0

    # ---- set-up phases -------------------------------------------------
    def phase(self, name: str, until: Optional[float] = None) -> None:
        """Close a phase of set-up (now, or at ``until`` on
        ``perf_counter``'s clock): logged with its seconds."""
        now = time.perf_counter() if until is None else until
        log("setup", done=name, s=round(now - self._phase_t, 3))
        self.scalars[f"setup.{name}_s"] = now - self._phase_t
        self._phase_t = now

    def open_window(self) -> float:
        """Set-up ends here.  Returns ``time.monotonic()`` at the opening.

        ``setup_s`` is process start to here *less the backend's start*
        (``backend_start_s``, reported beside it: the first, bare
        ``jax.devices()`` and nothing of the program's): the runtime taking
        the chips is the one phase that is the machine's and not the tree's,
        and the only one that moves by seconds between runs of one tree
        (6.9-11.1 s in 18 processes that import JAX and ask for the
        devices, whatever ran before them; PERF.md 4).  Importing, weights,
        engine, warm-up, compilation and fill all stay in."""
        after = time.perf_counter() - self.t_process
        backend = self.scalars.get("setup.backend_start_s", 0.0)
        self.scalars["opening_after_s"] = after
        self.scalars["backend_start_s"] = backend
        self.scalars["setup_s"] = after - backend
        self.scalars["compiles_before_window"] = self._compiles
        log("window", open_after_s=round(after, 3),
            backend_start_s=round(backend, 3),
            setup_s=round(after - backend, 3), seconds=self.seconds)
        return time.monotonic()

    def close_window(self) -> None:
        n = self._compiles - self.scalars["compiles_before_window"]
        self.scalars["compiles_in_window"] = n
        self.checks["no_compile_in_window"] = n == 0

    def build_model_config(self, **replace):
        """The program's model config from the configuration file, through
        the builder the file names."""
        from .builders import resolve
        cfg = resolve(self.cell.config["builder"])(self.cell.config)
        self.model_config = dataclasses.replace(cfg, **replace)
        return self.model_config

    def seed_key(self):
        import jax
        return jax.random.PRNGKey(self.seed)


class TraceSlice:
    """A traced slice of the window.  The profiler writes under a fixed
    directory inside the checkout, removed once the trace is reduced."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        # a rehearsal's (tests run in parallel) goes under TMPDIR instead
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if ctx.rehearsal \
            else os.path.join(ctx.cell.root, ".bench_trace", ctx.cell.name)
        self.t_start = self.t_stop = None
        #: set the moment a start or a stop is *asked for*: ``t_start`` is
        #: set by the helper thread only once the profiler is up, a second
        #: or more later, and a poll that tested it would ask twice
        self.start_asked = self.stop_asked = False
        self._thread = None

    def start_async(self) -> None:
        """``start`` from a helper thread: starting the profiler takes a
        second or more, and a load generator must not stall for it."""
        if not self.start_asked:
            self.start_asked = True
            self._begin(self.start)

    def stop_async(self) -> None:
        if self.start_asked and not self.stop_asked:
            self.stop_asked = True
            self._begin(self.stop)

    def _begin(self, fn) -> None:
        self.join()
        self._thread = threading.Thread(target=fn, daemon=True,
                                        name="bench-profiler")
        self._thread.start()

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join(timeout=120)
            self._thread = None

    def start(self) -> None:
        import jax
        self.start_asked = True
        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # every Python call is too many
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        # one annotation whose monotonic time is known ties the program's
        # spans (monotonic clock) to the trace's clock
        with jax.profiler.TraceAnnotation("bench.clock"):
            self.t_start = time.monotonic()
            time.sleep(0.001)

    def stop(self) -> None:
        import jax
        self.stop_asked = True
        self.t_stop = time.monotonic()
        jax.profiler.stop_trace()

    def reduce(self) -> None:
        from .trace import reduce as R
        self.join()
        found = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        ops = R.read_device_ops(found[0])
        host = R.read_host_events(found[0])
        clock = next((h for h in host if h.name == "bench.clock"), None)
        if clock is None:
            raise RuntimeError("the trace lacks the bench.clock annotation")
        shift = clock.start - self.t_start          # monotonic -> trace
        host += [R.HostEvent(s.thread, s.name, s.t0 + shift,
                             s.t0 + s.dur + shift)
                 for s in self.ctx.spans
                 if s.t0 + s.dur > self.t_start and s.t0 < self.t_stop]
        window = (clock.end, self.t_stop + shift)
        if ops or not self.ctx.rehearsal:   # a CPU trace has no device plane
            self.ctx.reduced = R.reduce_trace(ops, host, window)
        shutil.rmtree(self.dir, ignore_errors=True)


def peak_memory_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest chip.  On this runtime it counts
    live buffers, not a running program's temporaries (PERF.md 7)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def compute_metrics(ctx: Context, entries: List[dict]) -> Dict[str, dict]:
    """Each metric through the reader its file names; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for entry in entries:
        spec = read_json(os.path.join(HERE, "metrics",
                                      entry["name"] + ".json"))
        reader = importlib.import_module(
            f"{__package__}.metrics.readers.{spec['reader']}")
        value = reader.read(ctx, **spec.get("args", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def measure(cell: Cell, seed: int, seconds: float, trace: bool,
            t_process: float, rehearsal_peaks: Optional[dict] = None,
            control: Optional[dict] = None) -> Context:
    """One run of one cell: the context its kind filled.  With
    ``rehearsal_peaks`` (the tests' CPU rehearsal, sizes already cut in
    ``cell``) no chip is required and nothing measured is a device metric."""
    import jax
    from .peaks import peaks_of
    rehearsal = rehearsal_peaks is not None
    t_imported = time.perf_counter()
    jax.devices()       # the runtime takes the chips: the machine's phase
    t_backend = time.perf_counter()
    if rehearsal:
        devices, cache = jax.devices(), None
    else:       # the program's own functions stay inside ``setup_s``
        from deepspeed_tpu.utils.platform import (enable_compile_cache,
                                                  require_tpu)
        devices = require_tpu()
        cache = enable_compile_cache()
    t_platform = time.perf_counter()
    if len(devices) < cell.chips:
        raise SystemExit(f"{cell.name} needs {cell.chips} chip(s); "
                         f"jax.devices() reports {len(devices)}")
    peaks = dict(rehearsal_peaks) if rehearsal \
        else peaks_of(devices[0].device_kind)
    ctx = Context(cell=cell, seed=seed, seconds=seconds, trace=trace,
                  t_process=t_process, devices=devices[:cell.chips],
                  peaks=peaks, rehearsal=rehearsal, control=control or {})
    ctx._phase_t = t_process

    def on_event(event, duration, **_):
        if event == _COMPILE_EVENT:
            ctx._compiles += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    log("run", workload=cell.name, seed=seed, seconds=seconds,
        trace=int(trace), device=devices[0].device_kind, chips=cell.chips,
        compile_cache=cache)
    # importing JAX is the tree's and the installation's; the backend's
    # start (the bare ``jax.devices()``: the runtime taking the chips) is
    # the machine's, and is where set-up varies from run to run; importing
    # the program for its platform check and compile-cache set-up is the
    # tree's again
    ctx.phase("imports", until=t_imported)
    ctx.phase("backend_start", until=t_backend)
    ctx.phase("platform_and_cache", until=t_platform)
    kind = importlib.import_module(
        f"{__package__}.kinds.{cell.traffic['kind']}")
    try:
        kind.run(ctx)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    log("checks", **ctx.checks)
    return ctx


def result_of(ctx: Context) -> dict:
    """The result object of the contract."""
    import jax
    devices = jax.devices()
    cell = ctx.cell
    metrics = compute_metrics(
        ctx, cell.per_layer if ctx.trace else cell.end_to_end)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_memory_bytes(ctx.devices)}
    result = {"correct": bool(ctx.checks) and all(ctx.checks.values()),
              "attempted": ctx.attempted, "failed": ctx.failed,
              "metrics": metrics, "device": device}
    if ctx.trace and ctx.reduced is not None:
        device["busy_s"] = ctx.reduced.busy_s
        device["window_s"] = ctx.reduced.window_s
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in ctx.reduced.top_ops],
            "idle_gaps": [[k, v] for k, v in ctx.reduced.idle_gaps]}
    # what a reader of one run needs beside the metrics (the driver ignores
    # both keys): the run's own counts and clocks, and last every number a
    # check compared beside its limit, which also end standard error
    result["scalars"] = {k: v for k, v in ctx.scalars.items()
                         if isinstance(v, (int, float))}
    result["compared"] = {
        **{k: [v, limit] for k, (v, limit) in ctx.compared.items()},
        **{k: [int(v), 1] for k, v in ctx.checks.items()}}
    for k, (v, limit) in result["compared"].items():
        print(f"compared {k}={v} limit={limit}", file=sys.stderr, flush=True)
    return result
