"""Unified telemetry: span tracing, metrics stream, and Perfetto export.

The single observability substrate the ``wall_clock_breakdown`` timers,
``ServingMetrics``, the compile-discipline watch, and the goodput scorer
all used to re-derive piecemeal:

- :mod:`.spans` — nestable thread-aware :class:`Tracer` spans over the
  train step phases, the serving tick/admission path, and the elastic
  runner; names single-sourced in :class:`SpanName`;
- :mod:`.metrics` — :class:`MetricsRegistry` counters/gauges/histograms
  plus a :class:`MetricsSampler` streaming ``metrics.sample`` rows to a
  torn-line-tolerant ``metrics.jsonl`` sidecar; names single-sourced in
  :class:`MetricName`; online MFU via :func:`analytic_mfu`;
- :mod:`.export` — Chrome/Perfetto ``trace_event`` JSON export of the
  collected spans, schema validation, and the opt-in
  ``jax.profiler.trace`` capture window;
- :mod:`.op_maps` — device seconds by program and by named scope:
  each compiled program's op map (instruction -> scope), the published
  table of them, and :func:`device_time`, the join of a profiler trace's
  device ops to it;
- :mod:`.config` — the validated ``"telemetry"`` config section;
- :mod:`.propagate` — cross-process ``trace_id``/``parent_span_id``
  propagation (spool docs, ``DS_TRACE_CONTEXT`` env, clock-sync
  handshake) so every fleet process's spans stitch into one request tree;
- :mod:`.critical_path` — span-chain coverage, TTFT/MTTR critical-path
  decomposition, and the multi-pid wall-aligned Perfetto merge
  (``scripts/fleet_report.py`` is the CLI).

``scripts/run_report.py`` joins the three streams into one per-run
report and gates overhead + span inventory in ``BENCH_TELEMETRY.json``.
Reference: ``docs/telemetry.md``.
"""

from .config import DeepSpeedTelemetryConfig  # noqa: F401
from .critical_path import (MTTR_PHASES, TTFT_PHASES,  # noqa: F401
                            decompose_mttr, decompose_request,
                            decompose_training_restarts, merge_fleet_trace,
                            missing_worker_telemetry, request_chains,
                            span_chain_coverage, summarize_ttft)
from .op_maps import device_time  # noqa: F401
from .export import (profiler_trace, trace_events, validate_trace,  # noqa: F401
                     write_trace)
from .metrics import (METRIC_NAMES, Counter, Gauge, Histogram,  # noqa: F401
                      MetricName, MetricsRegistry, MetricsSampler,
                      analytic_mfu, host_rss_bytes, live_buffer_bytes,
                      peak_flops_per_chip, read_metrics)
from .propagate import (TRACE_ENV, TraceContext, child_context,  # noqa: F401
                        clock_sync, extract, from_env, inject,
                        mint_context, to_env, wall_offset_s)
from .spans import SPAN_NAMES, SpanName, SpanRecord, Tracer  # noqa: F401
