"""The readers of the program's request-keyed spans: only spans that start
inside the measured window count, gaps pair on one thread, nothing to read or
no common clock leaves the metric out, and the traced rehearsal of both
serving cells reports every metric that reads them."""

import time
import types

import pytest

from benchmarks.chip import rehearse
from benchmarks.chip.harness import Span
from benchmarks.chip.metrics.readers import (_window, span_arg_ratio_windowed,
                                             span_between_windowed,
                                             span_percentile_windowed)

from .common import ROOT, benchmark, tiny_overrides

BENCH = benchmark()
NEW = {
    "gpt2m-serve-chat-steady": {
        "gateway.queue_wait_p95_ms", "gateway.admit_span_ms_p95",
        "batcher.first_token_wait_p95_ms",
        "batcher.prefill_padding_share.chat", "batcher.pull_wait_ms_p50.chat",
        "batcher.host_between_ticks_ms_p50.chat"},
    "gpt2m-serve-decode-sat": {
        "batcher.pull_wait_ms_p50.decode",
        "batcher.host_between_ticks_ms_p50.decode"},
}


def _ctx(spans, opening=100.0, seconds=10.0):
    """A context whose window is [opening, opening + seconds) on the
    spans' clock: the process started at 90 and the window opened 10 s
    later, 4 s of them the backend's start, which ``setup_s`` leaves out."""
    return types.SimpleNamespace(
        spans=spans, seconds=seconds, t_process=opening - 10.0,
        scalars={"setup_s": 6.0, "opening_after_s": 10.0})


def test_the_window_is_the_openings_perf_counter_on_the_spans_clock():
    assert _window.window(_ctx([])) == (100.0, 110.0)
    no_setup = _ctx([])
    no_setup.scalars = {}
    assert _window.window(no_setup) is None


def test_only_spans_that_start_inside_the_window_count():
    spans = [Span("serve.queue", 99.9, 5.0),     # standing population
             Span("serve.queue", 100.0, 0.010),
             Span("serve.queue", 104.0, 0.020),
             Span("serve.queue", 109.99, 0.030),  # starts inside, ends after
             Span("serve.queue", 110.0, 7.0),     # the drain
             Span("serve.admit", 105.0, 9.0)]     # another name
    ctx = _ctx(spans)
    read = span_percentile_windowed.read
    assert read(ctx, span="serve.queue", q=100) == pytest.approx(30.0)
    assert read(ctx, span="serve.queue", q=50) == pytest.approx(20.0)
    assert read(ctx, span="serve.queue", q=0) == pytest.approx(10.0)
    assert read(ctx, span="serve.admit", q=50) == pytest.approx(9000.0)


def test_gaps_pair_an_end_with_the_next_start_on_the_same_thread():
    tick = lambda t0, dur, thread: Span("serve.tick", t0, dur, thread)
    spans = [tick(99.0, 0.9, "a"),                # before the window
             tick(100.0, 1.0, "a"),               # -> 101.004: 4 ms
             tick(100.5, 0.1, "b"),               # other thread -> 103: 2.4 s
             tick(101.004, 1.0, "a"),             # -> 102.010: 6 ms
             tick(102.010, 1.0, "a"),             # no next tick on "a"
             tick(103.0, 1.0, "b"),               # no next tick on "b"
             Span("serve.admit", 101.0, 0.003, "a")]
    read = span_between_windowed.read
    ctx = _ctx(spans)
    assert read(ctx, after="serve.tick", before="serve.tick",
                q=0) == pytest.approx(4.0)
    assert read(ctx, after="serve.tick", before="serve.tick",
                q=50) == pytest.approx(6.0)
    assert read(ctx, after="serve.tick", before="serve.tick",
                q=100) == pytest.approx(2400.0)
    # two names: the end of an admission to the next tick of its thread
    assert read(ctx, after="serve.admit", before="serve.tick",
                q=50) == pytest.approx(1.0)
    # the tick before the window is no sample, though its gap ends inside
    only_a = _ctx([s for s in spans if s.thread == "a"], opening=101.5)
    assert read(only_a, after="serve.tick", before="serve.tick",
                q=100) is None


def test_a_ratio_of_span_args_and_its_complement():
    prefill = lambda t0, tokens, padded: Span(
        "serve.prefill", t0, 0.01, "a", {"tokens": tokens, "padded": padded})
    spans = [prefill(99.0, 1, 128),               # before the window
             prefill(101.0, 100, 128), prefill(102.0, 156, 256),
             Span("serve.prefill", 103.0, 0.01, "a", {"tokens": 5}),
             Span("serve.prefill", 104.0, 0.01, "a", None)]
    read = span_arg_ratio_windowed.read
    ctx = _ctx(spans)
    assert read(ctx, span="serve.prefill", num="tokens",
                den="padded") == pytest.approx(100.0 * 256 / 384)
    assert read(ctx, span="serve.prefill", num="tokens", den="padded",
                complement=True) == pytest.approx(100.0 * 128 / 384)


@pytest.mark.parametrize("read, args", [
    (span_percentile_windowed.read, {"span": "serve.queue", "q": 95}),
    (span_between_windowed.read,
     {"after": "serve.tick", "before": "serve.tick", "q": 50}),
    (span_arg_ratio_windowed.read,
     {"span": "serve.prefill", "num": "tokens", "den": "padded"}),
], ids=["percentile", "between", "arg_ratio"])
def test_nothing_to_read_or_no_common_clock_leaves_the_metric_out(
        read, args, monkeypatch):
    full = [Span("serve.queue", 101.0, 0.01, "a"),
            Span("serve.tick", 101.0, 0.5, "a"),
            Span("serve.tick", 102.0, 0.5, "a"),
            Span("serve.prefill", 101.0, 0.01, "a",
                 {"tokens": 3, "padded": 8})]
    assert read(_ctx(full), **args) is not None
    # a parent commit's tracer has no such span: the metric is left out
    assert read(_ctx([]), **args) is None
    assert read(_ctx([Span("serve.admit", 101.0, 0.01, "a")]), **args) is None
    assert read(_ctx(full, opening=200.0), **args) is None
    # perf_counter and monotonic on two clocks: no window to filter by
    real = time.get_clock_info
    monkeypatch.setattr(time, "get_clock_info", lambda name: (
        types.SimpleNamespace(implementation="QueryPerformanceCounter()")
        if name == "perf_counter" else real(name)))
    assert read(_ctx(full), **args) is None


@pytest.mark.parametrize("cell", sorted(NEW))
def test_the_traced_rehearsal_reports_the_metrics_that_read_the_new_spans(
        cell, capsys):
    entry = next(w for w in BENCH["workloads"] if w["name"] == cell)
    listed = {m["name"] for m in BENCH["per_layer"]
              if cell in m.get("workloads", [])}
    assert NEW[cell] <= listed
    out = rehearse.rehearse(ROOT, cell, tiny_overrides(entry), seconds=1.2,
                            seed=2 ** 31 + 5, trace=True)
    assert out["correct"], capsys.readouterr().out
    assert NEW[cell] <= set(out["metrics"])


@pytest.mark.parametrize("metric", ["engine.step_ms_p50.train",
                                    "engine.step_ms_p50.zero3"])
def test_the_step_time_reads_the_engines_own_span(metric):
    """``engine.step_ms_p50.*`` is the median of the engine's ``train.step``
    spans that start inside the window, not the benchmark's fence."""
    import json
    import os

    from .common import BENCH_DIR
    with open(os.path.join(BENCH_DIR, "metrics", metric + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "span_percentile_windowed"
    assert spec["args"] == {"span": "train.step", "q": 50}
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["source"] == "program_span"
    spans = [Span("train.step", 99.0, 9.0),          # warm-up: compiles
             Span("train.step", 100.5, 0.600), Span("train.step", 101.2, 0.610),
             Span("train.step", 101.9, 0.640),
             Span("bench.train_step", 100.4, 0.700)]  # the fence: not read
    assert span_percentile_windowed.read(_ctx(spans), **spec["args"]) == \
        pytest.approx(610.0)
    assert span_percentile_windowed.read(_ctx(spans[-1:]),
                                         **spec["args"]) is None
