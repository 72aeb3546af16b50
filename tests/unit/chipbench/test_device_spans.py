"""``device_spans``: the readers of the program's ``serve.device`` spans on
hand-made spans: only spans that start inside the window count, a program is
chosen by a regular expression, nothing to read leaves the metric out, and
the guard against the trace clips the spans to the traced slice."""

import json
import os
import types

import pytest

from benchmarks.chip.harness import Span
from benchmarks.chip.metrics.readers import device_spans, device_time_share
from benchmarks.chip.trace import reduce as R
from deepspeed_tpu.telemetry import op_maps

from .common import BENCH_DIR, benchmark

BENCH = benchmark()
NEW = [m for m in BENCH["per_layer"]
       if json.load(open(os.path.join(
           BENCH_DIR, "metrics", m["name"] + ".json")))["reader"]
       == "device_spans"]
BACKLOG = [w["name"] for w in BENCH["workloads"]
           if "-sat" in w["name"] or "prefill-heavy" in w["name"]]


def _span(t0, dur, program, **args):
    return Span("serve.device", t0, dur, "waits",
                {"program": program, "waited": 0.0, **args})


def _ctx(spans, opening=100.0, seconds=10.0, reduced=None, trace_len_s=3.0):
    """A window of [opening, opening + seconds) on the spans' clock."""
    return types.SimpleNamespace(
        spans=spans, seconds=seconds, t_process=opening - 10.0,
        scalars={"opening_after_s": 10.0}, reduced=reduced,
        cell=types.SimpleNamespace(traffic={"trace_len_s": trace_len_s}))


SPANS = [
    _span(99.0, 0.9, "admit", padded=128),        # before the window
    _span(100.0, 0.004, "tick"),
    _span(100.004, 0.010, "admit", padded=128),   # back to back
    _span(100.014, 0.002, "tick"),
    _span(100.5, 0.030, "admit_prefix", padded=512),    # after a gap
    _span(100.53, 0.006, "tick"),
    _span(109.99, 0.050, "admit_wide", padded=256),     # starts inside
    _span(110.0, 0.5, "tick"),                    # the drain
    _span(110.5, 7.0, "admit", padded=1024),
    Span("serve.prefill", 105.0, 9.0, "loop", {"padded": 4096}),
]


def test_only_spans_that_start_inside_the_window_count():
    read, ctx = device_spans.read, _ctx(SPANS)
    admit = dict(program="^admit")
    assert read(ctx, "percentile_ms", q=50, **admit) == pytest.approx(30.0)
    assert read(ctx, "percentile_ms", q=0, **admit) == pytest.approx(10.0)
    assert read(ctx, "percentile_ms", q=100, **admit) == pytest.approx(50.0)
    # device seconds over the tokens the passes computed, in us a token
    assert read(ctx, "us_per", per="padded", **admit) == pytest.approx(
        1e6 * 0.090 / (128 + 512 + 256))
    assert read(ctx, "window_share", **admit) == pytest.approx(0.9)


def test_a_program_is_chosen_by_a_regular_expression():
    read, ctx = device_spans.read, _ctx(SPANS)
    assert read(ctx, "percentile_ms", program="^tick$",
                q=50) == pytest.approx(4.0)
    assert read(ctx, "percentile_ms", program="^admit$",
                q=50) == pytest.approx(10.0)
    assert read(ctx, "percentile_ms", program="^admit_prefix",
                q=50) == pytest.approx(30.0)
    assert read(ctx, "window_share", program="^admit|^tick$") == \
        pytest.approx(100.0 * 0.102 / 10.0)


def test_the_gaps_between_launches_are_the_devices_idle_time():
    # 100.016 -> 100.5 and 100.536 -> 109.99, whatever the programs
    assert device_spans.read(_ctx(SPANS), "idle_window_share") == \
        pytest.approx(100.0 * (0.484 + 9.454) / 10.0)
    back_to_back = [_span(100.0 + i, 1.0, "tick") for i in range(10)]
    assert device_spans.read(_ctx(back_to_back), "idle_window_share") == 0.0


def test_nothing_to_read_is_none_and_never_an_exception():
    read = device_spans.read
    parent = _ctx([s for s in SPANS if s.name != "serve.device"])
    for spec in (dict(what="percentile_ms", program="^admit", q=50),
                 dict(what="us_per", program="^admit", per="padded"),
                 dict(what="window_share", program="^admit"),
                 dict(what="idle_window_share"),
                 dict(what="trace_mismatch", program="^admit|^tick$")):
        assert read(parent, **spec) is None
        assert read(_ctx([]), **spec) is None
    ctx = _ctx(SPANS)
    assert read(ctx, "percentile_ms", program="^verify", q=50) is None
    assert read(ctx, "us_per", program="^tick$", per="padded") is None
    assert read(ctx, "trace_mismatch", program="^tick$") is None   # no trace
    no_clock = _ctx(SPANS)
    no_clock.scalars = {}
    assert read(no_clock, "percentile_ms", program="^admit", q=50) is None
    with pytest.raises(ValueError):
        read(ctx, "mean", program="^admit")


def _event(start, end, name, shape):
    text = f"%{name} = {shape}{{0}} fusion(f32[4]{{0}} %p)"
    return R.Op(0, "XLA Ops", *R.parse_hlo(text), text, start, end)


@pytest.fixture
def table():
    op_maps.clear_published()
    row = lambda name, shape, scope: {"name": name, "opcode": "fusion",
                                      "shape": shape, "scope": scope,
                                      "flags": []}
    op_maps.publish("serving", "tick", [row("fusion.1", "bf16[8]", "mlp")])
    op_maps.publish("serving", "admit", [
        row("fusion.3", "bf16[8]", "admit_chunk/mlp")])
    op_maps.publish("serving", "release", [row("fusion.7", "s32[8]", "")])
    yield
    op_maps.clear_published()
    device_time_share._JOINED.clear()


def test_the_guard_clips_the_spans_to_the_traced_slice(table):
    """The slice ends where the kind asked the profiler to stop (``at +
    length`` seconds into the window: 9.5 of 10 at a length of 3) and is as
    long as the trace says (2.0 s here: the profiler took 1 s to start), so
    on the spans' clock it is [107.5, 109.5]; the trace's own clock starts
    anywhere (50.0)."""
    events = [_event(50.0, 50.4, "fusion.1", "bf16[8]"),     # tick, clipped
              _event(50.4, 51.0, "fusion.3", "bf16[8]"),     # admit
              _event(51.0, 51.02, "fusion.7", "s32[8]"),     # release
              _event(51.02, 51.5, "fusion.1", "bf16[8]"),    # tick
              _event(51.9, 52.0, "fusion.3", "bf16[8]")]     # admit, clipped
    reduced = R.reduce_trace(events, [], (50.0, 52.0))
    spans = [_span(107.0, 0.9, "tick"),            # 0.4 of it in the slice
             _span(107.9, 0.6, "admit", padded=8),
             _span(108.5, 0.5, "tick"),            # release's 0.02 falls here
             _span(109.4, 0.5, "admit", padded=8),  # 0.1 in the slice
             _span(105.0, 1.0, "tick")]            # before the slice
    ctx = _ctx(spans, reduced=reduced)
    assert device_spans.slice_on_spans_clock(ctx) == pytest.approx(
        (107.5, 109.5))
    assert device_spans.clipped_s(spans, 107.5, 109.5) == pytest.approx(1.6)
    # by the trace: tick 0.4 + 0.48, admit 0.6 + 0.1
    assert device_spans.read(ctx, "trace_mismatch",
                             program="^admit|^tick$") == pytest.approx(
        100.0 * abs(1 - 1.6 / 1.58))
    assert device_spans.read(ctx, "trace_mismatch",
                             program="^admit") == pytest.approx(0.0, abs=1e-9)


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_each_new_metric_lists_its_cells_and_names_what_it_reads(metric):
    spec = json.load(open(os.path.join(BENCH_DIR, "metrics",
                                       metric["name"] + ".json")))
    assert spec["args"]["what"] in (
        "percentile_ms", "us_per", "window_share", "idle_window_share",
        "trace_mismatch")
    assert metric["better"] == "lower"
    guard = spec["args"]["what"] == "trace_mismatch"
    assert metric["source"] == ("device_trace" if guard else "program_span")
    if metric["name"].endswith(".chat"):
        assert metric["workloads"] == ["gpt2m-serve-chat-steady"]
        assert metric["moves"] in ("ttft_p95_ms", "tpot_p50_ms")
    else:
        assert metric["name"].endswith(".decode")
        assert metric["workloads"] == BACKLOG
        assert metric["moves"] == "serve_tokens_per_s"


def test_twelve_metrics_read_the_device_spans():
    assert len(NEW) == 12 and len(BACKLOG) == 7
