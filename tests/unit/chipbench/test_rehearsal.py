"""Every cell end to end through the harness at a 2-layer size on the CPU:
the same kinds, readers and checks as a chip run, with no device metric.
What only the chip can show (kernels in the programs, times, the trace's
device planes) is left to the chip."""

import os
import re
import subprocess
import sys

import pytest

from benchmarks.chip import harness, rehearse

from .common import ROOT, benchmark, tiny_overrides

BENCH = benchmark()


def _expected(cell, trace):
    group = BENCH["per_layer"] if trace else BENCH["end_to_end"]
    return {m["name"] for m in group
            if cell["name"] in m.get("workloads", [cell["name"]])}


@pytest.mark.parametrize("trace", [False, True], ids=["end_to_end", "traced"])
@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_rehearses(cell, trace, capsys):
    out = rehearse.rehearse(ROOT, cell["name"], tiny_overrides(cell),
                            seconds=1.2, seed=2 ** 31 + 11, trace=trace)
    printed = capsys.readouterr().out
    assert out["rehearsal"] and out["platform"] == "cpu"
    assert out["correct"], printed
    assert out["attempted"] > 0 and out["failed"] == 0
    got = set(out["metrics"])
    if trace:
        # the CPU's trace has no device plane: metrics read from it are
        # left out, the rest are all there
        from_trace = {m["name"] for m in BENCH["per_layer"]
                      if m["source"] == "device_trace"}
        assert got == _expected(cell, True) - from_trace
    else:
        assert got == _expected(cell, False)
    # beside the metrics: the run's own scalars (a stall of the machine and
    # the backend's start can be told from the server), and last of all the
    # numbers that were compared, each with its limit
    assert {"setup_s", "backend_start_s", "opening_after_s"} <= set(
        out["scalars"])
    if "serve" in cell["name"]:
        assert "largest_tick_gap_s" in out["scalars"]
    assert out["keys"][-1] == "compared" and out["keys"][:5] == [
        "correct", "attempted", "failed", "metrics", "device"]
    assert {"logits_rms_error", "logits_relative_error"} <= set(
        out["compared"])
    # no value of a rehearsal ever leaves it
    assert not any(isinstance(v, (int, float)) for v in out["metrics"])
    assert "[checks] " in printed and '"metrics"' not in printed


def test_open_loop_cell_reports_how_many_were_due(capsys):
    cell = next(w for w in BENCH["workloads"]
                if w["traffic"] == "chat-steady")
    rehearse.rehearse(ROOT, cell["name"], tiny_overrides(cell), seconds=1.0)
    printed = capsys.readouterr().out
    assert "[open_loop] due_in_window=20 first_tokens=20 failed=0" in printed


def _rms_logged(printed):
    return float(re.search(r"\[reference\].* rms_error=([0-9.]+)",
                           printed).group(1))


@pytest.mark.parametrize("cell,control", [
    ("gpt2m-serve-decode-sat", {"inference": {"dtype": "int8"}}),
    ("gpt2m-train-s1024", {"weights": "int8"}),
], ids=["serving-int8-weights", "training-int8-weights"])
def test_a_lowered_precision_reaches_the_logits_check(cell, control, capsys):
    """The negative controls of ``reference/control.py``: the reference
    keeps the weights the benchmark drew, so 8-bit weights inside the
    system raise the error it reads.  (Whether they pass the tolerances is
    a question of the published sizes, answered on the chip: compare.py.)"""
    cell = next(w for w in BENCH["workloads"] if w["name"] == cell)
    rms = []
    for c in (None, control):
        out = rehearse.rehearse(ROOT, cell["name"], tiny_overrides(cell),
                                seconds=0.3, seed=5, control=c)
        assert set(out["checks"]) >= {"logits_agree", "no_recompile"}
        rms.append(_rms_logged(capsys.readouterr().out))
    assert rms[1] > 1.25 * rms[0] > 0


def test_a_gateway_that_offers_probe_logits_is_asked(monkeypatch):
    """The logits check asks the program's public ``probe_logits`` and no
    private name: the fallback into the batcher (dead since PR 24) is gone."""
    import inspect
    import types

    import numpy as np

    from benchmarks.chip.kinds import _serving
    from benchmarks.chip.reference import compare

    assert not hasattr(_serving, "slot_path_logits")
    assert "_batcher" not in inspect.getsource(_serving)
    asked = []

    class Gateway:
        def probe_logits(self, prompts, ticks):
            asked.append((len(prompts), ticks))
            return ([[7] * ticks for _ in prompts],
                    [np.zeros((ticks + 1, 8), np.float32) for _ in prompts])

    reference = types.SimpleNamespace(
        forward=lambda file, params, tokens, last: np.zeros(
            (tokens.shape[0], last, 8), np.float32))
    monkeypatch.setattr(_serving, "resolve", lambda dotted: reference)
    ctx = types.SimpleNamespace(
        seed=3, reference_params={}, scalars={}, checks={}, compared={},
        cell=types.SimpleNamespace(
            config={"reference": "reference.any"},
            traffic={"check": {"ticks": 2, "prompt_lens": [3, 5]},
                     "serving": {"max_len": 16}}))
    engine = types.SimpleNamespace(
        model_config=types.SimpleNamespace(vocab_size=8))
    _serving.check_logits(ctx, engine, Gateway())
    assert asked == [(2, 2)] and ctx.checks["logits_agree"]
    assert ctx.compared["logits_rms_error"] == (0.0, compare.RMS_TOLERANCE)


def test_the_profiler_is_started_once_however_slow_its_start(monkeypatch):
    """``backlog``'s poll asks every 4 ms, and the profiler takes a second
    or more to come up: the flag is set when the start is asked for."""
    import inspect
    import time
    import types

    from benchmarks.chip.kinds import backlog

    calls = {"start": 0, "stop": 0}

    def slow_start(self):
        calls["start"] += 1
        time.sleep(0.3)
        self.t_start = time.monotonic()

    def stop(self):
        calls["stop"] += 1
        self.t_stop = time.monotonic()

    monkeypatch.setattr(harness.TraceSlice, "start", slow_start)
    monkeypatch.setattr(harness.TraceSlice, "stop", stop)
    slice_ = harness.TraceSlice(types.SimpleNamespace(
        rehearsal=True, cell=None))
    slice_.stop_async()                 # nothing was started: nothing stops
    for _ in range(5):                  # the poll, while the start is slow
        slice_.start_async()
        assert slice_.start_asked and slice_.t_start is None
    for _ in range(3):
        slice_.stop_async()
    slice_.join()
    assert calls == {"start": 1, "stop": 1} and slice_.stop_asked
    source = inspect.getsource(backlog)
    assert "slice_.t_start" not in source and "start_asked" in source


@pytest.mark.parametrize("length,seconds,start", [(3.0, 45.0, 41.5),
                                                  (2.5, 45.0, 42.0),
                                                  (3.0, 10.0, 6.5)])
def test_the_traced_slice_is_the_windows_last_seconds(length, seconds, start):
    import types

    from benchmarks.chip.kinds import _serving
    ctx = types.SimpleNamespace(seconds=seconds, cell=types.SimpleNamespace(
        traffic={"trace_len_s": length}))
    assert _serving.slice_of(ctx) == (start, length)


def test_the_serving_cells_trace_their_last_seconds():
    """The profiler's stop falls after the window's close (PERF.md 5): the
    slice's place is a constant of the serving kinds, no traffic file's."""
    import json
    import os
    import types

    from benchmarks.chip.kinds import _serving

    from .common import BENCH_DIR
    for cell in BENCH["workloads"]:
        with open(os.path.join(BENCH_DIR, "traffic",
                               cell["traffic"] + ".json")) as f:
            traffic = json.load(f)
        if traffic["kind"] in ("backlog", "open_loop"):
            assert "trace_at_s" not in traffic, cell["name"]
            at, length = _serving.slice_of(types.SimpleNamespace(
                seconds=BENCH["run_seconds"],
                cell=types.SimpleNamespace(traffic=traffic)))
            assert 0 < BENCH["run_seconds"] - at - length <= 1.0


def test_setup_leaves_the_backends_start_out():
    """``setup_s`` is process start to the opening less ``backend_start``,
    which is reported beside it; the window's place on the spans' clock
    still counts all of it."""
    import time

    from benchmarks.chip.metrics.readers import _window
    ctx = harness.Context(
        cell=None, seed=0, seconds=5.0, trace=False,
        t_process=time.perf_counter() - 10.0, devices=[], peaks={})
    ctx._phase_t = ctx.t_process
    ctx.phase("imports", until=ctx.t_process + 3.0)
    ctx.phase("backend_start", until=ctx.t_process + 7.5)
    ctx.open_window()
    s = ctx.scalars
    assert s["backend_start_s"] == pytest.approx(4.5)
    assert s["setup.imports_s"] == pytest.approx(3.0)
    assert s["opening_after_s"] == pytest.approx(10.0, abs=0.05)
    assert s["setup_s"] == pytest.approx(s["opening_after_s"] - 4.5)
    opening, close = _window.window(ctx)
    assert opening == pytest.approx(ctx.t_process + s["opening_after_s"])
    assert close - opening == 5.0


def test_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        harness.load_cell(ROOT, "no-such-cell")


def test_unknown_device_kind_is_an_error():
    from benchmarks.chip import peaks
    assert peaks.peaks_of("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        peaks.peaks_of("TPU v9 imaginary")


def test_run_py_has_no_cpu_path():
    """Without a TPU the command exits non-zero and prints no result."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "chip", "run.py"),
         "--workload", BENCH["workloads"][0]["name"], "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout and "no TPU" in proc.stderr
