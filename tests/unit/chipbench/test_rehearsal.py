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


def test_a_gateway_that_offers_probe_logits_is_asked():
    """The one function that touches the batcher's internals steps aside
    for a public entry, should the program grow one."""
    from benchmarks.chip.kinds import _serving

    class Gateway:
        def probe_logits(self, prompts, ticks):
            return [[7] * ticks for _ in prompts], "logits"

    assert _serving.slot_path_logits(Gateway(), [[1, 2], [3]], 3) == (
        [[7, 7, 7], [7, 7, 7]], "logits")


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
