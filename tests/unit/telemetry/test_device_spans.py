"""``serve.device``: a launch's time on the device, reconstructed from three
host stamps (its dispatch, its predecessor's completion, its own), and the
one watcher thread a registry starts for it, only under an enabled tracer."""

import threading

import pytest

import jax.numpy as jnp

from deepspeed_tpu.telemetry.spans import WAIT_THREAD, SpanName, Tracer
from deepspeed_tpu.utils.compile_watch import (CompiledProgramRegistry,
                                               device_span)


def _watchers(but=()):
    """The registries' watcher threads alive now (``but`` those another
    test's server, not collected yet, may have left)."""
    return [t for t in threading.enumerate()
            if t.name.startswith("device-spans:") and t not in but]


# launches as (t_dispatch, completion stamp or None for a lost output);
# spans as (start, dur, waited), lost launches left out
CHAINS = {
    # the device never waits: each launch begins where the last ended
    "back_to_back": ([(0.0, 3.0), (0.5, 5.0), (1.0, 9.0)],
                     [(0.0, 3.0, 0.0), (3.0, 2.0, 2.5), (5.0, 4.0, 4.0)], 0),
    # the second launch finds the device idle: it begins at its dispatch
    "idle_device": ([(0.0, 1.0), (4.0, 6.0)],
                    [(0.0, 1.0, 0.0), (4.0, 2.0, 0.0)], 0),
    # the first completion (true: 2.0) is stamped 0.5 late: the first span
    # grows and the second shrinks by as much, nothing overlaps
    "late_stamp": ([(0.0, 2.5), (0.1, 4.0)],
                   [(0.0, 2.5, 0.0), (2.5, 1.5, 2.4)], 0),
    # the second launch's output is lost: no span, one count, and what it
    # took falls to the third
    "lost_output": ([(0.0, 1.0), (0.2, None), (0.4, 5.0)],
                    [(0.0, 1.0, 0.0), (1.0, 4.0, 0.6)], 1),
}


@pytest.mark.parametrize("case", CHAINS)
def test_a_chain_of_launches_is_laid_end_to_end(case):
    launches, want, lost = CHAINS[case]
    before = _watchers()
    tracer = Tracer(name="t")
    reg = CompiledProgramRegistry("t", tracer=tracer)
    for i, (t_dispatch, done) in enumerate(launches):
        reg._stamp("p", t_dispatch, done, {"i": i})
    spans = tracer.spans()
    assert [(s.t0, s.dur, s.args["waited"]) for s in spans] == [
        pytest.approx(w) for w in want]
    assert reg.device_spans_lost == lost
    assert all(s.name == SpanName.SERVE_DEVICE and s.thread == WAIT_THREAD
               and s.args["program"] == "p" for s in spans)
    for a, b in zip(spans, spans[1:]):
        assert a.t0 + a.dur <= b.t0
    assert _watchers(before) == []      # stamping alone starts no thread


def test_the_first_launch_has_no_predecessor():
    assert device_span(2.0, 3.5, None) == (2.0, 1.5, 0.0)
    assert device_span(2.0, 3.5, 1.0) == (2.0, 1.5, 0.0)
    assert device_span(2.0, 3.5, 3.0) == (3.0, 0.5, 1.0)


@pytest.mark.parametrize("tracer", [None, Tracer(enabled=False)],
                         ids=["no_tracer", "disabled"])
def test_a_disabled_tracer_starts_no_thread_and_keeps_no_row(tracer):
    before = _watchers()
    reg = CompiledProgramRegistry("off", tracer=tracer)
    for _ in range(3):
        reg.watch("p", jnp.ones((2,)), tokens=1)
    assert _watchers(before) == [] and reg._launches is None
    assert reg.watch_stop(timeout=1.0)
    assert tracer is None or (tracer.spans() == []
                              and tracer.aggregates() == {})


def test_watched_launches_are_stamped_in_order_by_one_thread():
    before = _watchers()
    tracer = Tracer(name="t")
    reg = CompiledProgramRegistry("on", tracer=tracer)
    gone = jnp.ones((2,))
    gone.delete()                       # an output lost before its stamp
    for i, out in enumerate([jnp.ones((2,)), {"a": jnp.zeros((3,))}, gone,
                             jnp.ones((4,)) + 1]):
        reg.watch(f"p{i}", out, i=i)
    assert [t.name for t in _watchers(before)] == ["device-spans:on"]
    assert reg.watch_stop(timeout=30.0) and _watchers(before) == []
    spans = tracer.spans()
    assert [s.args["program"] for s in spans] == ["p0", "p1", "p3"]
    assert reg.device_spans_lost == 1
    assert all(s.dur >= 0 and s.args["waited"] >= 0 for s in spans)
    for a, b in zip(spans, spans[1:]):
        assert a.t0 + a.dur <= b.t0
    # a later launch starts another thread, which holds its registry
    # weakly and ends with it
    reg.watch("p4", jnp.ones((2,)))
    thread, = _watchers(before)
    del reg
    thread.join(timeout=10.0)
    assert not thread.is_alive()


def test_the_report_prints_device_time_by_program(tmp_path, capsys):
    """``scripts/run_report.py --trace``: launches, p50 and p95 ms, us a
    padded token (``padded``: the rows an admission's passes computed, a
    narrow last pass's in the last chunk's place), the share of a
    program's admissions that worked on the slot's own row of the pool
    (``in_place``) and the share of the run, by program."""
    import importlib.util
    import json
    import os

    from deepspeed_tpu.telemetry.export import write_trace
    tracer = Tracer(name="serving")
    reg = CompiledProgramRegistry("r", tracer=tracer)
    t = 10.0
    for i in range(20):                 # a tick of 3 ms, back to back
        reg._stamp("tick", t, t + 0.003, {})
        t += 0.003
        if i % 5 == 0:                  # and four admissions of 10 ms,
            # the last a chunk and a narrow last pass of 64 rows
            reg._stamp("admit", t, t + 0.010, {
                "padded": 256 if i < 15 else 128 + 64, "chunk": 128,
                "narrow": 0 if i < 15 else 64, "slot": i,
                "in_place": int(i >= 5)})
            t += 0.010
    with tracer.span(SpanName.SERVE_TICK):
        pass
    path = str(tmp_path / "trace.json")
    write_trace(path, tracer)
    spec = importlib.util.spec_from_file_location(
        "run_report", os.path.join(os.path.dirname(__file__), "..", "..",
                                   "..", "scripts", "run_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([str(tmp_path), "--trace", path, "--json"]) == 0
    table = json.loads(capsys.readouterr().out)["trace"]["device_by_program"]
    assert table["tick"] == {
        "n": 20, "p50_ms": pytest.approx(3.0, abs=2e-3),
        "p95_ms": pytest.approx(3.0, abs=2e-3), "us_per_padded_token": None,
        "in_place": None, "share": pytest.approx(0.6, abs=1e-3)}
    # the export keeps whole microseconds
    assert table["admit"]["n"] == 4
    assert table["admit"]["p50_ms"] == pytest.approx(10.0, abs=2e-3)
    # ... over the rows the passes computed, not the chunks they stand for
    assert table["admit"]["us_per_padded_token"] == pytest.approx(
        40000 / (3 * 256 + 128 + 64), abs=1e-2)
    assert table["admit"]["share"] == pytest.approx(0.4, abs=1e-3)
    assert table["admit"]["in_place"] == 0.75
    assert mod.main([str(tmp_path), "--trace", path]) == 0
    out = capsys.readouterr().out
    assert "device time by program (serve.device)" in out
    assert "      tick: 20, " in out and "      admit: 4, " in out
    assert ", 75%, " in out and ", -, " in out
    # a trace without such spans prints no table
    assert mod.device_span_table([{"ph": "X", "name": "serve.tick",
                                   "ts": 1, "dur": 1}]) == {}
