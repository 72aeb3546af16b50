"""Tracer units: nesting, the write-through to the profiler, recorded
waits, thread safety, capacity, synced calibration mode, and name
validation."""

import glob
import os
import threading
import time

import pytest

from deepspeed_tpu.telemetry.spans import (SPAN_NAMES, WAIT_THREAD, SpanName,
                                           Tracer)


def test_span_records_name_duration_and_args():
    tr = Tracer()
    with tr.span(SpanName.TRAIN_FWD, step=3):
        time.sleep(0.01)
    (rec,) = tr.spans()
    assert rec.name == "train.fwd"
    assert rec.dur >= 0.009
    assert rec.args == {"step": 3}
    assert rec.depth == 0
    agg = tr.aggregates()
    assert agg["train.fwd"]["count"] == 1
    assert agg["train.fwd"]["total_s"] == pytest.approx(rec.dur)


def test_nesting_depth_tracked_per_thread():
    tr = Tracer()
    with tr.span(SpanName.TRAIN_STEP):
        with tr.span(SpanName.TRAIN_FWD):
            with tr.span(SpanName.TRAIN_HOST_SYNC):
                pass
    by_name = {r.name: r for r in tr.spans()}
    assert by_name["train.step"].depth == 0
    assert by_name["train.fwd"].depth == 1
    assert by_name["train.host_sync"].depth == 2
    # inner spans complete first
    assert [r.name for r in tr.spans()] == \
        ["train.host_sync", "train.fwd", "train.step"]


def test_disabled_tracer_keeps_nothing_and_hands_back_the_bare_annotation():
    """The contract since the tracer writes through to the profiler: a
    disabled tracer keeps no record, reads no clock and takes no lock; what
    it hands back is the bare profiler annotation, not a tracer object."""
    from jax.profiler import TraceAnnotation
    tr = Tracer(enabled=False)
    tr._clock = tr._lock = None              # touching either would raise
    ctx = tr.span(SpanName.TRAIN_FWD, step=1)
    assert type(ctx) is TraceAnnotation
    with ctx:
        pass
    assert tr._records == [] and tr._agg == {}
    # names are checked whether or not records are kept: the annotation
    # reaches any attached profiler under that name
    with pytest.raises(ValueError, match="not registered in SpanName"):
        tr.span("not-even-a-registered-name")


def _host_events(logdir):
    """``{name: (line, stats)}`` of the host plane of the one trace under
    ``logdir``."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                out[e.name] = (line.name, dict(e.stats))
    return out


def test_spans_reach_an_attached_profiler_enabled_or_not(tmp_path):
    import jax
    on, off = Tracer(), Tracer(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with on.span(SpanName.SERVE_TICK):
            with on.span(SpanName.SERVE_PULL):
                time.sleep(0.002)
        with off.span(SpanName.TRAIN_DISPATCH, step=7):
            time.sleep(0.002)
        off.record(SpanName.SERVE_QUEUE, 1.0, 2.0, rid="req-1")
    finally:
        jax.profiler.stop_trace()
    events = _host_events(str(tmp_path))
    # both tracers' spans sit on a host thread line of the profiler's own
    # trace, under their bare names, keywords as stats
    assert {"serve.tick", "serve.pull", "train.dispatch"} <= set(events)
    assert events["train.dispatch"][1] == {"step": 7}
    assert events["serve.tick"][0] == events["train.dispatch"][0]
    assert "serve.queue" not in events       # a record() is the tracer's only
    assert [r.name for r in on.spans()] == ["serve.pull", "serve.tick"]
    assert off.spans() == []


def test_record_keeps_a_span_whose_start_was_noted_earlier():
    tr = Tracer()
    with tr.span(SpanName.SERVE_ADMIT, rid="req-9"):
        tr.record(SpanName.SERVE_QUEUE, 12.5, 0.25, rid="req-9", depth=3)
    queue, admit = tr.spans()
    assert (queue.name, queue.t0, queue.dur) == ("serve.queue", 12.5, 0.25)
    assert queue.args == {"rid": "req-9", "depth": 3}
    # a wait is a phase of no thread: it lands on the synthetic line, apart
    # from what is open on the caller's, and is nobody's child
    assert queue.depth == 0 and admit.depth == 0
    assert queue.wait and queue.thread == WAIT_THREAD
    assert not admit.wait and admit.tid != queue.tid
    assert tr.aggregates()["serve.queue"] == {"count": 1, "total_s": 0.25}
    with pytest.raises(ValueError, match="not registered in SpanName"):
        tr.record("serve.made_up", 0.0, 1.0)
    off = Tracer(enabled=False)
    off.record(SpanName.SERVE_QUEUE, 12.5, 0.25, rid="req-9")
    assert off.spans() == [] and off.aggregates() == {}
    with pytest.raises(ValueError):
        off.record("serve.made_up", 0.0, 1.0)


def test_a_span_whose_bookkeeping_raises_leaves_no_annotation_open():
    class Note:
        log = []

        def __enter__(self):
            self.log.append("enter")

        def __exit__(self, *exc):
            self.log.append("exit")

    class Registry:
        fail = True

        def note_host_sync(self, label):
            if self.fail:
                raise RuntimeError("barrier lost")

    reg = Registry()
    tr = Tracer(synced=True, sync_registry=reg)
    sp = tr.span(SpanName.TRAIN_STEP)
    sp._note = Note()
    with pytest.raises(RuntimeError):       # the entry barrier raises:
        sp.__enter__()                      # the annotation never opened
    assert Note.log == []
    reg.fail = False
    with pytest.raises(RuntimeError):
        with sp:
            reg.fail = True                 # the exit barrier raises:
    assert Note.log == ["enter", "exit"]    # it is closed all the same
    reg.fail = False
    with tr.span(SpanName.TRAIN_FWD):
        pass
    assert tr.spans()[-1].depth == 0        # and no depth leaked either time


def test_unregistered_name_raises_when_enabled():
    tr = Tracer()
    with pytest.raises(ValueError, match="not registered in SpanName"):
        tr.span("train.made_up")


def test_every_spanname_constant_is_in_the_frozen_set():
    for k, v in vars(SpanName).items():
        if not k.startswith("_") and isinstance(v, str):
            assert v in SPAN_NAMES


def test_thread_safety_and_thread_attribution():
    tr = Tracer()
    n, per = 8, 50

    def worker():
        for _ in range(per):
            with tr.span(SpanName.SERVE_TICK):
                pass

    threads = [threading.Thread(target=worker, name=f"w{i}")
               for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = tr.spans()
    assert len(recs) == n * per
    assert tr.aggregates()["serve.tick"]["count"] == n * per
    # thread attribution by NAME (the OS may reuse idents of joined
    # threads, so tids can collide across workers)
    assert {r.thread for r in recs} == {f"w{i}" for i in range(n)}
    # depth stayed 0 in every thread (no cross-thread stack bleed)
    assert all(r.depth == 0 for r in recs)


def test_capacity_bounds_records_but_not_aggregates():
    tr = Tracer(capacity=3)
    for _ in range(10):
        with tr.span(SpanName.TRAIN_FWD):
            pass
    assert len(tr.spans()) == 3
    assert tr.dropped == 7
    assert tr.aggregates()["train.fwd"]["count"] == 10
    tr.clear()
    assert tr.spans() == [] and tr.dropped == 0


def test_synced_mode_notes_host_syncs_on_the_registry():
    class FakeRegistry:
        def __init__(self):
            self.notes = []

        def note_host_sync(self, label, n=1):
            self.notes.append((label, n))

    reg = FakeRegistry()
    tr = Tracer(synced=True, sync_registry=reg)
    with tr.span(SpanName.TRAIN_OPTIMIZER):
        pass
    # one barrier per span edge, both reported to the discipline gate
    assert reg.notes == [("span.sync", 1), ("span.sync", 1)]
    # default mode never touches the registry
    reg2 = FakeRegistry()
    tr2 = Tracer(sync_registry=reg2)
    with tr2.span(SpanName.TRAIN_OPTIMIZER):
        pass
    assert reg2.notes == []


def test_span_inventory_sorted_distinct():
    tr = Tracer()
    for name in (SpanName.TRAIN_FWD, SpanName.TRAIN_BWD,
                 SpanName.TRAIN_FWD):
        with tr.span(name):
            pass
    assert tr.span_inventory() == ["train.bwd", "train.fwd"]
