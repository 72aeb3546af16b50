"""The reduction from a trace to busy time, kernel time, collective time
and named gaps: its arithmetic on plain intervals, and the whole of it on
the small trace recorded on a v5e (``record_fixture.py``)."""

import os

import pytest

from benchmarks.chip import flops
from benchmarks.chip.trace import reduce as R

from .common import BENCH_DIR

FIXTURE = os.path.join(BENCH_DIR, "trace", "fixture.xplane.pb")


def op(device, start, end, text, line="XLA Ops"):
    name, opcode, shape = R.parse_hlo(text)
    return R.Op(device, line, name, opcode, shape, text, start, end)


def test_union_subtract_gaps():
    assert R.union([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert R.total([(0, 1), (0.5, 2), (3, 4)]) == 3
    assert R.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [
        (0, 1), (2, 4), (6, 9)]
    assert R.subtract([(0, 1), (5, 6)], [(0, 10)]) == []
    assert R.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]
    assert R.clip([(0, 5), (7, 9)], 4, 8) == [(4, 5), (7, 8)]


def test_parse_hlo():
    text = ('%copy.74 = bf16[64,16,1024,64]{3,2,1,0:T(8,128)(2,1)} '
            'copy(bf16[64,1024,16,64]{3,2,1,0} %x)')
    assert R.parse_hlo(text) == ("copy.74", "copy", "bf16[64,16,1024,64]")
    call = ('%closed_call.3 = (bf16[1024,1,64]{2,1,0}, f32[2]{0}) '
            'custom-call(s32[64]{0} %p), custom_call_target="tpu_custom_call"')
    o = op(0, 0, 1, call)
    assert (o.name, o.op, o.is_kernel) == ("closed_call.3", "custom-call",
                                           True)
    assert R.parse_hlo("PjitFunction(step)")[1] == "other"
    assert op(0, 0, 1, "%all-gather-start.2 = (bf16[8]{0}) "
              "all-gather-start(bf16[2]{0} %p)").is_collective
    assert not op(0, 0, 1, text).is_collective


def test_a_gap_is_named_by_the_local_host_event_that_overlaps_it_most():
    host = [R.HostEvent("t", "bench.train_step", -500.0, 500.0),
            R.HostEvent("t", "serve.tick", 0.0, 10.0),
            R.HostEvent("t", "np.asarray", 4.0, 6.1),
            R.HostEvent("t", "elsewhere", 20.0, 21.0)]
    assert R.name_gap((4.5, 6.0), host) == "np.asarray"
    assert R.name_gap((0.5, 3.0), host) == "serve.tick"
    # nothing local overlaps a quarter of it: the innermost call around it
    assert R.name_gap((11.0, 19.0), host) == "bench.train_step"
    assert R.name_gap((9.0, 20.4), host[1:]) == "serve.tick"
    assert R.name_gap((30.0, 31.0), host[1:]) == "(no host event)"


def test_a_wait_never_names_a_gap():
    """The tracer's ``waits`` line holds what a request saw (``serve.queue``),
    not what the host did: a wait that ends in a gap's tail, or covers it
    whole, leaves the gap to the thread that caused it."""
    host = [R.HostEvent(R.WAIT_LINE, "serve.queue", 3.0, 6.0),
            R.HostEvent("scheduler", "serve.admit", 5.5, 9.0),
            R.HostEvent("scheduler", "serve.prefill", 5.8, 6.4)]
    assert R.name_gap((5.0, 6.0), host) == "serve.admit"
    assert R.name_gap((3.5, 4.5), host) == "(no host event)"
    # the same events on a thread's own line would have named both
    as_thread = [R.HostEvent("t", e.name, e.start, e.end) for e in host]
    assert R.name_gap((5.0, 6.0), as_thread) == "serve.queue"
    assert R.name_gap((3.5, 4.5), as_thread) == "serve.queue"
    from deepspeed_tpu.telemetry.spans import WAIT_THREAD
    assert R.WAIT_LINE == WAIT_THREAD


def test_busy_idle_and_exposed_collectives_on_two_devices():
    fusion = "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop"
    done = "%all-gather-done.1 = bf16[8]{0} all-gather-done((bf16[8]) %s)"
    start = "%all-gather-start.1 = (bf16[8]{0}) all-gather-start(bf16[2] %p)"
    ops = []
    for d in (0, 1):
        ops += [op(d, 0.0, 4.0, fusion),
                # the gather is in flight from 2 to 7; the compute stream
                # runs nothing from 4 to 5 and waits for it from 5 to 7:
                # those 3 s are exposed, the 2 s under the fusion are not
                op(d, 2.0, 7.0, start, line="Async XLA Ops"),
                op(d, 5.0, 7.0, done),
                op(d, 8.0, 9.0, fusion)]
    r = R.reduce_trace(ops, [R.HostEvent("main", "input", 7.0, 8.0)],
                       window=(0.0, 10.0))
    assert r.devices == [0, 1] and r.window_s == 10.0
    assert r.busy_s == pytest.approx(4 + 2 + 1)
    assert r.collective_s() == pytest.approx(5.0)
    assert r.exposed_collective_s() == pytest.approx(3.0)
    assert r.top_ops[0] == ("fusion.1 fusion bf16[8]", pytest.approx(5.0))
    assert ("input", pytest.approx(1.0)) in r.idle_gaps
    assert r.ops_time(lambda o: o.op == "fusion") == pytest.approx(5.0)


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        R.reduce_trace([], [])


@pytest.fixture(scope="module")
def recorded():
    return R.read_device_ops(FIXTURE), R.read_host_events(FIXTURE)


def test_recorded_trace_layout(recorded):
    ops, host = recorded
    assert {o.device for o in ops} == {0}
    assert {o.line for o in ops} == {"XLA Ops", "Async XLA Ops"}
    assert sum(h.name == "fixture.iteration" for h in host) == 3
    # three iterations of one program: every op appears three times
    stream = [o for o in ops if o.line == "XLA Ops"]
    assert len(stream) % 3 == 0


def test_recorded_trace_reduction(recorded):
    ops, host = recorded
    r = R.reduce_trace(ops, host)
    stream = [o for o in ops if o.line == "XLA Ops"]
    assert r.busy_s <= sum(o.dur for o in stream) + 1e-12
    assert 0 < r.busy_s < r.window_s
    # the device idles through the host's pauses between the iterations:
    # the two longest gaps are those, each over the 2 ms slept
    (n1, g1), (n2, g2) = r.idle_gaps[:2]
    assert n1 == n2 == "fixture.pause" and g1 > 0.002 and g2 > 0.002
    idle = r.window_s - r.busy_s
    assert g1 + g2 == pytest.approx(idle, rel=0.01)
    kernels = [o for o in r.ops if o.is_kernel]
    assert len(kernels) == 9          # flash fwd, flash bwd, decode: x3
    assert r.collective_s() == 0.0


def test_recorded_kernels_against_their_shapes(recorded):
    """Operations and bytes from the shapes in the trace: the flash calls of
    the fixture are [BH=2, S=1024, D=64]."""
    ops, host = recorded
    r = R.reduce_trace(ops, host)
    by_kind = {}
    for o in r.ops:
        if o.is_kernel:
            by_kind.setdefault(flops.flash_call(o.shape), []).append(o)
    fwd = 4 * 2 * 1024 * 1024 * 64 * 0.5
    assert sorted(k[0] for k in by_kind) == [0.0, fwd, 2.5 * fwd]
    # no kernel beats the chip: its share of the roofline is under 100%
    for (need_ops, need_bytes), calls in by_kind.items():
        least = max(need_ops / 197e12, need_bytes / 819e9)
        assert all(least < o.dur for o in calls)
