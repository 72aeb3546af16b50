"""From a profiler trace (``.xplane.pb``) to busy time, kernel time,
collective time and named idle gaps.

Layout of a TPU trace as this JAX writes it (see ``fixture.txt``): one plane
``/device:TPU:<n>`` per chip with the lines ``XLA Ops`` (the compute
stream; an event's name is its HLO instruction, text and all),
``Async XLA Ops`` (copies, slices and collectives in flight beside it),
``XLA Modules`` and ``Steps``; one plane ``/host:CPU`` with a line per
thread.  Device and host share a clock to within about a millisecond (in the
fixture a module starts on the device 1.1 ms before the host's call that
launched it), so a gap is named by the host event that covers most of it,
and gaps under a few milliseconds are named with that caveat.

Everything below the two ``read_*`` functions works on plain tuples, so the
arithmetic is tested without a trace.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]           # start, end, in seconds

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE, _ASYNC_LINE = "XLA Ops", "Async XLA Ops"
#: ``%name = shape opcode(operands...)``; the shape may be a tuple
_HLO = re.compile(r"^%(?P<name>\S+) = (?P<shape>\(.*?\)|\S+) "
                  r"(?P<op>[a-z][a-z0-9-]*)\(")
_COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|"
                         r"all-to-all|collective-permute|"
                         r"collective-broadcast)")
_WRAPPER_OPS = ("while", "conditional", "call")
#: the thread line of the program's tracer that holds recorded *waits*
#: (``telemetry/spans.py::WAIT_THREAD``: ``serve.queue``, a first token's
#: wait): a wait is what a request saw, not what the host did, and one that
#: ends in a gap's tail would name a gap it did not cause
WAIT_LINE = "waits"
#: host events that only wrap others and would name every gap
_WRAPPERS = re.compile(r"^(\$|PjitFunction|CommonPjRt|ThreadpoolListener|"
                       r"PythonRefManager)")


@dataclasses.dataclass(frozen=True)
class Op:
    """One device event of the compute stream or the async line."""
    device: int
    line: str
    name: str        # HLO instruction name, e.g. ``copy.74``
    op: str          # opcode, e.g. ``custom-call``
    shape: str       # result shape with layout stripped
    text: str        # the whole instruction
    start: float
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start

    @property
    def label(self) -> str:
        return f"{self.name} {self.op} {self.shape}"[:120]

    @property
    def is_collective(self) -> bool:
        return bool(_COLLECTIVE.match(self.op)) or bool(
            self.op in ("async-start", "async-done", "fusion")
            and _COLLECTIVE.match(self.name))

    @property
    def is_kernel(self) -> bool:
        return self.op == "custom-call" and "tpu_custom_call" in self.text


@dataclasses.dataclass(frozen=True)
class HostEvent:
    thread: str
    name: str
    start: float
    end: float


def _strip_layout(shape: str) -> str:
    return re.sub(r"\{[^}]*\}", "", shape)


def parse_hlo(text: str) -> Tuple[str, str, str]:
    """``(name, opcode, shape)`` of an HLO instruction's text; an event that
    is not one keeps its whole name and gets the opcode ``other``."""
    m = _HLO.match(text)
    if not m:
        return text[:80], "other", ""
    return m["name"], m["op"], _strip_layout(m["shape"])


def read_device_ops(path: str) -> List[Op]:
    from jax.profiler import ProfileData
    ops = []
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name not in (_OPS_LINE, _ASYNC_LINE):
                continue
            for e in line.events:
                name, op, shape = parse_hlo(e.name)
                ops.append(Op(int(m[1]), line.name, name, op, shape, e.name,
                              e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9))
    return ops


def read_host_events(path: str) -> List[HostEvent]:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns > 0 and not _WRAPPERS.match(e.name):
                    out.append(HostEvent(line.name, e.name, e.start_ns * 1e-9,
                                         (e.start_ns + e.duration_ns) * 1e-9))
    return out


# ------------------------------------------------------------- arithmetic

def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint sorted intervals covering the same points."""
    out: List[Interval] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in union(intervals))


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Iterable[Interval], b: Iterable[Interval]) -> List[Interval]:
    """The part of ``a`` that no interval of ``b`` covers."""
    out = []
    cover = union(b)
    for lo, hi in union(a):
        at = lo
        for c, d in cover:
            if d <= at:
                continue
            if c >= hi:
                break
            if c > at:
                out.append((at, c))
            at = max(at, d)
        if at < hi:
            out.append((at, hi))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return subtract([(lo, hi)], busy)


def name_gap(gap: Interval, events: Sequence[HostEvent]) -> str:
    """The host event that explains an idle gap.  First the local events
    (no longer than twenty times the gap) that overlap a quarter of it or
    more: the one that overlaps it most, the shorter of two that tie.
    Failing that, the shortest event that covers half of it (the innermost
    call around it); failing that, whatever overlaps it most; else ``(no
    host event)``.  Events of the ``waits`` line never name a gap."""
    a, b = gap
    local = cover = overlap = None
    for e in events:
        ov = min(b, e.end) - max(a, e.start)
        if ov <= 0 or e.thread == WAIT_LINE:
            continue
        dur = e.end - e.start
        if dur <= 20 * (b - a) and ov >= 0.25 * (b - a) and (
                local is None or (ov, -dur) > local[0]):
            local = ((ov, -dur), e)
        if ov >= 0.5 * (b - a) and (cover is None or dur < cover[0]):
            cover = (dur, e)
        if overlap is None or ov > overlap[0]:
            overlap = (ov, e)
    best = local or cover or overlap
    return best[1].name if best else "(no host event)"


@dataclasses.dataclass
class Reduced:
    """What the per-layer readers are given of a traced slice."""
    window_s: float
    devices: List[int]
    busy_s: float                        # mean over devices
    ops: List[Op]                        # compute stream, inside the window
    async_ops: List[Op]
    top_ops: List[Tuple[str, float]]     # label, seconds summed over calls
    idle_gaps: List[Tuple[str, float]]   # host event, seconds

    def ops_time(self, pred) -> float:
        """Seconds of compute-stream ops satisfying ``pred``, mean over
        devices."""
        return sum(o.dur for o in self.ops if pred(o)) / len(self.devices)

    def collective_intervals(self, device: int) -> List[Interval]:
        return [(o.start, o.end) for o in self.ops + self.async_ops
                if o.device == device and o.is_collective]

    def collective_s(self) -> float:
        return sum(total(self.collective_intervals(d))
                   for d in self.devices) / len(self.devices)

    def exposed_collective_s(self) -> float:
        """Collective time during which the device's compute stream runs
        nothing but the collective itself or the wait for it."""
        out = 0.0
        for d in self.devices:
            compute = [(o.start, o.end) for o in self.ops
                       if o.device == d and not o.is_collective]
            out += total(subtract(self.collective_intervals(d), compute))
        return out / len(self.devices)


def reduce_trace(ops: Sequence[Op], host: Sequence[HostEvent],
                 window: Optional[Interval] = None, top: int = 10) -> Reduced:
    """``window`` on the trace's clock; by default from the first device
    event's start to the last one's end."""
    # a loop or a call is one event around the events of its body: the
    # body's are kept, the wrapper's would count everything twice
    stream = [o for o in ops if o.line == _OPS_LINE
              and o.op not in _WRAPPER_OPS]
    if not stream:
        raise ValueError("the trace holds no device operation")
    lo, hi = window or (min(o.start for o in stream),
                        max(o.end for o in stream))
    inside = lambda seq: [dataclasses.replace(
        o, start=max(o.start, lo), end=min(o.end, hi)) for o in seq
        if min(o.end, hi) > max(o.start, lo)]
    stream = inside(stream)
    async_ops = inside([o for o in ops if o.line == _ASYNC_LINE])
    devices = sorted({o.device for o in stream})
    busy, idle = 0.0, []
    for d in devices:
        b = union((o.start, o.end) for o in stream if o.device == d)
        busy += total(b)
        idle += gaps(b, lo, hi)
    by_label: Dict[str, float] = {}
    for o in stream:
        by_label[o.label] = by_label.get(o.label, 0.0) + o.dur
    top_ops = sorted(by_label.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    return Reduced(
        window_s=hi - lo, devices=devices, busy_s=busy / len(devices),
        ops=stream, async_ops=async_ops,
        top_ops=[(k, v / len(devices)) for k, v in top_ops],
        idle_gaps=[(name_gap(g, host), g[1] - g[0]) for g in longest])
