"""A backlog above capacity: the queue is never empty, every slot is busy on
every tick, and what counts is the tokens completed per second."""

from __future__ import annotations

import gc
import time

from .. import loadgen
from ..harness import Context, TraceSlice, log
from . import _serving


def _diagnose(ctx, edges, at_open, at_close) -> None:
    """What tells one run from another: the admissions inside the window and
    the largest distance between two tick edges the poll saw (a stall of the
    machine reads seconds; the poll's own period is 4 ms)."""
    gaps = [b[0] - a[0] for a, b in zip(edges, edges[1:])]
    worst = max(range(len(gaps)), key=gaps.__getitem__)
    ctx.scalars["largest_tick_gap_s"] = gaps[worst]
    ctx.scalars["admitted_in_window"] = at_close[0] - at_open[0]
    log("backlog_detail", admitted_at_open=at_open[0],
        completed_at_open=at_open[1],
        admitted_in_window=at_close[0] - at_open[0],
        completed_in_window=at_close[1] - at_open[1],
        largest_tick_gap_s=round(gaps[worst], 4),
        at_s=round(edges[worst][0] - edges[0][0], 2))


def run(ctx: Context) -> None:
    engine, gateway = _serving.build_server(ctx)
    traffic = ctx.cell.traffic
    slots = gateway.config.slots
    outstanding = int(traffic["outstanding_per_slot"]) * slots
    requests = loadgen.backlog_requests(
        traffic, ctx.seed, engine.model_config.vocab_size, slots)
    submit = _serving.submitter(gateway)
    m = gateway.metrics

    def counters():
        """``(ticks, tokens, live slot-ticks)`` read between two ticks."""
        while True:
            a = m.ticks
            out = (a, m.tokens_out, m.active_slot_ticks)
            if m.ticks == a:
                return out

    state = {"t_open": None, "edges": [], "last_ticks": -1}
    slice_ = TraceSlice(ctx) if ctx.trace else None
    at, length = _serving.slice_of(ctx)

    def on_poll():
        now = time.monotonic()
        c = counters()
        if c[0] != state["last_ticks"]:     # a tick ended since last poll
            state["last_ticks"] = c[0]
            state["edges"].append((now,) + c)
        if slice_ and state["t_open"] is not None:
            t = now - state["t_open"]
            if not slice_.start_asked and t >= at:
                ctx.scalars["context_tokens_at_slice_start"] = \
                    _serving.live_context_tokens(backlog.sent)
                slice_.start_async()
            elif slice_.start_asked and not slice_.stop_asked \
                    and t >= at + length:
                ctx.scalars["context_tokens_at_slice_end"] = \
                    _serving.live_context_tokens(backlog.sent)
                slice_.stop_async()

    backlog = loadgen.Backlog(submit, requests, outstanding, time.sleep,
                              on_poll=on_poll)
    # fill: every slot admitted and ticking before the window opens
    fill_ticks = int(traffic["fill_ticks"])
    backlog.run_until(lambda: m.admitted > slots and m.ticks >= fill_ticks)
    ctx.phase("fill_slots")
    gc.collect()
    gc.freeze()
    gc.disable()
    at_open = (m.admitted, m.completed)
    state.update(edges=[], t_open=ctx.open_window())
    t_end = state["t_open"] + ctx.seconds
    backlog.run_until(lambda: time.monotonic() >= t_end)
    ctx.close_window()
    gc.enable()
    sent = backlog.sent

    edges = state["edges"]
    (t_a, ticks_a, tok_a, live_a), (t_b, ticks_b, tok_b, live_b) = \
        edges[0], edges[-1]
    n_ticks = ticks_b - ticks_a
    # from the first tick edge seen in the window to the last: whole ticks,
    # all their tokens, all that time
    ctx.scalars["tokens_per_s"] = (tok_b - tok_a) / (t_b - t_a)
    ctx.scalars["slot_occupancy"] = (live_b - live_a) / max(1, n_ticks * slots)
    ctx.scalars["slots"] = slots
    ctx.scalars["ticks"] = n_ticks
    _diagnose(ctx, edges, at_open, (m.admitted, m.completed))
    errors = [r for r in sent if r.error is not None or (
        r.handle.done() and r.handle.state != "done")]
    ctx.attempted, ctx.failed = len(sent), len(errors)
    ctx.checks["backlog_never_empty"] = (live_b - live_a) == n_ticks * slots
    ctx.checks["no_request_failed"] = not errors and backlog.refused is None
    _serving.harvest_spans(ctx, gateway)
    log("backlog", sent=len(sent), ticks=n_ticks, tokens=tok_b - tok_a,
        seconds=round(t_b - t_a, 4), occupancy=ctx.scalars["slot_occupancy"],
        failed=len(errors))
    if slice_:
        slice_.join()
        if not slice_.stop_asked:
            slice_.stop()
        slice_.reduce()
    _serving.finish(ctx, engine, gateway)
