"""Open-loop traffic at a fixed rate below the knee: requests are sent when
they are due, whatever the server has finished, and every time is counted
from the instant a request was due."""

from __future__ import annotations

import gc
import time

from .. import loadgen, stats
from ..harness import Context, TraceSlice, log
from . import _serving


def _longest_standstill(seen) -> float:
    """The longest time the generator saw the server's tick counter stand
    still: between the first and the last of consecutive sends that read
    the same count.  With requests live it reads 0 or a few milliseconds; a
    stall of the machine or of the device reads its length."""
    longest, first = 0.0, None
    for t, ticks in seen:
        if first is None or ticks != first[1]:
            first = (t, ticks)
        longest = max(longest, t - first[0])
    return longest


def run(ctx: Context) -> None:
    engine, gateway = _serving.build_server(ctx)
    traffic = ctx.cell.traffic
    vocab = engine.model_config.vocab_size
    m = gateway.metrics
    send = _serving.submitter(gateway)
    seen = []       # (time, tick counter) at every send

    def submit(req):
        seen.append((time.monotonic(), m.ticks))
        return send(req)
    schedule = loadgen.open_loop_schedule(traffic, ctx.seed, ctx.seconds,
                                          vocab)
    # steady state before the window: the standing population first, then
    # the arrival schedule's lead-in
    standing = loadgen.standing_population(traffic, ctx.seed, vocab)
    for req in standing:
        req.handle = submit(req)
    lead = float(traffic["lead_s"])
    t_open = time.monotonic() + lead
    head = [r for r in schedule if not r.counted]
    body = [r for r in schedule if r.counted]
    loadgen.drive_open_loop(submit, head, t_open, time.monotonic, time.sleep)
    rest = t_open - time.monotonic()
    if rest > 0:
        time.sleep(rest)
    ctx.phase("standing_population_and_lead_in")

    slice_ = TraceSlice(ctx) if ctx.trace else None
    at, length = _serving.slice_of(ctx)
    gc.collect()
    gc.freeze()
    gc.disable()
    del seen[:]
    t_open = ctx.open_window()
    occ_open = (m.active_slot_ticks, m.slot_ticks)
    if slice_:      # the loop below is split around the traced slice
        early = [r for r in body if r.due_s < at]
        mid = [r for r in body if at <= r.due_s < at + length]
        late = [r for r in body if r.due_s >= at + length]
        loadgen.drive_open_loop(submit, early, t_open, time.monotonic,
                                time.sleep)
        time.sleep(max(0.0, t_open + at - time.monotonic()))
        ctx.scalars["context_tokens_at_slice_start"] = \
            _serving.live_context_tokens(standing + schedule)
        slice_.start_async()
        loadgen.drive_open_loop(submit, mid, t_open, time.monotonic,
                                time.sleep)
        time.sleep(max(0.0, t_open + at + length - time.monotonic()))
        ctx.scalars["context_tokens_at_slice_end"] = \
            _serving.live_context_tokens(standing + schedule)
        slice_.stop_async()
        loadgen.drive_open_loop(submit, late, t_open, time.monotonic,
                                time.sleep)
    else:
        loadgen.drive_open_loop(submit, body, t_open, time.monotonic,
                                time.sleep)
    time.sleep(max(0.0, t_open + ctx.seconds - time.monotonic()))
    t_close = t_open + ctx.seconds
    queue_at_close = m.submitted - m.admitted - m.rejected
    occ_close = (m.active_slot_ticks, m.slot_ticks)
    ctx.close_window()
    # the sample is every request due in the window: wait for their first
    # tokens (not for their last: no drain of whole replies)
    deadline = time.monotonic() + float(traffic["drain_timeout_s"])
    for r in body:
        while r.handle is not None and r.handle.t_first_token is None \
                and not r.handle.done() and time.monotonic() < deadline:
            time.sleep(0.005)
    gc.enable()

    ttft, late_ms, failed = [], [], 0
    for r in body:
        late_ms.append((r.sent_s - r.due_s) * 1e3)
        h = r.handle
        if h is None or h.t_first_token is None or h.state in (
                "rejected", "failed", "timeout", "cancelled"):
            failed += 1
            continue
        ttft.append((h.t_first_token - (t_open + r.due_s)) * 1e3)
    tpot, itl = [], []
    for r in schedule:      # lead-in requests too: no drain, so a request
        h = r.handle        # may have begun before the window
        if h is None or h.state != "done" or not (
                t_open <= h.t_done < t_close):
            continue
        gap = stats.tpot_s(h.t_first_token, h.t_done, h.tokens_out)
        if gap is not None:
            tpot.append(gap * 1e3)
    ctx.samples.update(ttft_ms=ttft, tpot_ms=tpot, late_ms=late_ms)
    ctx.attempted, ctx.failed = len(body), failed
    if ttft:        # steadier statistics beside the tail, for a run's reader
        ctx.scalars["ttft_p50_ms"] = stats.percentile(ttft, 50.0)
        ctx.scalars["ttft_p90_ms"] = stats.percentile(ttft, 90.0)
    ctx.scalars["due_in_window"] = len(body)
    ctx.scalars["queue_at_close"] = queue_at_close
    ctx.scalars["slot_occupancy"] = (occ_close[0] - occ_open[0]) / max(
        1, occ_close[1] - occ_open[1])
    ctx.scalars["slots"] = gateway.config.slots
    ctx.scalars["largest_tick_gap_s"] = _longest_standstill(seen)
    ctx.checks["all_first_tokens"] = failed == 0
    _serving.harvest_spans(ctx, gateway)
    # queue wait: due -> the start of the request's serve.admit span; the
    # gateway admits in submission order (one priority), so the k-th admit
    # span belongs to the k-th request submitted
    admits = sorted((s for s in ctx.spans if s.name == "serve.admit"),
                    key=lambda s: s.t0)
    order = sorted((r for r in standing + schedule if r.handle is not None),
                   key=lambda r: r.handle.t_submit)
    ctx.samples["queue_wait_ms"] = [
        (s.t0 - (t_open + r.due_s)) * 1e3
        for s, r in zip(admits, order) if r.counted]
    log("open_loop", due_in_window=len(body), first_tokens=len(ttft),
        failed=failed, finished_in_window=len(tpot),
        standing=len(standing), lead_in=len(head),
        queue_at_close=queue_at_close,
        largest_tick_gap_s=round(ctx.scalars["largest_tick_gap_s"], 4),
        beyond_p95=stats.samples_beyond(len(ttft), 95.0))
    if slice_:
        slice_.reduce()
    _serving.finish(ctx, engine, gateway)
