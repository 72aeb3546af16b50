"""The readers the window-and-full configuration brings, on hand-made
contexts: the single-token sweeps of two pools against the tokens the
program says each pool's calls had to read (a ring's call is credited with
its ring's tokens, never the context's), the ratio of streamed to seen over
the window, and the chunk pass against the window's admissions; and
``mellum_family``'s counts at the cell's shapes."""

import json
import os
import types

import pytest

from benchmarks.chip import mellum_family
from benchmarks.chip.harness import Span
from benchmarks.chip.metrics.readers import (banded_decode_roofline,
                                             chunk_roofline)
from benchmarks.chip.trace.reduce import Op

from .common import ROOT

PEAKS = {"bf16_flops": 200e12, "hbm_bytes_per_s": 800e9}
SLOTS = 48


def _cfg():
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           "mellum2-12b-a2.5b-ep4.json")) as f:
        return mellum_family.build(json.load(f))


def _op(shape, start, dur, kernel=True):
    text = f"%k = {shape} custom-call(...)" + (
        ', custom_call_target="tpu_custom_call"' if kernel else "")
    return Op(0, "XLA Ops", "k", "custom-call", shape, text, start,
              start + dur)


def _reduced(ops, window_s=2.0):
    r = types.SimpleNamespace(ops=ops, devices=[0], window_s=window_s,
                              busy_s=sum(o.dur for o in ops))
    r.ops_time = lambda pred: sum(o.dur for o in ops if pred(o))
    return r


def _ctx(spans, ops, trace_len=2.0, seconds=10.0):
    """The window is [100, 110) on the spans' clock; the slice its last
    ``trace_len`` seconds but half a second: [107.5, 109.5)."""
    return types.SimpleNamespace(
        spans=spans, seconds=seconds, t_process=90.0, peaks=PEAKS,
        scalars={"opening_after_s": 10.0}, model_config=_cfg(),
        reduced=_reduced(ops, trace_len) if ops is not None else None,
        cell=types.SimpleNamespace(traffic={"trace_len_s": trace_len}))


def _sweep(t, full, window, streamed=(0, 0), calls=(0, 0)):
    return Span("serve.kv_sweep", t, 0.0, args={
        "full_tokens_live": full, "window_tokens_live": window,
        "full_tokens_streamed": streamed[0],
        "window_tokens_streamed": streamed[1],
        "full_calls": calls[0], "window_calls": calls[1]})


def test_a_rings_call_is_credited_with_its_rings_tokens():
    """Two kinds of calls, counters at both ends of the slice: ten ticks of
    48 rows at 5,000 tokens, 7 full layers seeing the context and 21 rings
    seeing 1,024 each.  The sweeps took 100 ms; what they had to read is the
    counters' growth at 2,048 B a token a layer, 3.44 + 2.06 GB over 800
    GB/s, so the share is known; crediting the rings with the context would
    read over 100%."""
    ticks, rows, context = 10, SLOTS, 5000
    full = ticks * rows * 7 * context
    ring = ticks * rows * 21 * 1024
    spans = [_sweep(100.0, 5, 7), _sweep(107.4, 1000, 2000),
             _sweep(108.0, 1000 + full // 2, 2000 + ring // 2),
             _sweep(109.4, 1000 + full, 2000 + ring,
                    calls=(7 * ticks, 21 * ticks)),
             _sweep(109.8, 10 ** 12, 10 ** 12)]      # after the slice
    row = f"bf16[{SLOTS},1,4096]"
    took = 0.100
    ops = [_op(row, 107.6 + i * 0.01, took / 28) for i in range(28)] + [
        _op(f"bf16[{SLOTS * 8},2304]", 108.5, 0.5),          # experts
        _op("bf16[32,512,128]", 108.6, 0.3),                 # a chunk pass
        _op(row, 108.7, 0.2, kernel=False)]
    ctx = _ctx(spans, ops)
    got = banded_decode_roofline.read(
        ctx, what="roofline", count="mellum_family.decode_count")
    least = (full + ring) * 2048 / PEAKS["hbm_bytes_per_s"]
    assert got == pytest.approx(100.0 * least / took)
    assert 100.0 * least / took < 100.0
    credited_by_context = ticks * rows * 28 * context * 2048 \
        / PEAKS["hbm_bytes_per_s"]
    assert 100.0 * credited_by_context / took > 105.0
    # memory bound: 8 operations a byte is far under the ridge
    ops_s, bytes_s = mellum_family.decode_count(ctx.model_config, full + ring)
    assert ops_s / PEAKS["bf16_flops"] < bytes_s / PEAKS["hbm_bytes_per_s"]


def test_streamed_over_live_is_the_counters_growth_over_the_window():
    spans = [_sweep(99.0, 10 ** 9, 10 ** 9, (10 ** 9, 10 ** 9)),   # the fill
             _sweep(100.5, 1000, 500, (1100, 500)),
             _sweep(105.0, 3000, 1500, (3300, 1500)),
             _sweep(109.9, 9000, 4500, (10000, 4500)),
             _sweep(110.5, 0, 0, (0, 0))]                          # the drain
    ctx = _ctx(spans, None)
    got = banded_decode_roofline.read(ctx, what="streamed_over_live")
    assert got == pytest.approx((8900 + 4000) / (8000 + 4000))
    # a roofline needs the trace; neither reads where the program records
    # no such counters (one pool: the parent)
    assert banded_decode_roofline.read(
        ctx, what="roofline", count="mellum_family.decode_count") is None
    assert banded_decode_roofline.read(
        _ctx([], None), what="streamed_over_live") is None
    assert banded_decode_roofline.read(
        _ctx([], [_op("bf16[48,1,4096]", 108.0, 0.1)]), what="roofline",
        count="mellum_family.decode_count") is None


def test_the_chunk_pass_is_held_to_the_windows_admissions():
    """Two admissions in the window (8 and 10 chunks of 512), 28 calls a
    chunk; the slice caught 56 calls, two chunks' worth, so the least time
    is 56 / 504 of what all of the window's prompt passes must do."""
    prefill = lambda t, chunks: Span("serve.prefill", t, 0.05, args={
        "tokens": chunks * 512 - 7, "start": 0, "chunk": 512,
        "padded": chunks * 512, "chunks": chunks})
    spans = [prefill(99.0, 3), prefill(101.0, 8), prefill(108.0, 10)]
    # (a call's result is rank 4 where a step takes a whole group of heads)
    ops = [_op("bf16[32,512,128]" if i % 2 else "bf16[4,8,512,128]",
               108.0 + i * 0.001, 0.0005)
           for i in range(56)] + [_op(f"bf16[{SLOTS},1,4096]", 109.0, 0.1)]
    ctx = _ctx(spans, ops)
    got = chunk_roofline.read(ctx, count="mellum_family.chunk_count")
    work, nbytes, calls = mellum_family.chunk_count(
        ctx.model_config, [(0, 8, 512), (0, 10, 512)])
    assert calls == 28 * 18
    least = 56 / calls * max(work / PEAKS["bf16_flops"],
                             nbytes / PEAKS["hbm_bytes_per_s"])
    assert got == pytest.approx(100.0 * least / (56 * 0.0005))
    assert chunk_roofline.read(_ctx([], ops),
                               count="mellum_family.chunk_count") is None
    assert chunk_roofline.read(_ctx(spans, None),
                               count="mellum_family.chunk_count") is None


def test_the_counts_at_the_cells_shapes():
    cfg = _cfg()
    # a tick of 48 rows at a live context of 4,900: 3.4 GB of whole rows in
    # 7 layers and 2.1 GB of rings in 21, against 6.8 GB of weights
    full, ring = 48 * 7 * 4900, 48 * 21 * 1024
    _, nbytes = mellum_family.decode_count(cfg, full + ring)
    assert round(48 * 7 * 4900 * 2048 / 1e9, 1) == 3.4
    assert round(48 * 21 * 1024 * 2048 / 1e9, 1) == 2.1
    assert nbytes == (full + ring) * 2048
    # 96 pairs a layer on 16 experts: every one visited, 6 pairs each
    ops, streamed = mellum_family.expert_count(cfg, 96 * 28, 16 * 28)
    assert round(streamed / 1e9, 2) == 5.55
    assert ops / streamed == 6.0        # operations a byte: under the ridge
    # a prompt of 4,096 in chunks of 512: a window layer's calls stop
    # growing at the window, a full layer's grow with the prefix
    work, _, calls = mellum_family.chunk_count(cfg, [(0, 8, 512)])
    one_geometry = 28 * (4096 * 4097 / 2) * 16384
    assert calls == 224 and 0.55 < work / one_geometry < 0.6
