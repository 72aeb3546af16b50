"""The reader of the selecting family's metrics on hand-made records: a
ratio is one counter's growth over another's inside the window, a roofline
the least time of the counted work over the device time of the tick's ops
under a scope, and a program without the records or the scopes (the parent
of the PR that added them) reads nothing and raises nothing."""

import json
import os
import time
import types

import pytest

from benchmarks.chip import dots3_family, harness
from benchmarks.chip.metrics.readers import device_time_share, sparse_select

from .common import BENCH_DIR, benchmark

COUNTS = ({"eligible": 1000, "selected": 400, "streamed": 1600,
           "ring_live": 300},
          {"eligible": 5000, "selected": 1400, "streamed": 5600,
           "ring_live": 1300})


def _config():
    with open(os.path.join(BENCH_DIR, "configs",
                           "dots3-note-prev-ep32.json")) as f:
        return dots3_family.build(json.load(f))


def _ctx(spans=True):
    t_process = time.perf_counter() - 100.0
    opening = t_process + 10.0          # the two clocks are one on Linux
    ctx = types.SimpleNamespace(
        t_process=t_process, seconds=45.0, scalars={"opening_after_s": 10.0},
        spans=[], reduced=None, model_config=None,
        peaks={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        cell=types.SimpleNamespace(traffic={"trace_len_s": 2.5}))
    if spans:
        ctx.spans = [harness.Span(sparse_select.SPAN, opening + at, 0.0,
                                  args=dict(c))
                     for at, c in zip((40.0, 44.4), COUNTS)]
    return ctx


def test_a_ratio_is_the_counters_growth_inside_the_window():
    ctx = _ctx()
    assert sparse_select.read(ctx, "ratio", counter="selected",
                              over="eligible") == pytest.approx(0.25)
    assert sparse_select.read(ctx, "ratio", counter="streamed",
                              over="selected") == pytest.approx(4.0)
    assert sparse_select.read(_ctx(spans=False), "ratio", counter="selected",
                              over="eligible") is None


def test_a_roofline_is_the_least_time_over_the_scopes_time(monkeypatch):
    cfg = _config()
    ctx = _ctx()
    ctx.model_config = cfg
    ctx.reduced = types.SimpleNamespace(busy_s=3.5, window_s=3.9)
    table = {("tick", "sparse_latent_attention/cache_read/index_select"): 0.4,
             ("tick", "sparse_latent_attention/cache_read"): 0.8,
             ("tick", "window_latent_attention/cache_read"): 0.2,
             ("admit", "admit_chunk/sparse_latent_attention/cache_read"): 9.0}
    monkeypatch.setattr(device_time_share, "_joined",
                        lambda r: (table, 0.0))
    specs = {m["name"]: m for m in benchmark()["per_layer"]
             if m["name"].endswith(".longgen")}
    for name, took, counter, count in (
            ("kernels.index_select_roofline.longgen", 0.4, "eligible",
             dots3_family.index_count),
            ("kernels.sparse_latent_decode_roofline.longgen", 0.8,
             "selected", dots3_family.sparse_decode_count),
            ("kernels.window_latent_decode_roofline.longgen", 0.2,
             "ring_live", dots3_family.window_decode_count)):
        assert specs[name]["unit"] == "%"
        with open(os.path.join(BENCH_DIR, "metrics", name + ".json")) as f:
            args = json.load(f)["args"]
        ops, nbytes = count(cfg, COUNTS[1][counter] - COUNTS[0][counter])
        least = max(ops / 197e12, nbytes / 819e9)
        got = sparse_select.read(ctx, **args)
        assert got == pytest.approx(100.0 * least / took), name
        assert 0.0 < got < 100.0
    # no op maps, no trace, or a counter the program does not keep: nothing
    monkeypatch.setattr(device_time_share, "_joined", lambda r: None)
    assert sparse_select.read(ctx, **args) is None
    ctx.reduced = None
    assert sparse_select.read(ctx, **args) is None


def test_the_counting_functions_count_what_the_mechanism_must_move():
    cfg = _config()
    assert dots3_family.index_count(cfg, 1.0) == (2 * 64 * 128, 256.0)
    assert dots3_family.sparse_decode_count(cfg, 1.0) == (
        2 * 128 * (576 + 512), 1152.0)
    assert dots3_family.window_decode_count(cfg, 1.0) == (
        2 * 64 * (1088 + 1024), 2176.0)
    assert dots3_family.expert_count(cfg, 2.0, 1.0) == (
        2 * 2 * 3 * 5120 * 1536, 2 * 3 * 5120 * 1536)
