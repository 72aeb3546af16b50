"""``device_time_share``: shares of the slice's busy time by program and by
scope from a hand-made reduction and a hand-made published table; nothing to
read, and never an exception, without a trace, without a table, or with a
program that has no such module."""

import json
import os
import sys
import types

import pytest

from benchmarks.chip.metrics.readers import device_time_share
from benchmarks.chip.trace import reduce as R
from deepspeed_tpu.telemetry import op_maps

from .common import BENCH_DIR, benchmark

NEW = [m for m in benchmark()["per_layer"]
       if json.load(open(os.path.join(
           BENCH_DIR, "metrics", m["name"] + ".json")))["reader"]
       == "device_time_share"]


def _event(device, start, end, name, shape, opcode="fusion"):
    text = f"%{name} = {shape}{{0}} {opcode}(f32[4]{{0}} %p)"
    return R.Op(device, "XLA Ops", *R.parse_hlo(text), text, start, end)


def _row(name, shape, scope, opcode="fusion", flags=()):
    return {"name": name, "opcode": opcode, "shape": shape, "scope": scope,
            "flags": list(flags)}


@pytest.fixture
def table():
    op_maps.clear_published()
    op_maps.publish("serving", "tick", [
        _row("fusion.1", "bf16[8]", "mlp"),
        _row("fusion.2", "bf16[4]", "cache_update"),
        _row("fusion.5", "bf16[2]", "sweep")])
    op_maps.publish("serving", "admit", [
        _row("fusion.3", "bf16[8]", "admit_chunk/mlp"),
        _row("fusion.4", "bf16[8]", "admit_chunk/head+admit_chunk/norm"),
        _row("take.1", "f32[8]", "admit_head", "dynamic-slice"),
        _row("fusion.5", "bf16[2]", "admit_row_cache", flags=["copied"]),
        _row("fusion.6", "bf16[6]", "admit_slot_write")])
    op_maps.publish("engine", "fused", [
        _row("fusion.10", "f32[8]", "qkv/norm", flags=["backward"]),
        _row("flash_bwd.1", "f32[8]", "attention/flash_bwd", "custom-call"),
        _row("fusion.11", "f32[8]", "attn_out+mlp/norm"),
        _row("fusion.12", "f32[8]", "mlp", flags=["remat"]),
        _row("fusion.13", "f32[8]", "optimizer")])
    yield
    op_maps.clear_published()
    device_time_share._JOINED.clear()


def _ctx(events, window=(0.0, 10.0)):
    return types.SimpleNamespace(reduced=R.reduce_trace(events, [], window))


def _serving():
    return _ctx([
        _event(0, 0.0, 2.0, "fusion.1", "bf16[8]"),           # tick / mlp
        _event(0, 2.0, 2.5, "fusion.5", "bf16[2]"),           # the tick's
        _event(0, 2.5, 3.5, "fusion.2", "bf16[4]"),           # cache_update
        _event(0, 4.0, 5.0, "fusion.3", "bf16[8]"),           # admit
        _event(0, 5.0, 5.5, "fusion.5", "bf16[2]"),           # the admit's
        _event(0, 5.5, 5.75, "fusion.4", "bf16[8]"),
        _event(0, 5.75, 6.0, "take.1", "f32[8]", "dynamic-slice"),
        _event(0, 6.0, 7.0, "fusion.6", "bf16[6]"),
        _event(0, 7.0, 8.0, "fusion.99", "bf16[1]")])         # nobody's


def test_shares_by_program_and_scope(table, capsys):
    ctx = _serving()
    read = device_time_share.read
    assert ctx.reduced.busy_s == pytest.approx(7.5)
    share = lambda s: pytest.approx(100.0 * s / 7.5)
    assert read(ctx, program="^admit") == share(3.0)
    assert read(ctx, program="^tick") == share(3.5)
    assert read(ctx, program="^tick", scope="cache_update") == share(1.0)
    # a fusion that spans scopes counts under the one that owns its root
    assert read(ctx, program="^admit",
                scope="^admit_head|^admit_chunk/head") == share(0.5)
    assert read(ctx, program="^admit", scope="norm") == share(0.0)
    assert read(ctx, program="^admit",
                scope="^admit_(row_cache|slot_write)") == share(1.5)
    assert read(ctx, unjoined=True) == share(1.0)
    assert read(ctx) == share(6.5)
    # one [scopes] line a run, naming the breakdown's ops
    out = [line for line in capsys.readouterr().out.splitlines()
           if line.startswith("[scopes]")]
    assert len(out) == 1
    assert "fusion.1=tick/mlp" in out[0] and "fusion.99=unjoined" in out[0]
    assert "fusion.4=admit/admit_chunk/head+admit_chunk/norm" in out[0]
    assert "fusion.6=admit/admit_slot_write" in out[0]


def test_the_train_steps_scopes_on_two_devices(table):
    events = []
    for d in (0, 1):
        events += [_event(d, 0.0, 1.0, "fusion.10", "f32[8]"),
                   _event(d, 1.0, 3.0, "flash_bwd.1", "f32[8]",
                          "custom-call"),
                   _event(d, 3.0, 4.0, "fusion.11", "f32[8]"),
                   _event(d, 4.0, 7.0, "fusion.12", "f32[8]"),
                   _event(d, 7.0, 8.0, "fusion.13", "f32[8]")]
    ctx = _ctx(events)
    read = device_time_share.read
    attention = "^(qkv|attention|attn_out)"
    assert read(ctx, scope=attention) == pytest.approx(50.0)
    assert read(ctx, scope="^mlp") == pytest.approx(37.5)
    assert read(ctx, scope="^optimizer") == pytest.approx(12.5)
    assert read(ctx, unjoined=True) == 0.0


def test_nothing_to_read_is_none_and_never_an_exception(table, monkeypatch):
    read = device_time_share.read
    rehearsal = types.SimpleNamespace(reduced=None)
    assert read(rehearsal, program="^admit") is None
    assert read(rehearsal, unjoined=True) is None
    ctx = _serving()
    op_maps.clear_published()               # a process that built no map
    assert read(ctx, program="^admit") is None
    assert read(ctx, unjoined=True) is None
    # a program without the module (the parent of the PR that added it)
    device_time_share._JOINED.clear()
    monkeypatch.setitem(sys.modules, "deepspeed_tpu.telemetry.op_maps", None)
    assert read(ctx, program="^admit") is None
    assert read(ctx, unjoined=True) is None


@pytest.mark.parametrize("metric", NEW, ids=lambda m: m["name"])
def test_each_new_metric_reads_a_share_of_a_hand_made_slice(table, metric):
    """Every entry this reader serves: its file's arguments are the
    reader's, its unit and source what a share of a trace is, and on the
    hand-made slices it reads a share between 0 and 100."""
    with open(os.path.join(BENCH_DIR, "metrics",
                           metric["name"] + ".json")) as f:
        spec = json.load(f)
    assert (metric["unit"], metric["source"], metric["better"]) == (
        "%", "device_trace", "lower")
    assert metric["workloads"]
    value = device_time_share.read(_serving(), **spec["args"])
    assert value is not None and 0.0 <= value <= 100.0
    assert device_time_share.read(
        types.SimpleNamespace(reduced=None), **spec["args"]) is None


def test_the_new_entries_are_the_issues():
    assert len(NEW) == 16
    by_layer = {}
    for m in NEW:
        by_layer.setdefault(m["layer"], []).append(m["name"])
    assert {k: len(v) for k, v in by_layer.items()} == {
        "batcher": 4, "model": 6, "train engine": 2, "device": 4}
