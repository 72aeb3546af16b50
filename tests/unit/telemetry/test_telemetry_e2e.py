"""Tier-1 telemetry e2e (the acceptance shape): a 5-step CPU train loop
and a 3-slot serving session, telemetry enabled, must emit the registered
span inventory with zero recompiles, stream online-MFU/step-time/memory
samples into a parseable ``metrics.jsonl``, export a schema-valid
Perfetto trace, and pass ``scripts/run_report.py`` report mode."""

import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt
from deepspeed_tpu.telemetry import (SpanName, Tracer, read_metrics,
                                     validate_trace, write_trace)
from deepspeed_tpu.utils.compile_watch import CompileWatch
from tests.unit.common import base_config, random_tokens, tiny_model

SEQ = 16
_RUN_REPORT = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "scripts", "run_report.py")


def _run_report():
    spec = importlib.util.spec_from_file_location("run_report", _RUN_REPORT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _engine(run_dir):
    engine, *_ = deepspeed_tpu.initialize(
        model=tiny_model(),
        config=base_config(micro_batch=1, extra={"telemetry": {
            "enabled": True,
            "metrics": {"path": os.path.join(run_dir, "metrics.jsonl"),
                        "interval_steps": 1}}}),
        rng=jax.random.PRNGKey(0))
    return engine


def _batch(rng):
    return random_tokens(8, SEQ, seed=int(rng.integers(0, 1 << 31)))


def test_train_loop_emits_span_inventory_metrics_and_trace(tmp_path):
    run_dir = str(tmp_path)
    engine = _engine(run_dir)
    rng = np.random.default_rng(0)
    from deepspeed_tpu.elasticity.elastic_agent import ElasticTrainRunner
    runner = ElasticTrainRunner(engine, os.path.join(run_dir, "ckpt"),
                                save_interval=2)

    with CompileWatch(engine.compile_registry) as watch:
        # warmup compiles both step protocols (micro/apply AND fused)
        for _ in range(2):
            engine.forward(_batch(rng))
            engine.backward()
            engine.step()
        engine.train_batch_fused(_batch(rng))
        watch.mark_warm()
        runner.resume()        # no checkpoint yet: fresh, span still lands
        out = runner.run([_batch(rng) for _ in range(5)], max_steps=5,
                         resume=False)
        assert out["steps"] == 5
        # the steady 5-step loop (fused path + periodic ckpt) compiled
        # nothing new — telemetry must not perturb compile discipline
        watch.assert_no_recompiles("telemetry-on train loop")

    inventory = set(engine.tracer.span_inventory())
    assert {SpanName.TRAIN_STEP, SpanName.TRAIN_FWD, SpanName.TRAIN_BWD,
            SpanName.TRAIN_OPTIMIZER, SpanName.TRAIN_HOST_SYNC,
            SpanName.TRAIN_DATA_FETCH, SpanName.CKPT_SAVE,
            SpanName.CKPT_COMMIT, SpanName.ELASTIC_RESUME} <= inventory

    # data-fetch spans: one per trained step
    assert engine.tracer.aggregates()["train.data_fetch"]["count"] == 5

    # metrics stream: per-step samples carrying the acceptance fields
    rows = read_metrics(os.path.join(run_dir, "metrics.jsonl"))
    stepped = [r for r in rows if "step" in r]
    assert len(stepped) >= 5
    m = stepped[-1]["m"]
    for field in ("train.mfu", "train.tflops", "train.tokens_per_s",
                  "mem.host_rss_bytes", "mem.hbm_live_bytes",
                  "compile.count", "compile.host_syncs", "train.steps"):
        assert field in m, field
    assert m["train.step_time_s"]["count"] >= 5
    assert m["train.step_time_s"]["p50"] > 0
    assert m["train.tokens_per_s"] > 0
    assert m["compile.count"] > 0

    # trace export: schema-valid and loadable
    trace_path = os.path.join(run_dir, "trace.json")
    obj = write_trace(trace_path, engine.tracer)
    assert validate_trace(obj) == []

    # the offline report joins the streams and exits 0
    rc = _run_report().main([run_dir, "--trace", trace_path])
    assert rc == 0


def test_serving_session_emits_spans_with_zero_recompiles(tmp_path):
    cfg = gpt.GPTConfig(vocab_size=256, max_seq_len=64, n_layer=1,
                        n_head=2, d_model=32, dtype=jnp.float32,
                        vocab_round_to=128)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    iengine = deepspeed_tpu.init_inference(model=(cfg, params),
                                           config={"dtype": "float32"})
    tracer = Tracer(name="serving")
    gw = iengine.serve(config={"slots": 3, "max_len": 32,
                               "prefill_chunk": 8}, tracer=tracer)
    rng = np.random.default_rng(1)
    handles = [gw.submit(
        rng.integers(1, 256, (int(rng.integers(3, 12)),)).astype(np.int32),
        max_new_tokens=3, seed=i) for i in range(6)]
    for h in handles:
        h.result(timeout=300.0)
    # after the stop: the loop pulls the tick it has in flight first
    gw.shutdown()
    snap = gw.snapshot()
    assert snap["recompiles"] == 0
    # an admission is one launch inside serve.prefill: no cache_alloc,
    # prefill_chunk or slot_write of its own (they stay with build_prefix
    # and the draft's lockstep admission); each launch's time on the
    # device is the registry's serve.device
    assert set(tracer.span_inventory()) == {
        SpanName.SERVE_QUEUE, SpanName.SERVE_ADMIT, SpanName.SERVE_PREFILL,
        SpanName.SERVE_TICK, SpanName.SERVE_PULL, SpanName.SERVE_HARVEST,
        SpanName.SERVE_FIRST_TOKEN, SpanName.SERVE_DEVICE}
    assert snap["launches_per_admission"] == 1.0
    # tick spans: one per decode tick; admits: one per request
    agg = tracer.aggregates()
    assert agg["serve.admit"]["count"] == 6
    assert agg["serve.tick"]["count"] == snap["ticks"] > 0
    # TTFT percentiles come from the shared histogram implementation
    assert gw.metrics.ttft.count == 6
    assert len(snap["ttft_s"]) == 6
    assert validate_trace(write_trace(str(tmp_path / "serve_trace.json"),
                                      tracer)) == []


def test_wall_clock_breakdown_enables_spans_without_telemetry(tmp_path):
    engine, *_ = deepspeed_tpu.initialize(
        model=tiny_model(),
        config=base_config(micro_batch=1,
                           extra={"wall_clock_breakdown": True,
                                  "steps_per_print": 2}),
        rng=jax.random.PRNGKey(0))
    assert engine.tracer.enabled       # breakdown alone turns spans on
    assert not engine.metrics_sampler.enabled
    rng = np.random.default_rng(0)
    for _ in range(4):
        engine.forward(_batch(rng))
        engine.backward()
        engine.step()
    # the old timer-log line now derives from span aggregates
    assert engine.tracer.aggregates()["train.fwd"]["count"] == 4


def test_disabled_by_default(tmp_path):
    engine, *_ = deepspeed_tpu.initialize(
        model=tiny_model(), config=base_config(micro_batch=1),
        rng=jax.random.PRNGKey(0))
    assert not engine.tracer.enabled
    assert not engine.metrics_sampler.enabled
    rng = np.random.default_rng(0)
    engine.train_batch_fused(_batch(rng))
    assert engine.tracer.spans() == []


def test_report_mode_flags_missing_rank_metrics(tmp_path):
    run_dir = str(tmp_path)
    # rank 0 present and parseable, rank 1 missing
    from deepspeed_tpu.telemetry.metrics import (MetricsRegistry,
                                                 MetricsSampler)
    MetricsSampler(MetricsRegistry(),
                   os.path.join(run_dir, "metrics.rank0.jsonl")).start()
    mod = _run_report()
    assert mod.main([run_dir, "--expect-rank-metrics", "1"]) == 0
    assert mod.main([run_dir, "--expect-rank-metrics", "2"]) == 1


def test_report_mode_prints_the_serving_line(tmp_path, capsys):
    """A gateway's gauges streamed through a sampler reach the report:
    ``live_block_share`` (the decode kernel's live share of the slot grid)
    beside occupancy and tokens/s."""
    from deepspeed_tpu.serving.metrics import ServingMetrics
    from deepspeed_tpu.telemetry.metrics import (MetricName, MetricsRegistry,
                                                 MetricsSampler)
    m = ServingMetrics()
    m.record_tick(active=3, slots=4, tokens=3, kv_blocks=(9, 16))
    snap = m.snapshot(queue_depth=2)
    sampler = MetricsSampler(MetricsRegistry(),
                             str(tmp_path / "metrics.jsonl"))
    sampler.attach_source(lambda: {
        MetricName.SERVE_QUEUE_DEPTH: snap["queue_depth"],
        MetricName.SERVE_OCCUPANCY: snap["slot_occupancy"],
        MetricName.SERVE_LIVE_BLOCK_SHARE: snap["live_block_share"]})
    sampler.start()
    mod = _run_report()
    assert mod.main([str(tmp_path)]) == 0
    assert "serving: queue_depth 2, occupancy 0.75, live_block_share " \
        "0.5625" in capsys.readouterr().out
    assert mod.main([str(tmp_path), "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["metrics"]["metrics.jsonl"]["serving"][
        "live_block_share"] == 9 / 16


def test_report_mode_prints_streamed_over_live(tmp_path, capsys):
    """``serve.kv_streamed_over_live`` (cached tokens the decode kernel's
    copies moved per token a query saw) reaches the report's serving line
    beside ``live_block_share``; a row at 300 of a 256-token block streams
    256 + 48 tokens with the dense kernel's 16-row tile."""
    from deepspeed_tpu.ops.pallas.decode_attention import sweep_token_counts
    from deepspeed_tpu.serving.metrics import ServingMetrics
    from deepspeed_tpu.telemetry.metrics import (MetricName, MetricsRegistry,
                                                 MetricsSampler)
    m = ServingMetrics()
    m.record_tick(active=1, slots=4, tokens=1, kv_blocks=(2, 16),
                  kv_by_kind={"full": sweep_token_counts(
                      [300], 1024, 256, copy_rows=16) + (1,)})
    snap = m.snapshot(queue_depth=0)
    assert (snap["kv_tokens_live"], snap["kv_tokens_streamed"]) == (301, 304)
    sampler = MetricsSampler(MetricsRegistry(),
                             str(tmp_path / "metrics.jsonl"))
    sampler.attach_source(lambda: {
        MetricName.SERVE_LIVE_BLOCK_SHARE: snap["live_block_share"],
        MetricName.SERVE_KV_STREAMED_OVER_LIVE: snap["streamed_over_live"]})
    sampler.start()
    mod = _run_report()
    assert mod.main([str(tmp_path)]) == 0
    assert "live_block_share 0.125, kv_streamed_over_live 1.01" in \
        capsys.readouterr().out
    assert mod.main([str(tmp_path), "--json"]) == 0
    report = json.loads(capsys.readouterr().out)
    assert report["metrics"]["metrics.jsonl"]["serving"][
        "kv_streamed_over_live"] == 304 / 301


@pytest.mark.parametrize("model,seq,ratio", [
    ("gpt", 1024, 88 / 128),     # gpt2m-train-s1024: a head is one block
    ("gpt", 2048, 304 / 384),    # opt1b3-train-zero3-4chip: 2 x 2 blocks
    ("bert", 1024, 1.0),         # kv_lens, not causal: the whole-block body
], ids=["gpt-s1024", "gpt-s2048", "bert-s1024"])
def test_report_prints_how_far_the_flash_causal_strips_engage(
        tmp_path, capsys, monkeypatch, model, seq, ratio):
    """The engine records ``flash_attention.causal_tile_plan`` of the step it
    traces as two counters; the report prints them and their ratio: the
    train cells' sequence lengths at the default blocks, and a BERT step.
    Beside them the kernels' call sites and those that read the packed qkv
    product in token-major rows: every one in a GPT-2-like step (two heads
    of 64 pair into a 128-lane block), none in BERT's (three arrays)."""
    monkeypatch.setenv("DS_TPU_PALLAS_INTERPRET", "1")
    from deepspeed_tpu.models import bert
    from deepspeed_tpu.runtime.model import from_gpt
    from deepspeed_tpu.parallel.mesh import ParallelDims, initialize_mesh
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, size=(1, seq + 1)).astype(np.int32)
    if model == "gpt":
        spec = from_gpt(gpt.GPTConfig(
            vocab_size=256, max_seq_len=seq, n_layer=1, n_head=2, d_model=128,
            dtype=jnp.float32, vocab_round_to=128))
        batch = {"tokens": tokens}
    else:
        spec = bert.model_spec(bert.BertConfig(
            vocab_size=256, max_seq_len=seq, n_layer=1, n_head=1, d_model=64,
            d_ff=128, dtype=jnp.float32))
        batch = {"tokens": tokens[:, :seq], "mlm_labels": tokens[:, :seq],
                 "seq_lens": np.asarray([seq - 24], np.int32)}
    engine, *_ = deepspeed_tpu.initialize(
        model=spec, mesh_manager=initialize_mesh(
            ParallelDims(dp=1), devices=jax.devices()[:1]),
        config=base_config(micro_batch=1, extra={"telemetry": {
            "enabled": True,
            "metrics": {"path": str(tmp_path / "metrics.jsonl"),
                        "interval_steps": 1}}}),
        rng=jax.random.PRNGKey(0))
    assert np.isfinite(float(engine.train_batch_fused(batch)))
    mod = _run_report()
    assert mod.main([str(tmp_path), "--json"]) == 0
    row = json.loads(capsys.readouterr().out)["metrics"]["metrics.jsonl"]
    assert row["flash_causal_tile_ratio"] == round(ratio, 4)
    assert row["flash_causal_tiles_visited"] == \
        ratio * row["flash_causal_tiles_square"] > 0
    packed = 1.0 if model == "gpt" else 0.0
    assert row["flash_calls"] > 0
    assert row["flash_packed_call_ratio"] == packed
    assert row["flash_calls_token_major_packed"] == \
        packed * row["flash_calls"]
    # the loss head of a CPU-sized GPT is the plain single pass; BERT's
    # head is its own and is not counted
    assert (row["head_logit_products"], row["head_row_chunks"]) == \
        ((3, 1) if model == "gpt" else (0, 0))
    assert mod.main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert f"= {round(ratio, 4)}" in out
    assert f"flash calls packed token-major / all {row['flash_calls_token_major_packed']} / " \
        f"{row['flash_calls']} = {packed}" in out
    assert ("head products 3 in 1 chunk(s)" in out) == (model == "gpt")
