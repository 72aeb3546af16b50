"""Metrics units: instruments, registry validation, the JSONL sampler
(torn-line tolerance included), and the online-MFU arithmetic against a
hand-computed fixture."""

import json
import threading

import pytest

from deepspeed_tpu.telemetry.metrics import (METRIC_NAMES, Counter, Gauge,
                                             Histogram, MetricName,
                                             MetricsRegistry,
                                             MetricsSampler, analytic_mfu,
                                             peak_flops_per_chip,
                                             read_metrics)


# ---------------------------------------------------------- instruments
def test_counter_gauge_basics():
    c = Counter("x")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = Gauge("y")
    assert g.value is None
    g.set(2)
    g.set(3.5)
    assert g.value == 3.5


def test_histogram_percentiles_and_reservoir_bound():
    h = Histogram("t", cap=100)
    for i in range(1, 101):
        h.observe(float(i))
    assert h.count == 100
    assert h.sum == pytest.approx(5050.0)
    assert h.percentile(50) == pytest.approx(50.0, abs=1)
    assert h.percentile(99) == pytest.approx(99.0, abs=1)
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["mean"] == pytest.approx(50.5)
    # past the cap: count/sum exact, reservoir keeps the newest
    for i in range(101, 201):
        h.observe(float(i))
    assert h.count == 200
    assert len(h.values()) == 100
    assert min(h.values()) == 101.0
    # empty histogram
    assert Histogram("e").percentile(50) is None


def test_histogram_percentile_tiny_reservoirs():
    # 0-, 1-, 2-sample reservoirs must return defined values (nearest-rank
    # ceil model), never raise — critical_path summarizes per-phase stats
    # over journals with a single decomposable request
    assert Histogram("0").percentile(50) is None
    assert Histogram("0").percentile(99) is None
    one = Histogram("1")
    one.observe(7.0)
    assert one.percentile(0) == 7.0
    assert one.percentile(50) == 7.0
    assert one.percentile(99) == 7.0
    assert one.percentile(100) == 7.0
    two = Histogram("2")
    two.observe(10.0)
    two.observe(20.0)
    assert two.percentile(0) == 10.0
    assert two.percentile(50) == 10.0
    assert two.percentile(51) == 20.0
    assert two.percentile(99) == 20.0
    assert two.percentile(100) == 20.0
    # out-of-range quantiles clamp instead of indexing out of bounds
    assert two.percentile(-5) == 10.0
    assert two.percentile(250) == 20.0


def test_histogram_thread_safety():
    h = Histogram("t", cap=10000)
    threads = [threading.Thread(
        target=lambda: [h.observe(1.0) for _ in range(500)])
        for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == 2000 and h.sum == pytest.approx(2000.0)


# ------------------------------------------------------------- registry
def test_registry_validates_names_and_caches_instruments():
    reg = MetricsRegistry()
    g = reg.gauge(MetricName.MFU)
    assert reg.gauge(MetricName.MFU) is g
    with pytest.raises(ValueError, match="not registered in MetricName"):
        reg.gauge("train.bogus")
    with pytest.raises(ValueError):
        reg.counter("nope")
    with pytest.raises(ValueError):
        reg.histogram("nope")
    g.set(0.41)
    reg.histogram(MetricName.STEP_TIME_S).observe(0.25)
    snap = reg.snapshot()
    assert snap["train.mfu"] == 0.41
    assert snap["train.step_time_s"]["count"] == 1


def test_every_metricname_constant_is_registered():
    for k, v in vars(MetricName).items():
        if not k.startswith("_") and isinstance(v, str):
            assert v in METRIC_NAMES


# -------------------------------------------------------------- sampler
def test_sampler_writes_rows_and_sources_merge(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    reg = MetricsRegistry()
    s = MetricsSampler(reg, path, rank=3, interval_steps=2)
    s.attach_source(lambda: {MetricName.ROLLBACKS: 7})
    s.start()
    reg.gauge(MetricName.TOKENS_PER_S).set(123.0)
    s.sample(step=4)
    rows = read_metrics(path)
    assert len(rows) == 2
    assert rows[0]["kind"] == "metrics.sample" and rows[0]["rank"] == 3
    assert "step" not in rows[0]
    assert rows[1]["step"] == 4
    assert rows[1]["m"]["train.tokens_per_s"] == 123.0
    assert rows[1]["m"]["elastic.rollbacks"] == 7
    # cadence: interval_steps=2
    assert s.should_sample(4) and not s.should_sample(5)


def test_sampler_source_failure_is_survived(tmp_path):
    path = str(tmp_path / "m.jsonl")
    s = MetricsSampler(MetricsRegistry(), path)
    s.attach_source(lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    s.attach_source(lambda: {MetricName.RESTARTS: 1})
    s.sample(step=1)
    rows = read_metrics(path)
    assert rows[-1]["m"]["elastic.restarts"] == 1


def test_sampler_source_names_validated(tmp_path):
    s = MetricsSampler(MetricsRegistry(), str(tmp_path / "m.jsonl"))
    s.attach_source(lambda: {"train.made_up": 1})
    with pytest.raises(ValueError, match="not registered"):
        s.sample(step=1)


def test_sampler_disabled_without_path():
    s = MetricsSampler(MetricsRegistry(), None)
    assert not s.enabled
    assert s.sample(step=1) is None
    assert not s.should_sample(1)


def test_read_metrics_skips_torn_and_garbage_lines(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    good = {"ts": 1.0, "seq": 1, "rank": 0, "kind": "metrics.sample",
            "m": {"train.steps": 3}}
    with open(path, "w") as f:
        f.write(json.dumps(good) + "\n")
        f.write("not json at all\n")
        f.write(json.dumps(good)[: len(json.dumps(good)) // 2])  # torn tail
    rows = read_metrics(path)
    assert len(rows) == 1
    assert rows[0]["m"]["train.steps"] == 3
    assert read_metrics(str(tmp_path / "absent.jsonl")) == []


# ----------------------------------------------------------- online MFU
def test_analytic_mfu_hand_computed_fixture():
    # 1000 tokens/s × 2e9 FLOPs/token = 2e12 FLOP/s achieved = 2 TFLOP/s;
    # on 2 chips of 100 TFLOP/s peak → MFU = 2e12 / 2e14 = 0.01
    out = analytic_mfu(tokens_per_s=1000.0, flops_per_token=2e9,
                       peak_flops=100e12, n_chips=2)
    assert out["tflops"] == pytest.approx(2.0)
    assert out["mfu"] == pytest.approx(0.01)
    # unknown peak: MFU reports 0, achieved TFLOP/s still real
    out = analytic_mfu(1000.0, 2e9, None)
    assert out["mfu"] == 0.0 and out["tflops"] == pytest.approx(2.0)


def test_analytic_mfu_matches_bench_formula_for_gpt():
    # the benchmark's arithmetic: mfu = tok/s * f / (peak * chips)
    from deepspeed_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=512, max_seq_len=128, n_layer=2,
                        n_head=4, d_model=128)
    f = gpt.flops_per_token(cfg)
    out = analytic_mfu(5000.0, f, 197e12, n_chips=1)
    assert out["mfu"] == pytest.approx(5000.0 * f / 197e12)


def test_peak_table_lookup():
    assert peak_flops_per_chip("TPU v5e") == 197e12
    assert peak_flops_per_chip("TPU v4") == 275e12
    assert peak_flops_per_chip("cpu") is None
    assert peak_flops_per_chip("") is None
