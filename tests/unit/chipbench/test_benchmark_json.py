"""``BENCHMARK.json`` keeps to its contract, and every file it names by a
name is there."""

import importlib
import json
import os
import re

import pytest

from .common import BENCH_DIR, ROOT, benchmark, check_configuration

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = benchmark()
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
CELLS = [w["name"] for w in BENCH["workloads"]]


def _cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    # a full check of 24 cells must fit 43200 s
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in BENCH["end_to_end"]:
        allowed |= {"bound"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        allowed |= {"layer", "moves"}
        moved = next(m for m in BENCH["end_to_end"]
                     if m["name"] == metric["moves"])
        # the metric it should move is reported in each of its cells
        assert set(_cells_of(metric)) <= set(_cells_of(moved))
    assert set(metric) <= allowed
    assert set(_cells_of(metric)) <= set(CELLS)
    if "roofline" in metric["name"] or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_names_are_unique():
    for group in (METRICS, BENCH["workloads"], BENCH["configs"]):
        names = [g["name"] for g in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_cells_and_configs():
    cells = BENCH["workloads"]
    four = [w for w in cells if w["chips"] == 4]
    assert all(w["chips"] in (1, 4) for w in cells)
    assert len(four) <= max(1, len(cells) // 4)
    used = {w["config"] for w in cells}
    assert used == {c["name"] for c in BENCH["configs"]}
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    for w in cells:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        reported = [m for m in BENCH["end_to_end"] if w["name"] in _cells_of(m)]
        assert {"setup_s"} < {m["name"] for m in reported}
        assert any(w["name"] in _cells_of(m) for m in BENCH["per_layer"])


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file(config):
    """What holds for a configuration of any family (``common.py``): no
    width in ``reduced``, the hooks, the builder's config against every
    width of the file, the floors."""
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    with open(os.path.join(ROOT, config["file"])) as f:
        file = json.load(f)
    check_configuration(file, config)


def test_the_published_sizes():
    """The two configurations as their sources publish them."""
    from benchmarks.chip import builders
    from deepspeed_tpu.models import gpt
    files = {c["name"]: json.load(open(os.path.join(ROOT, c["file"])))
             for c in BENCH["configs"]}
    g = builders.gpt2(files["gpt2-medium"])
    assert (g.n_layer, g.d_model, g.n_head, g.ffn_dim, g.vocab_size,
            g.max_seq_len) == (24, 1024, 16, 4096, 50257, 1024)
    assert g == gpt.GPT2_350M          # the preset the repo has quoted
    o = builders.opt(files["opt-1.3b"])
    assert (o.n_layer, o.d_model, o.n_head, o.ffn_dim, o.vocab_size,
            o.max_seq_len, o.activation, o.pos_offset) == (
        24, 2048, 32, 8192, 50272, 2048, "relu", 2)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_traffic_file_and_kind(cell):
    path = os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")
    with open(path) as f:
        traffic = json.load(f)
    assert traffic["why"] and traffic["who"]
    assert os.path.exists(os.path.join(BENCH_DIR, "kinds",
                                       traffic["kind"] + ".py"))
    if traffic["kind"] == "open_loop":
        assert isinstance(traffic["rate_hz"], (int, float))   # never searched


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_file_and_reader(metric):
    with open(os.path.join(BENCH_DIR, "metrics",
                           metric["name"] + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module(
        "benchmarks.chip.metrics.readers." + spec["reader"])
    assert callable(reader.read) and spec["what"]


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in BENCH["paths"]:
        for d, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" in d:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel) and len(rel) <= 200, rel
