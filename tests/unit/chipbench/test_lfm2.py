"""The LFM2-8B-A1B configuration as files: its configuration file against
the catalog's config through ``check_configuration``, its counting
functions, its traffic file, the metrics its cell adds and joins, and the
reader of a counter's growth over others on hand-made records (a program
without the span or the counter, the parent of the PR that added them, reads
nothing and raises nothing)."""

import json
import os
import types

import pytest

from benchmarks.chip import lfm2_family as family
from benchmarks.chip.harness import Span
from benchmarks.chip.metrics.readers import counter_growth

from .common import (BENCH_DIR, ROOT, benchmark, check_configuration,
                     differs_from_source)

NAME, CELL = "lfm2-8b-a1b", "lfm2-serve-assist-sat"
_C, _A = "conv", "full_attention"
#: the catalog's config of LiquidAI/LFM2-8B-A1B, every key
SOURCE = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [_C, _C, _A, _C, _C, _C, _A, _C, _C, _C, _A, _C, _C, _C,
                    _A, _C, _C, _C, _A, _C, _C, _A, _C, _C],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32,
    "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000,
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


def _entry():
    return next(c for c in benchmark()["configs"] if c["name"] == NAME)


def _file():
    with open(os.path.join(ROOT, _entry()["file"])) as f:
        return json.load(f)


def test_the_configuration_file_is_the_catalogs_config_cut_in_depth():
    file, entry = _file(), _entry()
    cfg = check_configuration(file, entry)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert file["published"] == {"num_hidden_layers": 24}
    # every key of the source letter for letter, booleans and strings too,
    # but the depth and the list of kinds that follows it: its first 14
    assert differs_from_source(file, SOURCE) == ["layer_types",
                                                 "num_hidden_layers"]
    assert file["layer_types"] == SOURCE["layer_types"][:14]
    rest = ("num_hidden_layers", "layer_types")
    assert {k: file[k] for k in SOURCE if k not in rest} == \
        {k: v for k, v in SOURCE.items() if k not in rest}
    assert file["deployment_chips_per_layer"] == 1 \
        and file["layer_period"] == 4
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim) == \
        (2048, 32, 8, 64)
    assert (cfg.d_ff, cfg.d_expert, cfg.conv_kernel, cfg.n_dense) == \
        (7168, 1792, 3, 2)
    assert (cfg.experts_per_token, cfg.n_experts, len(cfg.held)) == \
        (4, 32, 32)
    assert cfg.rope_theta == 1e6 and cfg.routed_scale == 1.0 \
        and cfg.vocab_size == cfg.padded_vocab == 65536
    # two leading dense layers, then three whole periods (a, c, c, c)
    assert [(u, n) for u, _, n in cfg.units] == [
        (("conv+dense",), 2), (("attention", "conv", "conv", "conv"), 3)]
    carried = family.build.published(cfg)
    assert {"conv_L_cache", "num_experts", "num_experts_per_tok",
            "num_dense_layers", "routed_scaling_factor", "rope_theta"} \
        <= set(carried)


def test_the_counts_at_the_published_widths():
    cfg = family.build(_file())
    # a cached token a layer: 4 H D operations, K and V of 8 heads of 64
    assert family.decode_count(cfg, 1.0) == (8192.0, 2048.0)
    per = 3 * 2048 * 1792
    assert family.expert_count(cfg, 10, 3) == (20.0 * per, 6.0 * per)
    # one chunk of 4 from position 2 in the three attention layers: queries
    # at 2..5 see 3 + 4 + 5 + 6 keys
    ops, nbytes, calls = family.chunk_count(cfg, [(2, 1, 4)])
    assert calls == 3 and ops == 3 * 18 * 8192.0
    assert nbytes == 3 * (4 * 4.0 * 2048 + 2048.0 * 6)
    two = family.chunk_count(cfg, [(0, 2, 4)])
    assert two[2] == 6 and two[0] == 3 * (10 + 26) * 8192.0


def test_the_traffic_file_is_the_issues():
    with open(os.path.join(BENCH_DIR, "traffic", "assist-sat.json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "backlog"
    serving = traffic["serving"]
    assert serving["max_len"] == 3072 and serving["queue_capacity"] == 1024
    assert serving["slots"] in (256, 192) \
        and serving["prefill_chunk"] in (256, 512, 1024)
    assert "paging" not in serving and "speculative" not in serving
    assert traffic["outstanding_per_slot"] == 2 and traffic["pairs"] == 512
    assert traffic["prompt_len"]["kind"] == traffic["output_len"]["kind"] \
        == "uniform"
    # ISSUE 61's band, or its one sanctioned fallback: half of it at the
    # same means
    band = tuple(traffic[k][edge] for k in ("prompt_len", "output_len")
                 for edge in ("min", "max"))
    assert band in ((256, 1792, 384, 1152), (640, 1408, 576, 960))
    # ISSUE 61's four prompts as it gave them, and a fifth: 1,026 ends TWO
    # tokens past a boundary of every swept chunk, so the tail carried
    # between two passes is in the compared positions themselves (at 1,300
    # alone a second pass that started from a zero tail read as a sound run:
    # PERF.md 6, PR 61)
    assert traffic["check"] == {
        "prompt_lens": [100, 600, 1300, 2500, 1026], "ticks": 6}
    assert all(1026 % c == 2 for c in (256, 512, 1024))
    assert traffic["fill_ticks"] == 8 and traffic["trace_len_s"] == 2.5
    # the deepest checked prompt is deeper than any timed one and past a
    # boundary of every swept chunk; the shortest is under every one
    assert max(traffic["check"]["prompt_lens"]) > band[1] \
        and min(traffic["check"]["prompt_lens"]) < 256


SPAN = "serve.state_steps"
ARGS = dict(span=SPAN, counter="conv_tokens_padded",
            over=["conv_tokens_real", "conv_tokens_padded"])


def _ctx(records, span=SPAN):
    """A window [100, 145) on the spans' clock."""
    return types.SimpleNamespace(
        t_process=90.0, seconds=45.0, scalars={"opening_after_s": 10.0},
        spans=[Span(span, t, 0.0, args=dict(a)) for t, a in records])


def _steps(rows, real, padded):
    return {"conv_rows_stepped": rows, "conv_tokens_real": real,
            "conv_tokens_padded": padded}


def test_the_share_is_a_counters_growth_over_the_sum_of_others():
    ctx = _ctx([(99.0, _steps(0, 0, 0)),                # the fill
                (101.0, _steps(500, 9000, 1000)),
                (120.0, _steps(900, 50000, 9000)),
                (144.0, _steps(1500, 99000, 21000)),
                (146.0, _steps(9999, 1, 99999))])      # the drain
    assert counter_growth.read(ctx, **ARGS) == pytest.approx(
        100.0 * 20000 / (90000 + 20000))
    # any span, any counters: the reader holds no name
    assert counter_growth.read(
        ctx, span=SPAN, counter="conv_rows_stepped",
        over=["conv_rows_stepped"]) == pytest.approx(100.0)
    assert counter_growth.read(ctx, span="serve.other", counter="a",
                               over=["a"]) is None


@pytest.mark.parametrize("records", [
    [], [(101.0, _steps(1, 10, 3))],
    # another family's records under the same span: no such counter
    [(101.0, {"ssm_rows_stepped": 1, "scan_tokens_real": 10,
              "scan_tokens_padded": 3}),
     (140.0, {"ssm_rows_stepped": 2, "scan_tokens_real": 90,
              "scan_tokens_padded": 9})],
    # no prompt pass in the window
    [(101.0, _steps(1, 10, 3)), (140.0, _steps(9, 10, 3))]],
    ids=["none", "one", "no_counter", "no_growth"])
def test_nothing_to_read_leaves_the_metric_out(records):
    assert counter_growth.read(_ctx(records), **ARGS) is None


def test_the_new_metrics_are_the_cells_alone_and_the_lists_it_joined():
    bench = benchmark()
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == {
        "kernels.gqa_decode_attention_roofline.assist",
        "kernels.chunk_attention_roofline.assist",
        "moe.expert_ffn_roofline.assist", "conv.padded_token_share.assist"}
    assert all(m["moves"] == "serve_tokens_per_s" and m["unit"] == "%"
               for m in mine.values())
    readers = {}
    for name in mine:
        with open(os.path.join(BENCH_DIR, "metrics", name + ".json")) as f:
            readers[name] = json.load(f)
    assert all(r["reader"] != "device_time_share" for r in readers.values())
    assert readers["conv.padded_token_share.assist"]["args"] == ARGS
    assert {r["args"].get("count") for r in readers.values()} == {
        "lfm2_family.decode_count", "lfm2_family.chunk_count",
        "lfm2_family.expert_count", None}
    joined = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ()) and m["name"] not in mine]
    assert "serve_tokens_per_s" in joined \
        and "moe.expert_ffn_time_share.reason" in joined \
        and "moe.expert_load_max_over_mean.reason" in joined
    # every ``.decode`` metric every other backlog cell reports, and no other
    decode = [n for n in joined if n.endswith(".decode")]
    assert len(decode) == 17 and len(joined) == 20
    cell, = (w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "assist-sat"
