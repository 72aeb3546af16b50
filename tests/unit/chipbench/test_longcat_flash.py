"""The LongCat-Flash configuration as files: its configuration file against
the catalog's config through ``check_configuration``, its counting
functions, its traffic file, and the reader of the zero-compute pairs'
share on hand-made records (a program without the counter, the parent of the
PR that added it, reads nothing and raises nothing)."""

import json
import os
import types

import pytest

from benchmarks.chip import longcat_flash_family as family
from benchmarks.chip.harness import Span
from benchmarks.chip.metrics.readers import moe_pairs_ratio

from .common import BENCH_DIR, ROOT, benchmark, check_configuration

NAME, CELL = "longcat-flash-chat-ep32", "longcat-serve-docqa-sat"
#: the catalog's config of meituan-longcat/LongCat-Flash-Chat, every key
SOURCE = {
    "attention_bias": False, "vocab_size": 131072, "hidden_size": 6144,
    "ffn_hidden_size": 12288, "expert_ffn_hidden_size": 2048,
    "num_layers": 28, "num_attention_heads": 64, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128,
    "qk_nope_head_dim": 128, "mla_scale_q_lora": True,
    "mla_scale_kv_lora": True, "routed_scaling_factor": 6,
    "n_routed_experts": 512, "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05, "rope_theta": 10000000,
    "attention_method": "MLA", "zero_expert_num": 256,
    "zero_expert_type": "identity", "moe_topk": 12}


def _entry():
    return next(c for c in benchmark()["configs"] if c["name"] == NAME)


def _file():
    with open(os.path.join(ROOT, _entry()["file"])) as f:
        return json.load(f)


def test_the_configuration_file_is_the_catalogs_config_cut_three_ways():
    file, entry = _file(), _entry()
    cfg = check_configuration(file, entry, SOURCE)
    assert entry["reduced"] == ["num_layers", "n_routed_experts",
                                "vocab_size"]
    assert file["published"] == {k: SOURCE[k] for k in entry["reduced"]}
    # every key of the source letter for letter, booleans and strings too
    assert {k: file[k] for k in SOURCE if k not in entry["reduced"]} == \
        {k: v for k, v in SOURCE.items() if k not in entry["reduced"]}
    assert file["deployment_chips_per_layer"] == 32 \
        and 32 * file["n_routed_experts"] == SOURCE["n_routed_experts"]
    assert (cfg.d_model, cfg.n_head, cfg.d_nope, cfg.d_rope, cfg.d_v) == \
        (6144, 64, 128, 64, 128)
    assert (cfg.q_rank, cfg.kv_rank, cfg.d_ff, cfg.d_expert) == \
        (1536, 512, 12288, 2048)
    assert (cfg.experts_per_token, cfg.n_experts, cfg.n_zero_experts,
            len(cfg.held)) == (12, 512, 256, 16)
    assert cfg.routed_scale == 6.0 and cfg.rope_theta == 1e7
    assert cfg.cache_layers == 8 and cfg.cache_row == (640,)
    for key in ("weights", "norm_topk_prob", "router_bias",
                "rotary_pairing", "e_score_correction_bias", "max_len",
                "mtp"):
        assert file["assumed"][key], key


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 4096), ("moe_topk", 8), ("zero_expert_num", 128),
    ("expert_ffn_hidden_size", 1024), ("routed_scaling_factor", 9)])
def test_a_width_or_the_gate_changed_is_not_this_configuration(key, value):
    with pytest.raises(AssertionError):
        check_configuration({**_file(), key: value}, _entry(), SOURCE)


def test_the_counting_functions():
    cfg = family.build(_file())
    # a cached token a sublayer: 64 heads score 576 and weigh 512 elements
    assert family.decode_count(cfg, 1.0) == (2.0 * 64 * (576 + 512),
                                             2.0 * 576)
    # a held pair: three products of 6144 x 2048; a zero-compute pair is none
    assert family.expert_count(cfg, 2.0, 1.0) == (
        2.0 * 2 * 3 * 6144 * 2048, 2.0 * 3 * 6144 * 2048)
    # one chunk of 1,024 from position 0: two calls a layer, 8 in all
    ops, nbytes, calls = family.chunk_count(cfg, [(0, 1, 1024)])
    assert calls == 8
    pairs = 1024 * 1025 / 2
    assert ops == pytest.approx(8 * (2 * 64 * 320 * pairs
                                     + 2 * 512 * 64 * 256 * 1024))
    assert nbytes == pytest.approx(8 * (1024 * 2 * 64 * 320
                                        + 1024 * 2 * 576))
    # the form the program runs costs more than what is counted
    absorbed = 8 * 2 * 64 * (576 + 512) * pairs
    assert 1.5 < absorbed / ops < 3.4
    # a second chunk sees the first
    more = family.chunk_count(cfg, [(0, 2, 1024)])
    assert more[2] == 16 and more[0] > 3 * ops


def test_the_traffic_is_the_issues():
    with open(os.path.join(BENCH_DIR, "traffic", "docqa-sat.json")) as f:
        traffic = json.load(f)
    cell = next(w for w in benchmark()["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        (NAME, "docqa-sat", 1)
    assert traffic["kind"] == "backlog"
    assert traffic["serving"]["max_len"] == 6144 \
        and traffic["serving"]["queue_capacity"] == 256 \
        and traffic["serving"]["prefill_chunk"] in (512, 1024, 2048) \
        and traffic["serving"]["slots"] in (48, 64)
    assert traffic["outstanding_per_slot"] == 2 and traffic["pairs"] == 512
    assert traffic["prompt_len"]["kind"] == traffic["output_len"]["kind"] \
        == "uniform"
    # ISSUE 57's band, or its one sanctioned fallback: half of it at the
    # same means
    band = tuple(traffic[k][edge] for k in ("prompt_len", "output_len")
                 for edge in ("min", "max"))
    assert band in ((2048, 4096, 96, 160), (2560, 3584, 112, 144))
    assert traffic["check"] == {"prompt_lens": [300, 1100, 2600, 4500],
                                "ticks": 6}
    assert traffic["fill_ticks"] == 8 and traffic["trace_len_s"] == 2.5


def _ctx(records):
    """A window [100, 145) on the spans' clock."""
    return types.SimpleNamespace(
        t_process=90.0, seconds=45.0, scalars={"opening_after_s": 10.0},
        spans=[Span(moe_pairs_ratio.SPAN, t, 0.0, args=dict(a))
               for t, a in records])


def test_the_share_is_one_counters_growth_over_anothers_in_the_window():
    before = {"held": 5, "routed": 1200, "zero": 400}
    ctx = _ctx([(99.0, {"held": 0, "routed": 0, "zero": 0}),   # the fill
                (101.0, before),
                (120.0, {"held": 50, "routed": 7200, "zero": 2500}),
                (144.0, {"held": 90, "routed": 13200, "zero": 4360}),
                (146.0, {"held": 99, "routed": 99999, "zero": 0})])
    assert moe_pairs_ratio.read(ctx, counter="zero", over="routed") == \
        pytest.approx(100.0 * (4360 - 400) / (13200 - 1200))
    assert moe_pairs_ratio.read(ctx, counter="held", over="routed") == \
        pytest.approx(100.0 * 85 / 12000)


@pytest.mark.parametrize("records", [
    [], [(101.0, {"routed": 10, "zero": 3})],
    # the parent's records: no such counter
    [(101.0, {"held": 1, "routed": 10}), (140.0, {"held": 2, "routed": 90})],
    # nothing routed in the window
    [(101.0, {"routed": 10, "zero": 3}), (140.0, {"routed": 10, "zero": 3})]],
    ids=["none", "one", "no_counter", "no_growth"])
def test_nothing_to_read_leaves_the_metric_out(records):
    assert moe_pairs_ratio.read(_ctx(records), counter="zero",
                                over="routed") is None


def test_the_new_metrics_are_the_cells_alone_and_the_lists_it_joined():
    bench = benchmark()
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == {
        "moe.zero_pair_share.docqa",
        "kernels.latent_chunk_attention_roofline.docqa",
        "kernels.latent_decode_attention_roofline.docqa",
        "moe.expert_ffn_roofline.docqa"}
    assert all(m["moves"] == "serve_tokens_per_s" and m["unit"] == "%"
               for m in mine.values())
    for name in mine:
        with open(os.path.join(BENCH_DIR, "metrics", name + ".json")) as f:
            assert json.load(f)["reader"] != "device_time_share"
    joined = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ()) and m["name"] not in mine]
    assert "serve_tokens_per_s" in joined \
        and "moe.expert_ffn_time_share.reason" in joined \
        and "moe.expert_load_max_over_mean.reason" in joined
    assert len(joined) == 20
