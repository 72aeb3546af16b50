"""The Solar-Open2-250B configuration as files: its configuration file
against the catalog's config through ``check_configuration``, its counting
functions at the published widths, its traffic file, and the six metrics its
cell adds with the lists it joined (the siblings ``build`` refuses by name are
``test_family_conformance.py``'s, from the family's row of ``SPECS``)."""

import json
import os

from benchmarks.chip import solar_open2_family as family

from .common import (BENCH_DIR, ROOT, benchmark, check_configuration,
                     differs_from_source)

NAME, CELL = "solar-open2-250b-ep8", "solar2-serve-longctx-sat"
#: the catalog's config of upstage/Solar-Open2-250B, every key
SOURCE = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
    "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608,
    "intermediate_size": 10240, "moe_intermediate_size": 1280,
    "rms_norm_eps": 1e-05, "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
    "use_gqa_gate": True, "kda_use_full_proj": False,
    "kda_allow_neg_eigval": True, "n_routed_experts": 320,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]


def _entry():
    return next(c for c in benchmark()["configs"] if c["name"] == NAME)


def _file():
    with open(os.path.join(ROOT, _entry()["file"])) as f:
        return json.load(f)


def test_the_configuration_file_is_the_catalogs_config_but_for_three_counts():
    file, entry = _file(), _entry()
    cfg = check_configuration(file, entry, SOURCE)
    assert entry["reduced"] == REDUCED
    assert file["published"] == {"num_hidden_layers": 48,
                                 "n_routed_experts": 320,
                                 "vocab_size": 196608}
    # every key of the source letter for letter, booleans, the null and the
    # nested groups too, but the three counts
    assert differs_from_source(file, SOURCE) == sorted(REDUCED)
    assert {k: file[k] for k in SOURCE if k not in REDUCED} == \
        {k: v for k, v in SOURCE.items() if k not in REDUCED}
    assert (file["num_hidden_layers"], file["n_routed_experts"],
            file["vocab_size"]) == (4, 40, 24576)
    assert file["deployment_chips_per_layer"] == 8 \
        and file["layer_period"] == 4
    assert 8 * file["n_routed_experts"] == 320 \
        and 8 * file["vocab_size"] == 196608
    # every published width unchanged
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim) == \
        (4096, 64, 8, 128)
    assert (cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel) == (64, 128, 4)
    assert (cfg.d_expert, cfg.n_experts, cfg.experts_per_token,
            cfg.n_shared_experts, len(cfg.held)) == (1280, 320, 8, 1, 40)
    assert cfg.vocab_size == cfg.padded_vocab == 24576
    assert cfg.max_seq_len == 1048576 and cfg.routed_scale == 1.0
    carried = family.build.published(cfg)
    assert {"hidden_size", "head_dim", "intermediate_size",
            "moe_intermediate_size", "num_experts_per_tok"} <= set(carried)
    for key in ("deployment", "assumed", "precision", "precision_judged"):
        assert file[key], key
    assert {"kda_low_rank", "beta", "gqa_gate", "gate", "weights",
            "kda_chunk", "max_len", "intermediate_size"} <= set(
                file["assumed"])


def test_the_counts_at_the_published_widths():
    cfg = family.build(_file())
    # a cached token of the grouped layer: 4 H D operations, K and V of 8
    # heads of 128: 8 operations a byte
    assert family.decode_count(cfg, 1.0) == (32768.0, 4096.0)
    per = 3 * 4096 * 1280
    assert family.expert_count(cfg, 10, 3) == (20.0 * per, 6.0 * per)
    assert 2 * per == 31_457_280
    # a (live slot, KDA layer) row: 2 x 4,194,304 B, 7 operations an element
    ops, nbytes = family.state_step_count(cfg, 1)
    assert nbytes == 2 * 4_194_304 and ops / nbytes == 0.875
    ops, nbytes = family.chunk_scan_count(cfg, 1)
    assert ops == 64 * (10.0 * 64 * 128 + 6.0 * 128 * 128)
    assert nbytes == 4.0 * 7 * 8192
    # one chunk of 4 from position 2 in the one grouped layer: queries at
    # 2..5 see 3 + 4 + 5 + 6 keys
    ops, nbytes, calls = family.chunk_count(cfg, [(2, 1, 4)])
    assert calls == 1 and ops == 18 * 32768.0
    assert nbytes == 4 * 4.0 * 8192 + 4096.0 * 6
    two = family.chunk_count(cfg, [(0, 2, 4)])
    assert two[2] == 2 and two[0] == (10 + 26) * 32768.0


def _traffic():
    with open(os.path.join(BENCH_DIR, "traffic", "longctx-sat.json")) as f:
        return json.load(f)


def test_the_traffic_file_is_the_issues():
    traffic = _traffic()
    assert traffic["kind"] == "backlog"
    serving = traffic["serving"]
    assert serving["max_len"] == 16384 and serving["queue_capacity"] == 512
    # ISSUE 64's geometry, or its sanctioned fallbacks: 80 slots where 96
    # pass 16.2 GB, the chunk that the sweep keeps
    assert serving["slots"] in (96, 80) \
        and serving["prefill_chunk"] in (512, 1024)
    assert "paging" not in serving and "speculative" not in serving
    assert traffic["outstanding_per_slot"] == 2 and traffic["pairs"] == 512
    assert traffic["prompt_len"]["kind"] == traffic["output_len"]["kind"] \
        == "uniform"
    # the issue's band, or half of it at the same means
    band = tuple(traffic[k][edge] for k in ("prompt_len", "output_len")
                 for edge in ("min", "max"))
    assert band in ((4096, 6144, 6144, 8192), (4608, 5632, 6656, 7680))
    assert traffic["check"] == {"prompt_lens": [300, 1100, 3300, 9100],
                                "ticks": 6}
    assert traffic["fill_ticks"] == 8 and traffic["trace_len_s"] == 2.5
    # a prompt and its reply fit the slot, and one checked prompt is past
    # 8,192: a long row is compared
    assert band[1] + band[3] <= serving["max_len"]
    assert max(traffic["check"]["prompt_lens"]) > 8192


def test_a_pass_computes_the_chunk_it_is_credited_with():
    """``chunk_count`` credits every pass with ``chunk`` rows, and that is
    what a pass computes in this cell: the batcher's ladder has no width
    under the chunk in slots this long, so ``serve.prefill``'s ``padded`` is
    ``chunks x chunk`` and ``narrow`` 0 for every prompt of the band."""
    from deepspeed_tpu.serving.batcher import ladder_passes, pass_widths
    serving = _traffic()["serving"]
    chunk, smax = serving["prefill_chunk"], serving["max_len"]
    widths = pass_widths(chunk, smax)
    assert min(widths) == chunk
    for n in (4096, 4097, 5000, 6144):
        passes, _, narrow = ladder_passes(n, chunk, widths, first=1)
        assert narrow == 0 and passes == -(-n // chunk)


def test_the_new_metrics_are_the_cells_alone_and_the_lists_it_joined():
    bench = benchmark()
    mine = {m["name"]: m for m in bench["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == {
        "kernels.kda_decode_step_roofline.longctx",
        "kernels.kda_chunk_scan_roofline.longctx",
        "kda.state_time_share.longctx",
        "kernels.gqa_decode_attention_roofline.longctx",
        "kernels.chunk_attention_roofline.longctx",
        "moe.expert_ffn_roofline.longctx"}
    assert all(m["moves"] == "serve_tokens_per_s" and m["unit"] == "%"
               and m["source"] == "device_trace" for m in mine.values())
    readers = {}
    for name in mine:
        with open(os.path.join(BENCH_DIR, "metrics", name + ".json")) as f:
            readers[name] = json.load(f)
    # accepted readers, no new reader code
    assert {r["reader"] for r in readers.values()} == {
        "state_kernels", "decode_roofline", "chunk_roofline", "expert_ffn"}
    assert {r["args"].get("count") for r in readers.values()} == {
        "solar_open2_family.state_step_count",
        "solar_open2_family.chunk_scan_count",
        "solar_open2_family.decode_count", "solar_open2_family.chunk_count",
        "solar_open2_family.expert_count", None}
    joined = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if CELL in m.get("workloads", ()) and m["name"] not in mine]
    assert "serve_tokens_per_s" in joined \
        and "moe.expert_ffn_time_share.reason" in joined \
        and "moe.expert_load_max_over_mean.reason" in joined
    # every ``.decode`` metric every other backlog cell reports, and no other
    decode = [n for n in joined if n.endswith(".decode")]
    assert len(decode) == 17 and len(joined) == 20
    # appended: the last of each list it joined, of the cells and of the
    # configurations
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL, m["name"]
    assert bench["workloads"][-1]["name"] == CELL \
        and bench["configs"][-1]["name"] == NAME
    cell = bench["workloads"][-1]
    assert cell["chips"] == 1 and cell["traffic"] == "longctx-sat" \
        and cell["config"] == NAME
    assert len(bench["workloads"]) == 14 \
        and sum(w["chips"] == 4 for w in bench["workloads"]) == 1
