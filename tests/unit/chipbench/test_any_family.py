"""A configuration of any family is files: the hooks a configuration file
names resolve, ``reduced`` may hold counts under the source's own names and
never a width, and the builder's config is compared with the file key by
key, whatever the family's attention looks like."""

import copy
import dataclasses
import json
import os

import pytest

from benchmarks.chip import builders, flops
from benchmarks.chip.metrics.readers import decode_roofline

from .common import (BENCH_DIR, LATENT, LATENT_SOURCE, ROOT, WIDTHS, benchmark,
                     check_configuration, differs_from_source)

BENCH = benchmark()


def _file(config):
    with open(os.path.join(ROOT, config["file"])) as f:
        return json.load(f)


def _decode_counts(config):
    """The counting function of each ``decode_roofline`` metric read in a
    cell of this configuration: the one its metric file's ``args`` name, or
    the reader's default."""
    import inspect
    default = inspect.signature(
        decode_roofline.read).parameters["count"].default
    cells = {w["name"] for w in BENCH["workloads"]
             if w["config"] == config["name"]}
    out = []
    for m in BENCH["per_layer"]:
        with open(os.path.join(BENCH_DIR, "metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        if spec["reader"] == "decode_roofline" \
                and cells & set(m.get("workloads", cells)):
            out.append(spec.get("args", {}).get("count", default))
    return out, default


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_hooks_resolve(config):
    """``init``, ``reference``, ``train_module`` and the decode kernel's
    counting function, for each configuration the benchmark has.  Nothing
    here knows a family's cache row: the count is the one the cell's metric
    file names, held only to ``f(config, tokens) -> (operations, bytes)``,
    both above 0; the dense ``2 H D`` row is asserted only where a metric
    file names no count and so takes the reader's default."""
    file = _file(config)
    cfg = builders.resolve(file["builder"])(file)
    assert callable(builders.resolve(file["init"]))
    reference = builders.resolve(file["reference"])
    assert reference.__name__.startswith("benchmarks.chip.reference.")
    assert callable(reference.forward)
    if "train_module" in file:      # a family that is only served has none
        assert callable(builders.resolve(file["train_module"]))
    counts, default = _decode_counts(config)
    for count in counts:
        ops, nbytes = builders.resolve(count)(cfg, 1000.0)
        assert ops > 0 and nbytes > 0, count
        assert builders.resolve(count)(cfg, 2000.0) == (2 * ops, 2 * nbytes)
        if count == default:
            assert (ops, nbytes) == flops.decode_call(1000.0, cfg.n_head,
                                                      cfg.head_dim)


@pytest.mark.parametrize("name,served", [("gpt2-medium", True),
                                         ("opt-1.3b", False)])
def test_the_default_count_is_the_dense_cache_row(name, served):
    """``flops.decode_call_dense``: K and V of ``H D`` elements a cached
    token, two bytes each, for the two dense configurations the benchmark
    has; the served one takes it by naming no count."""
    config = next(c for c in BENCH["configs"] if c["name"] == name)
    counts, default = _decode_counts(config)
    assert default == "flops.decode_call_dense"
    assert counts == ([default] if served else [])
    cfg = builders.resolve(_file(config)["builder"])(_file(config))
    ops, nbytes = builders.resolve(default)(cfg, 1000.0)
    assert nbytes == 1000.0 * 2 * cfg.n_head * cfg.head_dim * 2
    assert ops == 4.0 * 1000.0 * cfg.n_head * cfg.head_dim


def test_resolve_finds_modules_and_attributes():
    assert builders.resolve("builders.gpt2") is builders.gpt2
    assert builders.resolve("flops").__name__ == "benchmarks.chip.flops"
    with pytest.raises(AttributeError):
        builders.resolve("builders.no_such_family")
    with pytest.raises(ModuleNotFoundError):
        builders.resolve("no_such_module.f")


def test_no_kind_holds_a_familys_name():
    for name in os.listdir(os.path.join(BENCH_DIR, "kinds")):
        if name.endswith(".py"):
            with open(os.path.join(BENCH_DIR, "kinds", name)) as f:
                text = f.read()
            for word in ("gpt_reference", "models import gpt", "from_gpt",
                         "_batcher", "gpt.init"):
                assert word not in text, (name, word)


@pytest.mark.parametrize("key,refused", [
    ("num_hidden_layers", False), ("n_layer", False),
    ("n_routed_experts", False), ("vocab_size", False),
    ("num_attention_heads", False), ("max_position_embeddings", False),
    ("hidden_size", True), ("kv_lora_rank", True), ("q_lora_rank", True),
    ("qk_rope_head_dim", True), ("v_head_dim", True),
    ("moe_intermediate_size", True), ("intermediate_size", True),
    ("num_experts_per_tok", True), ("n_embd", True), ("ffn_dim", True),
    ("ssm_state_size", True), ("mamba_expand", True),
])
def test_widths(key, refused):
    assert bool(WIDTHS.search(key)) == refused


# ---- the two files the benchmark has, copied and changed ---------------

def _opt():
    """The file, its entry, and the numbers of the file as its source."""
    entry = next(c for c in BENCH["configs"] if c["name"] == "opt-1.3b")
    file = _file(entry)
    numbers = {k: v for k, v in file.items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
    return file, dict(entry), numbers


def _changed(file, entry, reduced, **keys):
    file, entry = {**copy.deepcopy(file), **keys}, dict(entry)
    file["reduced"] = entry["reduced"] = list(reduced)
    return file, entry


@pytest.mark.parametrize("reduced,keys", [
    (["num_hidden_layers"], {"num_hidden_layers": 6}),
    (["vocab_size"], {"vocab_size": 6284}),
    (["num_hidden_layers", "vocab_size", "num_attention_heads"],
     {"num_hidden_layers": 6, "vocab_size": 6284, "num_attention_heads": 8}),
], ids=["depth", "vocabulary", "depth-vocabulary-heads"])
def test_a_copy_with_a_count_cut_and_listed_passes(reduced, keys):
    source, entry, numbers = _opt()
    file, entry = _changed(source, entry, reduced, **keys)
    cfg = check_configuration(file, entry, numbers)
    assert cfg.n_layer == file["num_hidden_layers"]


@pytest.mark.parametrize("reduced,keys,says", [
    ([], {"ffn_dim": 4096}, "differs from the source"),
    ([], {"num_hidden_layers": 6}, "differs from the source"),
    (["ffn_dim"], {"ffn_dim": 4096}, "names a width"),
    (["hidden_size", "word_embed_proj_dim"],
     {"hidden_size": 1024, "word_embed_proj_dim": 1024}, "names a width"),
], ids=["width-unlisted", "depth-unlisted", "width-listed", "hidden-listed"])
def test_a_copy_with_a_width_changed_fails(reduced, keys, says):
    source, entry, numbers = _opt()
    file, entry = _changed(source, entry, reduced, **keys)
    with pytest.raises(AssertionError, match=says):
        check_configuration(file, entry, numbers)


def test_a_builder_that_does_not_carry_the_files_width_fails(monkeypatch):
    """A builder that returns a preset, whatever the file says."""
    source, entry, numbers = _opt()
    preset = builders.opt(source)

    def fixed(file):
        return preset
    fixed.published = builders.opt.published
    monkeypatch.setattr(builders, "fixed_preset", fixed, raising=False)
    file, entry = _changed(source, entry, [], builder="builders.fixed_preset")
    check_configuration(file, entry, numbers)          # the preset's own file
    file["ffn_dim"] = 4096
    with pytest.raises(AssertionError, match="departs from the file"):
        check_configuration(file, entry)
    fixed.published = lambda cfg: {"hidden_size": cfg.d_model}
    with pytest.raises(AssertionError, match="lacks the widths"):
        check_configuration(source | {"builder": "builders.fixed_preset"},
                            entry)


# ---- a family whose d_model is not n_head * head_dim -------------------

@dataclasses.dataclass(frozen=True)
class LatentConfig:
    d_model: int
    n_head: int
    d_ff: int
    d_expert: int
    q_rank: int
    kv_rank: int
    d_nope: int
    d_rope: int
    d_v: int
    experts_per_token: int
    n_layer: int
    n_experts: int
    vocab_size: int


def _latent(file):
    return LatentConfig(
        file["hidden_size"], file["num_attention_heads"],
        file["intermediate_size"], file["moe_intermediate_size"],
        file["q_lora_rank"], file["kv_lora_rank"], file["qk_nope_head_dim"],
        file["qk_rope_head_dim"], file["v_head_dim"],
        file["num_experts_per_tok"], file["num_hidden_layers"],
        file["n_routed_experts"], file["vocab_size"])


_latent.published = lambda c: {
    "hidden_size": c.d_model, "num_attention_heads": c.n_head,
    "intermediate_size": c.d_ff, "moe_intermediate_size": c.d_expert,
    "q_lora_rank": c.q_rank, "kv_lora_rank": c.kv_rank,
    "qk_nope_head_dim": c.d_nope, "qk_rope_head_dim": c.d_rope,
    "v_head_dim": c.d_v, "num_experts_per_tok": c.experts_per_token,
    "num_hidden_layers": c.n_layer, "n_routed_experts": c.n_experts,
    "vocab_size": c.vocab_size}


@pytest.fixture
def latent(monkeypatch):
    """The issue's one-chip cut: 32 chips share a layer, so 12 of 384
    experts, an eighth of the vocabulary, 1 dense + 4 expert layers."""
    monkeypatch.setattr(builders, "latent_family", _latent, raising=False)
    reduced = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    file = {**copy.deepcopy(LATENT), "source": LATENT_SOURCE,
            "builder": "builders.latent_family",
            "init": "builders.gpt_init", "reference": "reference.gpt_reference",
            "num_hidden_layers": 5, "n_routed_experts": 12,
            "vocab_size": 20480, "reduced": reduced,
            "deployment_chips_per_layer": 32,
            "published": {"num_hidden_layers": 61, "n_routed_experts": 384,
                          "vocab_size": 163840}}
    return file, {"source": LATENT_SOURCE, "reduced": reduced}


def test_latent_attention_with_depth_reduced_passes(latent):
    file, entry = latent
    cfg = check_configuration(file, entry, LATENT)
    assert cfg.d_model != cfg.n_head * (cfg.d_nope + cfg.d_rope)
    assert cfg.d_model != cfg.n_head * cfg.d_v
    assert differs_from_source(file, LATENT) == sorted(entry["reduced"])


@pytest.mark.parametrize("keys,says", [
    ({"kv_lora_rank": 256}, "differs from the source"),
    ({"qk_rope_head_dim": 32}, "differs from the source"),
    ({"rope_scaling": {"factor": 32, "type": "yarn",
                       "original_max_position_embeddings": 4096}},
     "differs from the source"),
    ({"num_hidden_layers": 4}, "the floor is 5"),
    ({"n_routed_experts": 6}, "fewer than 8"),
    ({"vocab_size": 16384}, "eighth of the vocabulary"),
    ({"num_hidden_layers": 62}, "num_hidden_layers"),
    ({"deployment_chips_per_layer": 0.5}, "chips"),
], ids=["latent-rank", "rope-dim", "nested-group", "depth-floor",
        "expert-floor", "vocabulary-floor", "more-than-published",
        "chips-not-whole"])
def test_latent_attention_with_a_width_changed_or_a_floor_broken_fails(
        latent, keys, says):
    file, entry = latent
    with pytest.raises(AssertionError, match=says):
        check_configuration({**file, **keys}, entry, LATENT)


def test_a_width_in_reduced_is_refused_even_when_stated(latent):
    file, entry = latent
    entry = {**entry, "reduced": entry["reduced"] + ["kv_lora_rank"]}
    file = {**file, "kv_lora_rank": 256, "reduced": entry["reduced"]}
    with pytest.raises(AssertionError, match="names a width"):
        check_configuration(file, entry, LATENT)
