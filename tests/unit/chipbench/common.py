"""Shared by the chip benchmark's tests: where things are, and the tiny
sizes every cell is rehearsed at on the CPU.  The sizes are data under
``tiny/``, found by the configuration's, the kind's and the traffic file's
name (``tiny/README.txt``), so a cell added as files rehearses as it is."""

import json
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
BENCH_DIR = os.path.join(ROOT, "benchmarks", "chip")


def _read(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return _read(ROOT, "BENCHMARK.json")


def tiny_config(config: str) -> dict:
    """The keys of one configuration file cut to 2 layers of d64."""
    path = os.path.join(HERE, "tiny", "configs", config + ".json")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"configuration {config!r} has no tiny sizes: add {path} "
            f"(see tiny/README.txt)")
    return _read(path)


def tiny_overrides(cell: dict) -> dict:
    """The rehearsal's overrides for one ``workloads`` entry."""
    kind = _read(BENCH_DIR, "traffic", cell["traffic"] + ".json")["kind"]
    out = _read(HERE, "tiny", "kinds", kind + ".json")
    own = os.path.join(HERE, "tiny", "traffic", cell["traffic"] + ".json")
    if os.path.exists(own):
        for key, value in _read(own).items():
            out[key] = {**out[key], **value} if isinstance(
                out.get(key), dict) else value
    return {"config_file": tiny_config(cell["config"]), **out}


# ---- what holds for a configuration of any family ----------------------

#: a key that ``reduced`` may never name: a hidden, intermediate, latent,
#: state or projection size, a key that ends in ``_dim`` or ``_rank``, a head
#: size, an expansion factor, the experts per token.  ``hidden`` is anchored
#: to the size, so that ``num_hidden_layers`` (depth, the first cut the
#: model-configs guide prescribes) may stand there under its own name.
WIDTHS = re.compile(
    r"(_dim|_rank)$|hidden_size|hidden_dim|intermediate|latent|head_size|"
    r"state_size|proj_size|expansion|expand|per_tok|"
    r"^(n_embd|n_inner|d_model|d_ff|d_inner|d_state|d_conv)$")
#: the catalog's config of moonshotai/Kimi-K2.7-Code, the keys that give its
#: shape: latent attention, so 7168 is not 64 heads of anything
LATENT = {
    "hidden_size": 7168, "intermediate_size": 18432, "kv_lora_rank": 512,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "v_head_dim": 128, "num_attention_heads": 64, "num_key_value_heads": 64,
    "moe_intermediate_size": 2048, "n_routed_experts": 384,
    "n_shared_experts": 1, "num_experts_per_tok": 8,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "num_hidden_layers": 61, "vocab_size": 163840,
    "max_position_embeddings": 262144, "rope_theta": 50000,
    "rope_scaling": {"factor": 64, "type": "yarn",
                     "original_max_position_embeddings": 4096}}
LATENT_SOURCE = ("https://huggingface.co/moonshotai/Kimi-K2.7-Code/blob/main/"
                 "config.json")


def differs_from_source(file: dict, source_config: dict) -> list:
    """The top-level keys of the source's config that the file changes or
    leaves out: numbers compared, nested groups compared whole."""
    return sorted(
        k for k, v in source_config.items()
        if isinstance(v, (int, float, dict, list)) and not isinstance(v, bool)
        and file.get(k, None) != v)


def check_configuration(file: dict, entry: dict, source_config=None):
    """What ``test_configuration_file`` holds a configuration file and its
    entry of ``BENCHMARK.json`` to, for a model of any family; returns the
    program's config as the file's builder makes it.

    - ``reduced`` is the entry's, names at most 16 keys and no width;
    - the hooks the kinds resolve are there: ``builder``, ``init`` and
      ``reference`` (a module with ``forward``);
    - the builder's config reproduces every width of the file, compared key
      by key through the ``published`` mapping the builder carries, and
      every other key the mapping holds;
    - against the source's config, where the caller has it (the file a copy
      was made from, the published config of a test's family): nothing
      differs but what ``reduced`` lists;
    - where the file states them, the cut's floors: under ``published`` the
      published count of each reduced count, the held one no larger; a
      whole period and four layers after the leading dense ones
      (``first_k_dense_replace``, ``layer_period``), 8 routed experts, an
      eighth of the vocabulary; ``deployment_chips_per_layer`` a whole
      number of chips."""
    from benchmarks.chip.builders import resolve
    assert file["source"] == entry["source"]
    assert file["reduced"] == entry["reduced"] and len(entry["reduced"]) <= 16
    named = [k for k in entry["reduced"] if WIDTHS.search(k)]
    assert not named, f"reduced names a width: {named}"
    builder = resolve(file["builder"])
    assert callable(resolve(file["init"]))
    assert callable(resolve(file["reference"]).forward)
    cfg = builder(file)
    carried = builder.published(cfg)
    widths = [k for k, v in file.items() if WIDTHS.search(k)
              and isinstance(v, (int, float)) and not isinstance(v, bool)]
    missing = [k for k in widths if k not in carried]
    assert not missing, f"the builder's published() lacks the widths {missing}"
    wrong = {k: (file.get(k), v) for k, v in carried.items()
             if file.get(k) is not None and file.get(k) != v}
    assert not wrong, f"the builder's config departs from the file: {wrong}"
    if source_config is not None:
        changed = [k for k in differs_from_source(file, source_config)
                   if k not in entry["reduced"]]
        assert not changed, f"differs from the source, not in reduced: {changed}"
    published = file.get("published", {})
    for key in entry["reduced"]:
        if key in published:
            assert file[key] <= published[key], key
        if "layer" in key and key in file:
            dense = file.get("first_k_dense_replace", 0)
            need = dense + max(4, file.get("layer_period", 1))
            assert file[key] >= need, \
                f"{key}: {file[key]} layers held, the floor is {need}"
        elif "expert" in key and key in file:
            assert file[key] >= 8, f"{key}: fewer than 8 routed experts held"
        elif "vocab" in key and key in published:
            assert 8 * file[key] >= published[key], \
                f"{key}: under an eighth of the vocabulary"
    chips = file.get("deployment_chips_per_layer", 1)
    assert isinstance(chips, int) and chips >= 1, \
        f"deployment_chips_per_layer: {chips} is no whole number of chips"
    return cfg
