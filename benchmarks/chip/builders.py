"""From a configuration file's published keys to the program's model config,
its weights and its training module.

A configuration file names its hooks, each resolved inside
``benchmarks/chip`` by ``resolve``, so no kind holds a family's name and a
later family brings a module of its own and names that:

``builder``    ``f(file) -> model config``.  It reads the source's own key
               names, so the file can be laid beside the published
               ``config.json``.  The function carries ``published(config)``:
               for each key of the file that it carries into the program's
               config, the value the config holds, so that the tests compare
               the two key by key (every width of the file must be there).
``init``       ``f(config, key, dtype=None) -> params``: the weights, made on
               the device from the key when called under ``jit``; ``dtype``
               is the type they are served in (None: as the family draws its
               master weights).
``reference``  a module under ``reference/`` with ``forward(file, params,
               tokens, last_n)``: the plain float32 forward.
``train_module`` (training cells) ``f(config) -> ModelSpec`` for
               ``deepspeed_tpu.initialize``.
"""

from __future__ import annotations

import importlib

_ACTIVATIONS = {"gelu_new": "gelu", "relu": "relu"}


def resolve(dotted: str):
    """``"builders.gpt2"`` -> this package's ``builders.gpt2``: a module of
    that dotted name under ``benchmarks/chip``, else an attribute of one."""
    try:
        return importlib.import_module(f"{__package__}.{dotted}")
    except ModuleNotFoundError as e:
        if e.name != f"{__package__}.{dotted}":
            raise
    module, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(f"{__package__}.{module}"), attr)


def _gpt_config(**fields):
    from deepspeed_tpu.models import gpt
    return gpt.GPTConfig(**fields)


def gpt2(file: dict):
    """GPT-2 (``GPT2LMHeadModel``): learned positions, GELU (tanh form),
    pre-LN, tied head."""
    assert file["tie_word_embeddings"] and file["n_ctx"] == file["n_positions"]
    return _gpt_config(
        vocab_size=file["vocab_size"], max_seq_len=file["n_positions"],
        n_layer=file["n_layer"], n_head=file["n_head"],
        d_model=file["n_embd"], d_ff=file["n_inner"],
        activation=_ACTIVATIONS[file["activation_function"]],
        pos_embed="learned", tie_word_embeddings=True)


gpt2.published = lambda cfg: {
    "n_embd": cfg.d_model, "n_inner": cfg.ffn_dim, "n_head": cfg.n_head,
    "n_layer": cfg.n_layer, "n_positions": cfg.max_seq_len,
    "vocab_size": cfg.vocab_size}


def opt(file: dict):
    """OPT (``OPTForCausalLM``) with ``do_layer_norm_before`` and no
    embedding projection: the same block as GPT-2 with ReLU and positions
    stored at an offset."""
    assert file["do_layer_norm_before"] and file["enable_bias"] \
        and file["tie_word_embeddings"] \
        and file["word_embed_proj_dim"] == file["hidden_size"]
    return _gpt_config(
        vocab_size=file["vocab_size"],
        max_seq_len=file["max_position_embeddings"],
        n_layer=file["num_hidden_layers"],
        n_head=file["num_attention_heads"], d_model=file["hidden_size"],
        d_ff=file["ffn_dim"],
        activation=_ACTIVATIONS[file["activation_function"]],
        pos_embed="learned", pos_offset=file["position_offset"],
        tie_word_embeddings=True)


opt.published = lambda cfg: {
    "hidden_size": cfg.d_model, "word_embed_proj_dim": cfg.d_model,
    "ffn_dim": cfg.ffn_dim, "num_attention_heads": cfg.n_head,
    "num_hidden_layers": cfg.n_layer,
    "max_position_embeddings": cfg.max_seq_len, "vocab_size": cfg.vocab_size}


def gpt_init(cfg, key, dtype=None):
    """``models/gpt.py::init``'s weights (std 0.02, float32 masters), cast
    to ``dtype`` where one is given."""
    import jax
    from deepspeed_tpu.models import gpt
    params = gpt.init(cfg, key)
    if dtype is None:
        return params
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), params)


def gpt_train_module(cfg):
    from deepspeed_tpu.runtime.model import from_gpt
    return from_gpt(cfg)
