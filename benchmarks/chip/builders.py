"""From a configuration file's published keys to the program's model config.

A configuration file names its builder (``"builder": "builders.gpt2"``,
resolved inside ``benchmarks/chip``); a later family brings a module of its
own and names that.  The builder reads the source's own key names, so the
file can be laid beside the published ``config.json``.
"""

from __future__ import annotations

import importlib

_ACTIVATIONS = {"gelu_new": "gelu", "relu": "relu"}


def resolve(dotted: str):
    """``"builders.gpt2"`` -> this package's ``builders.gpt2``."""
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
