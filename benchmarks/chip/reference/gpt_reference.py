"""The plain reference: a GPT-2 / OPT decoder forward in float32 ``jax.numpy``.

One function serves both configurations from their files' published keys.
It uses no kernel, no cache, no scan and no code of the program under test:
embedding plus learned positions (OPT stores them at an offset), then per
layer ``x += W_o . softmax(causal(q k^T / sqrt(d_head))) v`` on
``LayerNorm(x)`` and ``x += W_2 . act(W_1 . LayerNorm(x))``, a final
LayerNorm and the tied head.  Every matrix product runs at
``jax.default_matmul_precision("highest")``: on a TPU a float32 product
otherwise runs in bf16 passes.

Departures from the published code: none in the mathematics.  Weights come
in the program's stacked layout (``blocks.wqkv`` is ``[L, d, 3, H, d_head]``
and so on) because the program draws them; they are upcast to float32 layer
by layer.  Only the last ``last`` positions go through the head, and the
vocabulary's padding rows are cut off.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: the source's key for each size, by family of configuration file
_KEYS = {
    "n_layer": ("n_layer", "num_hidden_layers"),
    "n_head": ("n_head", "num_attention_heads"),
    "d_model": ("n_embd", "hidden_size"),
    "vocab": ("vocab_size",),
    "activation": ("activation_function",),
}


def sizes(file: dict) -> dict:
    """The sizes the forward needs, read from a configuration file."""
    out = {k: next(file[name] for name in names if name in file)
           for k, names in _KEYS.items()}
    out["pos_offset"] = file.get("position_offset", 0)
    out["eps"] = file.get("layer_norm_epsilon", 1e-5)
    return out


def _layer_norm(x, scale, bias, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def _act(x, name: str):
    if name == "relu":
        return jnp.maximum(x, 0.0)
    if name == "gelu_new":   # GPT-2's tanh form
        return 0.5 * x * (1.0 + jnp.tanh(
            0.7978845608028654 * (x + 0.044715 * x ** 3)))
    raise ValueError(f"the reference has no activation {name!r}")


def forward(file: dict, params, tokens, last: int):
    """Float32 logits ``[B, last, vocab]`` at the last ``last`` positions
    of ``tokens`` ``[B, S]``."""
    z = sizes(file)
    f32 = lambda a: jnp.asarray(a).astype(jnp.float32)
    B, S = tokens.shape
    H = z["n_head"]
    with jax.default_matmul_precision("highest"):
        x = f32(params["wte"])[tokens] + f32(params["wpe"])[
            jnp.arange(S) + z["pos_offset"]][None]
        causal = jnp.tril(jnp.ones((S, S), bool))
        blocks = params["blocks"]
        for l in range(z["n_layer"]):
            p = {k: f32(v[l]) for k, v in blocks.items()}
            h = _layer_norm(x, p["ln1_scale"], p["ln1_bias"], z["eps"])
            qkv = jnp.einsum("bsd,dthe->bsthe", h, p["wqkv"]) + p["bqkv"]
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            s = jnp.einsum("bqhe,bkhe->bhqk", q, k) / jnp.sqrt(
                jnp.float32(q.shape[-1]))
            s = jnp.where(causal[None, None], s, -jnp.inf)
            a = jnp.einsum("bhqk,bkhe->bqhe", jax.nn.softmax(s, -1), v)
            x = x + jnp.einsum("bqhe,hed->bqd", a, p["wo"]) + p["bo"]
            h = _layer_norm(x, p["ln2_scale"], p["ln2_bias"], z["eps"])
            ff = _act(h @ p["wi"] + p["bi"], z["activation"])
            x = x + ff @ p["wo_mlp"] + p["bo_mlp"]
        x = _layer_norm(x[:, S - last:], f32(params["lnf_scale"]),
                        f32(params["lnf_bias"]), z["eps"])
        assert H * q.shape[-1] == z["d_model"]
        return (x @ f32(params["wte"]).T)[..., :z["vocab"]]
