"""The plain reference of the gated-short-convolution family (``lfm2_moe``)
in float32 ``jax.numpy``.

It follows the published equations in their explicit form and uses no
kernel, no cache, no carried tail, no scan over layers and no code of the
program under test: a full causal pass.  RMSNorm (``norm_eps``) throughout,
no bias anywhere.  Layer ``l`` is of kind ``layer_types[l]``; on ``h =
norm(x)`` (``operator_norm``):

- ``conv``: ``[B | C | u] = W_in h`` split in that order; ``s = B * u``;
  ``y_t = w_0 s_{t-2} + w_1 s_{t-1} + w_2 s_t`` with ``s_{<0} = 0``, as
  ``conv_L_cache`` shifted multiply-adds over the whole sequence, no
  activation, no bias; ``x += W_out (C * y)``;
- ``full_attention``: ``q = W_q h`` as ``num_attention_heads`` heads of 64,
  ``k = W_k h`` and ``v = W_v h`` as ``num_key_value_heads`` heads; q and k
  of every head RMS-normalised over the head's elements with a learned
  weight, THEN rotated over the whole head, halves paired (element ``i``
  with ``i + 32``), frequencies ``theta^(-2i/64)``; k and v repeated out to
  the query heads (head ``i`` reads key-value head ``i // 4``); scores ``q .
  k / sqrt(64)``, causal; softmax; ``x += W_o concat_heads(p v)``;
- ``h2 = norm(x)`` (``ffn_norm``); in the first ``num_dense_layers`` layers
  ``x += W_2 (silu(W_1 h2) * W_3 h2)``; in the others ``p = sigmoid(W_r
  h2)`` over all ``num_experts``, the ``num_experts_per_tok`` largest of ``p
  + expert_bias`` chosen, ``w = p_chosen / (sum p_chosen + 1e-6) *
  routed_scaling_factor``, ``x += sum_chosen w_i W_d,i (silu(W_g,i h2) *
  W_u,i h2)``: every expert is held;
- ``norm`` (the model's last), then the head, which is the embedding.

Departures from the published code: none in the mathematics (the program's
gate divides by ``sum + 1e-20`` where this file has the published ``1e-6``:
a millionth of a weight, written in the configuration file).  Weights come
in the program's layout because the program draws them: the four attention
matrices head-major (``[heads, 64, d_model]``), gate beside up in ``w_gu``;
``runs`` in depth order, each one dict of stacks or, where a unit of several
layers repeats, a list of them (repetition ``r``'s layers are index ``r`` of
each, in the list's order), which is read off the arrays' shapes here and
not from the program's config.  To fit beside a stopped server every matrix
is upcast to float32 a block of columns at a time, each expert alone
(``lax.fori_loop``: one upcast expert alive at a time), and attention runs
a block of query rows at a time (``lax.map``).  Every product runs at
``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .latent_moe_reference import _f32, _matmul, _norm, _swiglu
from .mellum_reference import _Q_BLOCK, _layers, _rotate

HEAD_DIM = 64


def _conv(file, x, p):
    """``x`` [S, d] -> ``x + W_out (C * conv(B * u))``."""
    S = x.shape[0]
    h = _norm(x, p["ln1"], file["norm_eps"])
    gate_b, gate_c, u = jnp.split(_matmul(h, p["w_in"]), 3, axis=-1)
    s = gate_b * u
    taps = _f32(p["conv_w"])                    # [L, d]: taps[L-1] is now
    L = taps.shape[0]
    back = jnp.pad(s, ((L - 1, 0), (0, 0)))     # s_{<0} = 0
    y = sum(taps[j] * back[j:j + S] for j in range(L))
    return x + _matmul(gate_c * y, p["w_out"])


def _attention(file, x, p):
    """``x`` [S, d] -> ``x + W_o attention``."""
    eps, D = file["norm_eps"], HEAD_DIM
    H, Hkv = file["num_attention_heads"], file["num_key_value_heads"]
    S = x.shape[0]
    h = _norm(x, p["ln1"], eps)
    freq = float(file["rope_theta"]) ** (
        -jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    project = lambda w: jnp.einsum("sd,hed->she", h, _f32(w))
    q = _rotate(_norm(project(p["wq"]), p["q_norm"], eps), freq, 1.0)
    k = _rotate(_norm(project(p["wk"]), p["k_norm"], eps), freq, 1.0)
    # keys and values repeated out to the query heads
    k = jnp.repeat(k, H // Hkv, axis=1)
    v = jnp.repeat(project(p["wv"]), H // Hkv, axis=1)
    n_blocks = -(-S // _Q_BLOCK)
    blocks = jnp.pad(q, ((0, n_blocks * _Q_BLOCK - S), (0, 0), (0, 0))
                     ).reshape(n_blocks, _Q_BLOCK, H, D)

    def block(args):
        qb, start = args
        s = jnp.einsum("qhd,shd->hqs", qb, k) / math.sqrt(D)
        seen = (start + jnp.arange(_Q_BLOCK))[:, None] >= jnp.arange(S)[None]
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("hqs,shd->qhd", pr, v)

    o = jax.lax.map(block, (blocks, jnp.arange(n_blocks) * _Q_BLOCK))
    o = o.reshape(n_blocks * _Q_BLOCK, H, D)[:S]
    return x + jnp.einsum("she,hed->sd", o, _f32(p["wo"]))


def _expert_layer(file, x, p, w_gu, w_down):
    """``(x + routed, chosen [S, k], w [S, k])``.  ``w_gu`` / ``w_down``:
    functions from an expert's index to its two matrices, sliced out of the
    weights as they were given."""
    h = _norm(x, p["ln2"], file["norm_eps"])
    scores = jax.nn.sigmoid(h @ _f32(p["router"]))
    _, chosen = jax.lax.top_k(scores + _f32(p["router_bias"]),
                              file["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6) \
        * file["routed_scaling_factor"]

    def add_expert(e, out):
        # one expert at a time: every token through it, weighted by its
        # routing weight where it chose this expert, else by zero
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), -1, keepdims=True)
        return out + w_e * _swiglu(h, w_gu(e), w_down(e))

    return x + jax.lax.fori_loop(0, file["num_experts"], add_expert,
                                 jnp.zeros_like(x)), chosen, w


def _stream(file, params, tokens):
    """One sequence ``tokens`` [S] through every layer: the stream before
    the last norm, and every expert layer's ``(chosen, w)`` in depth
    order."""
    eps, E = file["norm_eps"], file["num_experts"]
    x = _f32(params["wte"][tokens])
    layers = list(_layers(params["runs"]))
    assert len(layers) == file["num_hidden_layers"]
    gates = []
    for l, (kind, (part, r)) in enumerate(zip(file["layer_types"], layers)):
        dense = l < file["num_dense_layers"]
        p = {k: v[r] for k, v in part.items()
             if dense or k not in ("w_gu", "w_down")}
        x = _conv(file, x, p) if kind == "conv" else _attention(file, x, p)
        if dense:
            x = x + _swiglu(_norm(x, p["ln2"], eps), p["w_gu"], p["w_down"])
            continue
        one = lambda k: lambda e, part=part, r=r: \
            jax.lax.dynamic_index_in_dim(
                part[k].reshape((-1,) + part[k].shape[2:]),
                r * E + e, keepdims=False)
        x, chosen, w = _expert_layer(file, x, p, one("w_gu"), one("w_down"))
        gates.append((chosen, w))
    return x, gates


def forward(file: dict, params, tokens, last: int):
    """Float32 logits ``[B, last, vocab]`` at the last ``last`` positions
    of ``tokens`` ``[B, S]``."""
    rows = []
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            x, _ = _stream(file, params, tokens[b])
            x = _norm(x[x.shape[0] - last:], params["lnf"], file["norm_eps"])
            rows.append(_matmul(x, params["wte"].T)[:, :file["vocab_size"]])
    return jnp.stack(rows)


def gates(file: dict, params, tokens):
    """``(chosen, w)``, ``[B, expert layers, S, num_experts_per_tok]``
    int32 and float32: the experts each layer's gate chooses for every token
    of ``tokens`` ``[B, S]`` and the weights it gives them (what
    ``lfm2_control.py --in-common`` holds the program's gate to)."""
    with jax.default_matmul_precision("highest"):
        rows = [_stream(file, params, tokens[b])[1]
                for b in range(tokens.shape[0])]
    return tuple(jnp.stack([jnp.stack([g[i] for g in row]) for row in rows])
                 for i in (0, 1))
