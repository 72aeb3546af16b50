"""The plain reference of the window-and-full attention family (``mellum``)
in float32 ``jax.numpy``.

It follows the published equations in their explicit form and uses no
kernel, no cache, no ring, no scan over layers and no code of the program
under test: a full causal pass, a window layer's band a mask.  RMSNorm (eps
from the file) throughout, no bias anywhere.  Layer ``l`` is of kind
``layer_types[l]``; on ``h = norm(x)``:

- ``q = W_q h`` as ``num_attention_heads`` heads of ``head_dim``, ``k = W_k
  h`` and ``v = W_v h`` as ``num_key_value_heads`` heads; query head ``i``
  reads key-value head ``i // (heads / kv heads)``;
- q and k of every head RMS-normalised over the head's elements with a
  learned weight (``assumed.qk_norm`` of the file), then rotated over the
  whole head, halves paired (element ``i`` with ``i + D/2``), frequencies
  ``theta^(-2i/D)``: plain on a ``sliding_attention`` layer; on a
  ``full_attention`` layer YaRN's (``f / factor`` blended with ``f`` by the
  linear ramp between the correction dims of ``beta_fast`` and ``beta_slow``
  over the original positions), cosines and sines times
  ``attention_factor``;
- scores ``q . k / sqrt(D)``, causal; a ``sliding_attention`` layer sees
  ``0 <= i - j < sliding_window``; softmax; ``x += W_o concat_heads(p v)``;
- ``h2 = norm(x)``; router logits ``W_r h2`` over all
  ``published.num_experts`` experts, the ``num_experts_per_tok`` largest
  chosen, weights a softmax over the chosen logits (the softmax over all,
  renormalised over the chosen: ``norm_topk_prob``); ``x += sum_{i chosen
  and held} w_i W_d,i (silu(W_g,i h2) * W_u,i h2)``.  The experts held are
  ids ``0 .. num_experts - 1`` of the deployment's (the file's count of
  them): what the absent ones would add is left out, as in the program;
- ``norm``, then the untied head over the held rows of the vocabulary.

Departures from the published code: none in the mathematics.  Weights come
in the program's layout because the program draws them: the four attention
matrices head-major (``[heads, D, d_model]``), gate beside up in ``w_gu``; ``runs`` in depth order, each one dict of stacks or, where a unit
of several layers repeats, a list of them (repetition ``r``'s layers are
index ``r`` of each, in the list's order), which is read off the arrays'
shapes here and not from the program's config.  To fit beside a stopped
server every matrix is upcast to float32 a block of columns at a time, each
held expert alone (``lax.fori_loop``: one upcast expert alive at a time),
and attention runs a block of query rows at a time (``lax.map``).  Every
product runs at ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .latent_moe_reference import _f32, _matmul, _norm, _swiglu

_Q_BLOCK = 256          # query rows of attention at a time


def inv_freq(file: dict, kind: str):
    """``[head_dim / 2]`` rotary frequencies of a layer of ``kind``, and the
    factor its cosines and sines take."""
    rope = file["rope_parameters"][kind]
    dim, theta = file["head_dim"], float(rope["rope_theta"])
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if rope["rope_type"] == "default":
        return freq, 1.0

    def correction_dim(rotations):
        return dim * math.log(rope["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return freq / rope["factor"] * ramp + freq * (1.0 - ramp), \
        float(rope["attention_factor"])


def _rotate(x, freq, factor):
    """Halves paired, ``x`` [S, heads, D] at positions 0..S-1."""
    S, _, D = x.shape
    ang = (jnp.arange(S, dtype=jnp.float32)[:, None] * freq)[:, None, :]
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(file, x, p, kind):
    """``x`` [S, d] -> ``x + W_o attention`` of a layer of ``kind``."""
    eps, D = file["rms_norm_eps"], file["head_dim"]
    H, Hkv = file["num_attention_heads"], file["num_key_value_heads"]
    S = x.shape[0]
    h = _norm(x, p["ln1"], eps)
    freq, factor = inv_freq(file, kind)
    project = lambda w: jnp.einsum("sd,hed->she", h, _f32(w))
    q = _norm(project(p["wq"]), p["q_norm"], eps)
    k = _norm(project(p["wk"]), p["k_norm"], eps)
    v = project(p["wv"])
    q = _rotate(q, freq, factor).reshape(S, Hkv, H // Hkv, D)
    k = _rotate(k, freq, factor)
    window = file["sliding_window"] if kind == "sliding_attention" else S
    n_blocks = -(-S // _Q_BLOCK)
    pad = n_blocks * _Q_BLOCK - S
    blocks = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        (n_blocks, _Q_BLOCK) + q.shape[1:])

    def block(args):
        qb, start = args
        s = jnp.einsum("qkgd,skd->kgqs", qb, k) / math.sqrt(D)
        dist = (start + jnp.arange(_Q_BLOCK))[:, None] - jnp.arange(S)[None]
        seen = (dist >= 0) & (dist < window)
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("kgqs,skd->qkgd", pr, v)

    o = jax.lax.map(block, (blocks, jnp.arange(n_blocks) * _Q_BLOCK))
    o = o.reshape(n_blocks * _Q_BLOCK, H, D)[:S]
    return x + jnp.einsum("she,hed->sd", o, _f32(p["wo"]))


def _expert_layer(file, x, p, w_gu, w_down):
    """``w_gu`` / ``w_down``: functions from a held expert's local index to
    its two matrices, sliced out of the weights as they were given."""
    h = _norm(x, p["ln2"], file["rms_norm_eps"])
    top, chosen = jax.lax.top_k(h @ _f32(p["router"]),
                                file["num_experts_per_tok"])
    w = jax.nn.softmax(top, -1)

    def add_expert(e, out):
        # one held expert at a time: every token through it, weighted by
        # its routing weight where it chose this expert, else by zero
        w_e = jnp.sum(jnp.where(chosen == e, w, 0.0), -1, keepdims=True)
        return out + w_e * _swiglu(h, w_gu(e), w_down(e))

    return x + jax.lax.fori_loop(0, file["num_experts"], add_expert,
                                 jnp.zeros_like(x))


def _layers(runs):
    """``(part, repetition)`` of every layer in depth order, a part one dict
    of stacks ``[repetitions, ...]``."""
    for run in runs:
        parts = [run] if isinstance(run, dict) else list(run)
        for r in range(parts[0]["ln1"].shape[0]):
            for part in parts:
                yield part, r


def forward(file: dict, params, tokens, last: int):
    """Float32 logits ``[B, last, vocab]`` at the last ``last`` positions
    of ``tokens`` ``[B, S]``."""
    eps, held = file["rms_norm_eps"], file["num_experts"]
    rows = []
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            x = _f32(params["wte"][tokens[b]])
            layers = list(_layers(params["runs"]))
            assert len(layers) == file["num_hidden_layers"]
            for kind, (part, r) in zip(file["layer_types"], layers):
                p = {k: v[r] for k, v in part.items()
                     if k not in ("w_gu", "w_down")}
                one = lambda k: lambda e, part=part, r=r: \
                    jax.lax.dynamic_index_in_dim(
                        part[k].reshape((-1,) + part[k].shape[2:]),
                        r * held + e, keepdims=False)
                x = _expert_layer(file, _attention(file, x, p, kind), p,
                                  one("w_gu"), one("w_down"))
            x = _norm(x[x.shape[0] - last:], params["lnf"], eps)
            rows.append(_matmul(x, params["head"].T)[:, :file["vocab_size"]])
    return jnp.stack(rows)
