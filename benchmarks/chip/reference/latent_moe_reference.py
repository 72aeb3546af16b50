"""The plain reference of the latent-attention, routed-expert family
(``kimi_k2`` / DeepSeek-V3 generation) in float32 ``jax.numpy``.

It follows the published equations in their *explicit* form and uses no
kernel, no cache, no scan and no code of the program under test.  RMSNorm
(eps from the file) throughout.  Per layer, on ``h = norm(x)``:

- attention: ``c_q = norm(W_qa h)``; per head ``[q_n | q_r] = W_qb c_q``;
  ``[c_kv | k_r] = W_kva h``; ``c = norm(c_kv)``; per head ``[k_n | v] =
  W_kvb c`` (keys and values are UP-PROJECTED here; the program absorbs the
  up-projection into the query instead); score ``(q_n.k_n + R(q_r).R(k_r)) *
  a`` with ``a = (d_nope + d_rope)^-1/2 * m^2``, ``m = 0.1 mscale_all_dim
  ln(factor) + 1``; causal softmax; ``x += W_o concat_heads(p v)``.  ``R``
  rotates the rotary dims in interleaved pairs ``(2j, 2j+1)`` (the published
  code's pairing) with YaRN frequencies: ``f_j = theta^(-2j/d_rope)``,
  blended ``f_j / factor * (1 - g_j) + f_j * g_j``, ``g`` one minus the
  linear ramp between the correction dims of ``beta_fast`` and
  ``beta_slow`` over the original positions;
- the first ``first_k_dense_replace`` layers: ``x += W_d (silu(W_g h) * W_u
  h)``;
- the others: ``s = sigmoid(W_r h)`` over all ``published.n_routed_experts``
  experts, the ``num_experts_per_tok`` largest ``s + b`` selected, weights
  ``routed_scaling_factor * s_i / sum_sel s``; ``x += sum_{i selected and
  held} w_i E_i(h) + E_shared(h)``, every ``E`` a SwiGLU.  The experts held
  are ids ``0 .. n_routed_experts - 1`` of the deployment's (the file's
  count of them): what the absent ones would add is left out, as in the
  program;
- ``norm``, then the untied head over the held rows of the vocabulary.

Departures from the published code: none in the mathematics.  Weights come
in the program's layout (gate beside up in ``w_gu``; layers stacked in
``dense_blocks`` and ``moe_blocks``) because the program draws them.  To fit
beside a stopped server every matrix is upcast to float32 a block of columns
at a time, each held expert alone (``lax.fori_loop`` over the held experts:
a loop, so that one upcast expert is alive at a time), and attention runs a
block of query rows at a time (``lax.map`` over the blocks).  Every product
runs at ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_Q_BLOCK = 256          # query rows of attention at a time
_COL_BLOCK = 4608       # columns of a matrix upcast at a time


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(scale)


def _matmul(x, w):
    """``x @ w`` with ``w`` [in, out] upcast a block of columns at a time."""
    n = w.shape[-1]
    return jnp.concatenate(
        [x @ _f32(w[..., i:i + _COL_BLOCK]) for i in range(0, n, _COL_BLOCK)],
        axis=-1)


def _swiglu(h, w_gu, w_down):
    f = w_gu.shape[-1] // 2
    act = jax.nn.silu(_matmul(h, w_gu[..., :f])) * _matmul(h, w_gu[..., f:])
    return _matmul(act, w_down)


def yarn_inv_freq(file: dict):
    """The rotary frequencies ``[d_rope / 2]`` in closed form."""
    dim, theta = file["qk_rope_head_dim"], float(file["rope_theta"])
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    scaling = file.get("rope_scaling")
    if not scaling:
        return freq
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    g = 1.0 - ramp
    return freq / factor * (1.0 - g) + freq * g


def _rotate(x, inv_freq):
    """Interleaved pairs of ``x`` [S, ..., d_rope] at positions 0..S-1."""
    S = x.shape[0]
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (-1,))
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                      x1 * jnp.sin(ang) + x2 * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def softmax_scale(file: dict) -> float:
    a = 1.0 / math.sqrt(file["qk_nope_head_dim"] + file["qk_rope_head_dim"])
    scaling = file.get("rope_scaling")
    if scaling and scaling.get("mscale_all_dim"):
        m = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1
        a *= m * m
    return a


def _attention(file, x, p, inv_freq):
    """``x`` [S, d] -> ``x + W_o attention``, explicit (up-projected)."""
    eps = file["rms_norm_eps"]
    r, d_n = file["kv_lora_rank"], file["qk_nope_head_dim"]
    S = x.shape[0]
    h = _norm(x, p["ln1"], eps)
    c_q = _norm(h @ _f32(p["wq_a"]), p["q_norm"], eps)
    q = jnp.einsum("sr,rhe->she", c_q, _f32(p["wq_b"]))
    kv = h @ _f32(p["wkv_a"])
    c = _norm(kv[:, :r], p["kv_norm"], eps)
    k_r = _rotate(kv[:, r:], inv_freq)                        # [S, d_rope]
    up = jnp.einsum("sr,rhe->she", c, _f32(p["wkv_b"]))
    k_n, v = up[..., :d_n], up[..., d_n:]
    q_n, q_r = q[..., :d_n], _rotate(q[..., d_n:], inv_freq)
    a = softmax_scale(file)
    # a block of query rows at a time, one after the other (``lax.map``
    # keeps one block's scores alive; a Python loop leaves that to the
    # compiler's scheduler)
    n_blocks = -(-S // _Q_BLOCK)
    pad = n_blocks * _Q_BLOCK - S
    blocked = lambda t: jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)) \
        .reshape((n_blocks, _Q_BLOCK) + t.shape[1:])

    def block(args):
        qn, qr, start = args
        s = (jnp.einsum("qhe,khe->hqk", qn, k_n)
             + jnp.einsum("qhe,ke->hqk", qr, k_r)) * a
        visible = (jnp.arange(S)[None, :]
                   <= start + jnp.arange(_Q_BLOCK)[:, None])
        s = jnp.where(visible[None], s, -jnp.inf)
        return jnp.einsum("hqk,khe->qhe", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, (blocked(q_n), blocked(q_r),
                            jnp.arange(n_blocks) * _Q_BLOCK))
    o = o.reshape((n_blocks * _Q_BLOCK,) + o.shape[2:])[:S]
    return x + jnp.einsum("she,hed->sd", o, _f32(p["wo"]))


def _expert_layer(file, x, p, held, w_gu=None, w_down=None):
    """``p``: one layer's parameters.  ``w_gu`` / ``w_down``: functions from
    a held expert's local index to its two matrices (default: ``p``'s own
    stacks); ``forward`` hands functions that slice one expert out of the
    weights as they were given, so that no layer's experts are ever copied
    out as a block."""
    if w_gu is None:
        w_gu, w_down = (lambda e: p["w_gu"][e]), (lambda e: p["w_down"][e])
    eps = file["rms_norm_eps"]
    k = file["num_experts_per_tok"]
    h = _norm(x, p["ln2"], eps)
    s = jax.nn.sigmoid(h @ _f32(p["router"]))             # [S, all experts]
    _, chosen = jax.lax.top_k(s + _f32(p["router_bias"]), k)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if file.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * file["routed_scaling_factor"]
    out = _swiglu(h, p["ws_gu"], p["ws_down"])            # the shared expert
    ids = jnp.asarray(held, jnp.int32)

    def add_expert(local, out):
        # one held expert at a time: every token through it, weighted by
        # its routing weight where it chose this expert, else by zero
        w_e = jnp.sum(jnp.where(chosen == ids[local], w, 0.0), -1,
                      keepdims=True)
        return out + w_e * _swiglu(h, w_gu(local), w_down(local))

    return x + jax.lax.fori_loop(0, len(held), add_expert, out)


def forward(file: dict, params, tokens, last: int):
    """Float32 logits ``[B, last, vocab]`` at the last ``last`` positions
    of ``tokens`` ``[B, S]``."""
    eps = file["rms_norm_eps"]
    inv_freq = yarn_inv_freq(file)
    held = tuple(range(file["n_routed_experts"]))
    at = lambda tree, l: jax.tree_util.tree_map(lambda a: a[l], tree)
    n_dense = file["first_k_dense_replace"]
    rows = []
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            x = _f32(params["wte"][tokens[b]])
            for l in range(file["num_hidden_layers"]):
                if l < n_dense:
                    p = at(params["dense_blocks"], l)
                    x = _attention(file, x, p, inv_freq)
                    x = x + _swiglu(_norm(x, p["ln2"], eps), p["w_gu"],
                                    p["w_down"])
                else:
                    moe = params["moe_blocks"]
                    p = at({k: v for k, v in moe.items()
                            if k not in ("w_gu", "w_down")}, l - n_dense)
                    one = lambda k: lambda e, l=l - n_dense: \
                        jax.lax.dynamic_index_in_dim(
                            moe[k].reshape((-1,) + moe[k].shape[2:]),
                            l * len(held) + e, keepdims=False)
                    x = _expert_layer(file, _attention(file, x, p, inv_freq),
                                      p, held, one("w_gu"), one("w_down"))
            x = _norm(x[x.shape[0] - last:], params["lnf"], eps)
            rows.append(_matmul(x, params["lm_head"].T)[
                :, :file["vocab_size"]])
    return jnp.stack(rows)
