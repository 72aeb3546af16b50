"""The plain reference of the selected-latent / window-latent family
(``dots3_note``) in float32 ``jax.numpy``.

It follows the equations in their *explicit* form and uses no kernel, no
cache, no scan and no code of the program under test (its helpers are the
latent-attention reference's: the norm, the blocked matmul, the SwiGLU and
the expert layer, which is the same published router).  RMSNorm (eps from
the file) throughout; every layer is ``x += attn(norm(x)); x +=
ffn(norm(x))``.  On ``h = norm(x)``, with the widths of the layer's kind
(``full_attention``: the plain keys; ``sliding_attention``: the ``swa_``
keys):

- ``c_q = r_q norm(W_qa h)``, per head ``[q_n | q_r] = W_qb c_q``; ``[c_kv |
  k_r] = W_kva h``, ``c = r_kv norm(c_kv)``; per head ``[k_n | v] = W_kvb
  c`` (keys and values are UP-PROJECTED here; the program absorbs the
  up-projection into the query).  ``r = sqrt(hidden_size / rank)`` under
  ``apply_mla_qkv_lora_rescale``, else 1.  Score ``(q_n.k_n + R(q_r).R(k_r))
  (d_nope + d_rope)^-1/2``, ``R`` the rotation in interleaved pairs by
  ``theta^(-2j/d_rope)``;
- a full layer's query ``t`` sees the set ``S_t``: with ``q_I = W_qI c_q``
  (``index_n_heads`` x ``index_head_dim``), ``k_I = LayerNorm(W_kI h)``, the
  first ``qk_rope_head_dim`` elements of both rotated with halves paired, ``w
  = W_w h``: ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s]) *
  index_n_heads^-1/2 * index_head_dim^-1/2``, and ``S_t`` the ``min(t + 1,
  index_topk)`` tokens ``s <= t`` of largest ``I[t, s]``, by a FULL stable
  sort (ties to the lower position);
- a window layer's query ``t`` sees ``t - sliding_window_size < s <= t``;
- softmax over the seen keys; every head's ``p v`` times ``g = sigmoid(W_g
  h)``, one scalar a head; ``x += W_o concat_heads``;
- the first ``first_k_dense_replace`` layers: ``x += W_d (silu(W_g h) * W_u
  h)``; the others the expert layer of ``latent_moe_reference``;
- ``norm``, then the untied head over the held rows of the vocabulary.

Weights come in the program's layout because the program draws them: runs
of layers in depth order, a run one stack per position of its unit; walking
the runs, each repetition's positions in order, visits the layers in depth
order, and the file's ``layer_types`` says what each is.  To fit beside a
stopped server, attention runs a group of heads at a time and, inside it, a
block of query rows at a time (``lax.map``), the FFNs a block of rows at a
time, and the helpers upcast a block of columns or one expert at a time.
Every product runs at
``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .latent_moe_reference import (_expert_layer, _f32, _matmul, _norm,
                                   _swiglu)

_Q_BLOCK = 128          # query rows of attention at a time
_HEAD_GROUP = 16        # heads up-projected and attended at a time
_ROW_BLOCK = 1024       # rows of an FFN at a time
_INDEX_NORM_EPS = 1e-6

_WIDTHS = {
    "full_attention": ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
                       "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
                       "rope_theta"),
    "sliding_attention": ("swa_num_attention_heads", "swa_q_lora_rank",
                          "swa_kv_lora_rank", "swa_qk_nope_head_dim",
                          "swa_qk_rope_head_dim", "swa_v_head_dim",
                          "swa_rope_theta")}


def _rotate(x, theta, interleaved=True, start=0):
    """``x`` [S, ..., D] at positions ``start .. start + S - 1``: pairs
    ``(2j, 2j+1)``, or halves ``(j, j + D/2)``."""
    S, D = x.shape[0], x.shape[-1]
    freq = float(theta) ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = (start + jnp.arange(S)).astype(jnp.float32)[:, None] * freq
    ang = ang.reshape((S,) + (1,) * (x.ndim - 2) + (-1,))
    if interleaved:
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.stack([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                          x1 * jnp.sin(ang) + x2 * jnp.cos(ang)],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


def _blocked(t, n_blocks):
    pad = n_blocks * _Q_BLOCK - t.shape[0]
    return jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)) \
        .reshape((n_blocks, _Q_BLOCK) + t.shape[1:])


def _by_rows(fn, x):
    """``fn`` (rows in, rows out, each row on its own) over ``x`` a block of
    rows at a time, one after the other."""
    S = x.shape[0]
    n = -(-S // _ROW_BLOCK)
    padded = jnp.pad(x, ((0, n * _ROW_BLOCK - S), (0, 0)))
    out = jax.lax.map(fn, padded.reshape(n, _ROW_BLOCK, -1))
    return out.reshape(n * _ROW_BLOCK, -1)[:S]


def selection(file, c_q, h, p, theta):
    """``seen`` [S, S] bool: query ``t``'s set ``S_t``, by a full sort."""
    S = h.shape[0]
    dr = file["qk_rope_head_dim"]
    Hi, Di = file["index_n_heads"], file["index_head_dim"]
    rot = lambda t, start=0: jnp.concatenate(
        [_rotate(t[..., :dr], theta, False, start), t[..., dr:]], -1)
    k = h @ _f32(p["wi_k"])
    mu = jnp.mean(k, -1, keepdims=True)
    k = (k - mu) * jax.lax.rsqrt(
        jnp.mean((k - mu) ** 2, -1, keepdims=True) + _INDEX_NORM_EPS)
    k_i = rot(k * _f32(p["wi_k_scale"]) + _f32(p["wi_k_bias"]))
    w = h @ _f32(p["wi_w"])
    n_blocks = -(-S // _Q_BLOCK)

    def block(args):
        cq, wq, start = args
        t = start + jnp.arange(_Q_BLOCK)
        q = rot(jnp.einsum("qr,rhe->qhe", cq, _f32(p["wi_q"])), start)
        score = jnp.einsum(
            "qh,qhk->qk", wq,
            jax.nn.relu(jnp.einsum("qhe,ke->qhk", q, k_i))) \
            * Hi ** -0.5 * Di ** -0.5
        score = jnp.where(jnp.arange(S)[None] <= t[:, None], score, -jnp.inf)
        order = jnp.argsort(-score, axis=-1, stable=True)
        rank = jnp.argsort(order, axis=-1, stable=True)
        return rank < jnp.minimum(t + 1, file["index_topk"])[:, None]

    seen = jax.lax.map(block, (_blocked(c_q, n_blocks), _blocked(w, n_blocks),
                               jnp.arange(n_blocks) * _Q_BLOCK))
    return seen.reshape(n_blocks * _Q_BLOCK, S)[:S]


def _attention(file, x, p, kind, chosen=None):
    """``x`` [S, d] -> ``x + W_o (g * attention)``, explicit.  ``chosen`` (a
    list): a full layer appends its ``seen`` [S, S] to it."""
    H, rq, r, d_n, d_r, d_v, theta = (file[k] for k in _WIDTHS[kind])
    eps, d = file["rms_norm_eps"], file["hidden_size"]
    rescale = bool(file["apply_mla_qkv_lora_rescale"])
    S = x.shape[0]
    h = _norm(x, p["ln1"], eps)
    c_q = _norm(h @ _f32(p["wq_a"]), p["q_norm"], eps) \
        * (math.sqrt(d / rq) if rescale else 1.0)
    kv = h @ _f32(p["wkv_a"])
    c = _norm(kv[:, :r], p["kv_norm"], eps) \
        * (math.sqrt(d / r) if rescale else 1.0)
    k_r = _rotate(kv[:, r:], theta)                           # [S, d_rope]
    n_blocks = -(-S // _Q_BLOCK)
    starts = jnp.arange(n_blocks) * _Q_BLOCK
    if kind == "full_attention":
        seen = selection(file, c_q, h, p, theta)
        if chosen is not None:
            chosen.append(seen)
        seen_b = _blocked(seen, n_blocks)
    else:       # the band: each block of query rows makes its own
        seen_b = jnp.zeros((n_blocks, 0), bool)
    a = 1.0 / math.sqrt(d_n + d_r)
    g = min(_HEAD_GROUP, H)
    assert H % g == 0, (H, g)
    # a group of heads at a time, one after the other (a scan: one group's
    # keys, values and outputs alive), each adding its part of ``W_o o``
    grouped = lambda w: w.reshape(w.shape[0], H // g, g, w.shape[-1]) \
        .transpose(1, 0, 2, 3)                  # [rank, H, e] by group
    gate = jax.nn.sigmoid(h @ _f32(p["w_gate"]))              # [S, H]

    def group(out, args):
        w_kvb, w_qb, w_o, gate_g = args
        up = jnp.einsum("sr,rhe->she", c, _f32(w_kvb))
        k_n, v = up[..., :d_n], up[..., d_n:]
        q = jnp.einsum("sr,rhe->she", c_q, _f32(w_qb))
        q_n, q_r = q[..., :d_n], _rotate(q[..., d_n:], theta)

        def block(args):
            qn, qr, see, start = args
            if kind != "full_attention":
                back = (start + jnp.arange(_Q_BLOCK))[:, None] \
                    - jnp.arange(S)
                see = (back >= 0) & (back < file["sliding_window_size"])
            s = (jnp.einsum("qhe,khe->hqk", qn, k_n)
                 + jnp.einsum("qhe,ke->hqk", qr, k_r)) * a
            s = jnp.where(see[None], s, -jnp.inf)
            # a padded row of the last block sees nothing: keep it finite
            s = jnp.where(jnp.any(see, -1)[None, :, None], s, 0.0)
            return jnp.einsum("hqk,khe->qhe", jax.nn.softmax(s, -1), v)

        o = jax.lax.map(block, (_blocked(q_n, n_blocks),
                                _blocked(q_r, n_blocks), seen_b, starts))
        o = o.reshape((n_blocks * _Q_BLOCK,) + o.shape[2:])[:S]
        return out + jnp.einsum("she,hed->sd", o * gate_g[..., None],
                                _f32(w_o)), None

    out, _ = jax.lax.scan(group, x, (
        grouped(p["wkv_b"]), grouped(p["wq_b"]),
        p["wo"].reshape((H // g, g) + p["wo"].shape[1:]),
        gate.reshape(S, H // g, g).transpose(1, 0, 2)))
    return out


def layers_of(params):
    """The layers' parameters in depth order, from the runs: each
    repetition of a run, its unit's positions in order."""
    at = lambda tree, i: jax.tree_util.tree_map(lambda a: a[i], tree)
    for run in params["runs"]:
        parts = [run] if isinstance(run, dict) else list(run)
        n = jax.tree_util.tree_leaves(parts[0])[0].shape[0]
        for i in range(n):
            for part in parts:
                yield at(part, i)


def forward(file: dict, params, tokens, last: int, chosen=None):
    """Float32 logits ``[B, last, vocab]`` at the last ``last`` positions
    of ``tokens`` ``[B, S]``.  ``chosen`` (a list): every full layer's sets
    ``S_t`` as ``[S, S]`` bool are appended to it, rows in order then layers
    in depth order (:func:`selections`)."""
    eps = file["rms_norm_eps"]
    held = tuple(range(file["n_routed_experts"]))
    rows = []
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            x = _f32(params["wte"][tokens[b]])
            for l, p in enumerate(layers_of(params)):
                x = _attention(file, x, p, file["layer_types"][l], chosen)
                if l < file["first_k_dense_replace"]:
                    x = _by_rows(lambda r, p=p: r + _swiglu(
                        _norm(r, p["ln2"], eps), p["w_gu"], p["w_down"]), x)
                else:
                    x = _by_rows(lambda r, p=p: _expert_layer(
                        file, r, p, held), x)
            x = _norm(x[x.shape[0] - last:], params["lnf"], eps)
            rows.append(_matmul(x, params["head"].T)[:, :file["vocab_size"]])
    return jnp.stack(rows)


def selections(file: dict, params, tokens):
    """The sets the reference's full layers chose for ONE row of tokens
    ``[1, S]``: ``[full layers, S, S]`` bool, row ``t`` of a layer the set
    ``S_t``."""
    chosen = []
    forward(file, params, tokens[:1], 1, chosen)
    return jnp.stack(chosen)
