"""The plain reference of the linear-attention / grouped-attention family
(``solar_open2``: Solar-Open2-250B) in float32 ``jax.numpy``.

It follows the layer equations, uses no kernel, no cache, no chunked form
and no code of the program under test.  ``norm`` is RMSNorm (eps from the
file).  ``x_0 = Emb[token]``; layer ``l`` (0-indexed) is ``x +=
mixer(norm(x))``, ``x += ffn(norm(x))``; logits ``= W_head norm_f(x)`` over
the rows of the vocabulary held here (an untied head).

- layers in ``gqa_layers`` (the first of each period of four), grouped
  softmax attention with no rotation and no position (``use_rope`` false)
  under an output gate (``use_gqa_gate``): per head ``[q | z] = W_q h``
  (``num_attention_heads`` heads of ``head_dim``, the projection twice as
  wide), ``k = W_k h``, ``v = W_v h`` (``num_key_value_heads`` heads); query
  head ``j`` reads key-value head ``j // (heads / kv heads)``; ``a =
  softmax(q k^T / sqrt(head_dim), causal) v``; ``a <- a * sigmoid(z)``
  element by element; ``mixer = W_o concat_heads(a)``;
- every other layer, Kimi Delta Attention (``linear_attn_config``:
  ``num_heads`` heads of ``head_dim`` = ``d_k`` = ``d_v``) with negative
  eigenvalues (``kda_allow_neg_eigval``): ``q~ | k~ | v~ = W_qkv h``; each
  channel through a causal depthwise convolution of
  ``short_conv_kernel_size`` taps (no bias, zeros before the sequence) and
  SiLU; per head ``q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(d_k)``, ``k = k~ /
  sqrt(|k~|^2 + 1e-6)``, ``v = v~``; ``g = -exp(A_log[head]) softplus(W_fb
  (W_fa h) + dt_bias)`` per key channel; ``beta = 2 sigmoid(W_b h)`` per
  head.  The state ``S`` ``[d_k, d_v]`` per head, zero at the start, **token
  by token** (a ``lax.scan`` over time; the program scans prompts in chunks,
  in the WY form): ``S' = exp(g)[:, None] S``; ``u = beta (v - S'^T k)``;
  ``S = S' + k u^T``; ``o = S^T q``.  ``o <- norm_head(o; w) * sigmoid(W_gb
  (W_ga h) + b_g)`` (the norm over one head's channels, one scale shared by
  the heads); ``mixer = W_o o``;
- the FFN of every layer: ``s = sigmoid(W_r h)`` over all
  ``published.n_routed_experts``, the ``num_experts_per_tok`` largest ``s +
  b`` selected, weights ``routed_scaling_factor * s_i / sum_sel s``
  (``norm_topk_prob``); ``sum_{i selected and held} w_i E_i(h) +
  E_shared(h)``, every ``E`` a SwiGLU.  The experts held are ids ``0 ..
  n_routed_experts - 1`` of the deployment's (the file's count of them):
  what the absent ones would add is left out, as in the program.

Departures from the published description: none in the mathematics.  What
the config does not state (the low-rank shapes, ``b_g``, ``dt_bias``, the l2
norm's eps, the selection bias, the gate's form) is the file's ``assumed``.
Weights come in the program's layout because the program draws them:
``runs``, one entry per repeated unit of layer kinds (``_units`` below reads
the list as the program does), one stack per position of the unit; ``W_q |
W_k | W_v`` of a KDA layer side by side in ``w_qkv`` and their three
convolutions in one ``conv_w``; a grouped layer's ``wq`` holds ``[q | z]`` a
head, a row an output channel (``[heads * 2 head_dim, hidden]``); gate beside
up in ``w_gu``.  To fit beside a stopped server that holds 14 GB, a KDA layer
runs a block of ``_HEAD_BLOCK`` heads at a time and
a grouped layer a key-value head's group at a time (``lax.fori_loop``, so
that one block's activations are alive at a time), every matrix is upcast
to float32 a block of columns at a time, each held expert alone, and
attention runs a block of query rows at a time (``lax.map``), so that 9,100
tokens fit.  Every product runs at ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_Q_BLOCK = 256          # query rows of attention at a time
_COL_BLOCK = 4096       # columns of a matrix upcast at a time
_HEAD_BLOCK = 8         # KDA heads at a time
_MAX_UNIT = 4           # the program's: kinds in the largest repeated unit
_L2_EPS = 1e-6


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


def _cols(w, start, n):
    """Columns ``start .. start + n`` of ``w`` [in, out], float32."""
    return _f32(jax.lax.dynamic_slice_in_dim(w, start, n, axis=1))


def _kda(file, h, p):
    """Kimi Delta Attention of ``h`` [S, d] -> [S, d], the recurrence, a
    block of heads at a time (heads meet only in ``W_o``'s sum)."""
    lin = file["linear_attn_config"]
    H, K, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    S, F = h.shape[0], H * K
    hb = min(_HEAD_BLOCK, H)
    n = hb * K
    low_f = h @ _f32(p["w_fa"])                             # [S, r]
    low_g = h @ _f32(p["w_ga"])
    beta_all = 2.0 * jax.nn.sigmoid(h @ _f32(p["w_b"]))     # [S, H]
    a_all = -jnp.exp(_f32(p["A_log"]))                      # [H]

    def conv(part, c0):
        """SiLU of the causal convolution of ``W_qkv``'s columns ``c0 ..``
        of ``q~``, ``k~`` or ``v~`` (``part`` 0, 1, 2)."""
        u = h @ _cols(p["w_qkv"], part * F + c0, n)         # [S, n]
        w = _f32(jax.lax.dynamic_slice_in_dim(p["conv_w"], part * F + c0, n,
                                              axis=1))      # [taps, n]
        padded = jnp.concatenate([jnp.zeros((taps - 1, n)), u])
        return jax.nn.silu(sum(w[j] * padded[j:j + S] for j in range(taps)))

    def block(b, out):
        c0 = b * n
        q, k, v = (conv(part, c0).reshape(S, hb, K) for part in range(3))
        unit = lambda t: t * jax.lax.rsqrt(
            jnp.sum(t * t, -1, keepdims=True) + _L2_EPS)
        q, k = unit(q) * K ** -0.5, unit(k)
        bias = jax.lax.dynamic_slice_in_dim(_f32(p["dt_bias"]), c0, n)
        dt = jax.nn.softplus(low_f @ _cols(p["w_fb"], c0, n) + bias)
        a = jax.lax.dynamic_slice_in_dim(a_all, b * hb, hb)
        g = a[:, None] * dt.reshape(S, hb, K)
        beta = jax.lax.dynamic_slice_in_dim(beta_all, b * hb, hb, axis=1)

        def token(state, xs):
            q_t, k_t, v_t, g_t, b_t = xs    # [hb,K] x4, [hb]
            state = jnp.exp(g_t)[:, :, None] * state        # [hb, d_k, d_v]
            u_t = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
            state = state + k_t[:, :, None] * u_t[:, None, :]
            return state, jnp.einsum("hkv,hk->hv", state, q_t)

        _, o = jax.lax.scan(token, jnp.zeros((hb, K, K)), (q, k, v, g, beta))
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                              + file["rms_norm_eps"]) * _f32(p["norm_o"])
        gate = jax.nn.sigmoid(
            low_g @ _cols(p["w_gb"], c0, n)
            + jax.lax.dynamic_slice_in_dim(_f32(p["b_g"]), c0, n))
        rows = _f32(jax.lax.dynamic_slice_in_dim(p["w_o"], c0, n, axis=0))
        return out + (o.reshape(S, n) * gate) @ rows

    return jax.lax.fori_loop(0, H // hb, block, jnp.zeros_like(h))


def _gqa(file, h, p):
    """Gated grouped attention of ``h`` [S, d] -> [S, d], nothing rotated, a
    key-value head's group of query heads at a time."""
    H, Hkv, D = file["num_attention_heads"], file["num_key_value_heads"], \
        file["head_dim"]
    G = H // Hkv
    S = h.shape[0]
    n_blocks = -(-S // _Q_BLOCK)
    pad = n_blocks * _Q_BLOCK - S
    blocked = lambda t: jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)) \
        .reshape((n_blocks, _Q_BLOCK) + t.shape[1:])

    def group(j, out):
        # the group's G heads of [q | z], 2 D rows of ``wq`` a head
        qz = (h @ _f32(jax.lax.dynamic_slice_in_dim(
            p["wq"], j * G * 2 * D, G * 2 * D, axis=0)).T).reshape(S, G, 2, D)
        q, z = qz[:, :, 0], qz[:, :, 1]
        k = h @ _cols(p["wk"], j * D, D)                    # [S, D]
        v = h @ _cols(p["wv"], j * D, D)

        def rows(args):
            qb, start = args                                # [Q, G, D]
            s = jnp.einsum("qgd,kd->gqk", qb, k) * D ** -0.5
            visible = (jnp.arange(S)[None, :]
                       <= start + jnp.arange(_Q_BLOCK)[:, None])
            s = jnp.where(visible[None], s, -jnp.inf)
            return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(s, -1), v)

        a = jax.lax.map(rows, (blocked(q), jnp.arange(n_blocks) * _Q_BLOCK))
        a = a.reshape((n_blocks * _Q_BLOCK, G, D))[:S] * jax.nn.sigmoid(z)
        w_o = _f32(jax.lax.dynamic_slice_in_dim(p["wo"], j * G * D, G * D,
                                                axis=0))
        return out + a.reshape(S, G * D) @ w_o

    return jax.lax.fori_loop(0, Hkv, group, jnp.zeros_like(h))


def _experts(file, h, p, w_gu, w_down):
    """``routed(h) + shared(h)``.  ``w_gu`` / ``w_down``: functions from a
    held expert's local index to its two matrices, so that no layer's
    experts are ever copied out as a block."""
    s = jax.nn.sigmoid(h @ _f32(p["router"]))             # [S, all experts]
    _, chosen = jax.lax.top_k(s + _f32(p["router_bias"]),
                              file["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if file["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * file["routed_scaling_factor"]
    out = _swiglu(h, p["ws_gu"], p["ws_down"])            # the shared expert

    def add_expert(local, out):
        # one held expert at a time: every token through it, weighted by
        # its routing weight where it chose this expert, else by zero
        w_e = jnp.sum(jnp.where(chosen == local, w, 0.0), -1, keepdims=True)
        return out + w_e * _swiglu(h, w_gu(local), w_down(local))

    return jax.lax.fori_loop(0, file["n_routed_experts"], add_expert, out)


def _kinds(file):
    """A layer's mixer: ``G`` for the layers of ``gqa_layers``, 0-indexed,
    ``K`` for every other."""
    gqa = set(file["gqa_layers"])
    return ["G" if l in gqa else "K"
            for l in range(file["num_hidden_layers"])]


def _units(kinds):
    """``(unit, repeats)`` in depth order, as the program lays its
    parameters out: from each depth the unit of at most ``_MAX_UNIT`` kinds
    that covers the most layers, a unit of several only where it repeats."""
    out, i = [], 0
    while i < len(kinds):
        best = (1, 1)
        for u in range(1, _MAX_UNIT + 1):
            n = 1
            while kinds[i + n * u:i + (n + 1) * u] == kinds[i:i + u]:
                n += 1
            if (n > 1 or u == 1) and n * u > best[0] * best[1]:
                best = (u, n)
        out.append((kinds[i:i + best[0]], best[1]))
        i += best[0] * best[1]
    return out


def forward(file: dict, params, tokens, last: int):
    """Float32 logits ``[B, last, vocab]`` at the last ``last`` positions
    of ``tokens`` ``[B, S]``."""
    eps = file["rms_norm_eps"]
    n_held = file["n_routed_experts"]
    rows = []
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            x = _f32(params["wte"][tokens[b]])
            for (unit, n), run in zip(_units(_kinds(file)), params["runs"]):
                parts = [run] if isinstance(run, dict) else run
                for l in range(n):
                    for kind, stack in zip(unit, parts):
                        p = {k: v[l] for k, v in stack.items()
                             if k not in ("w_gu", "w_down")}
                        mixer = _kda if kind == "K" else _gqa
                        x = x + mixer(file, _norm(x, p["ln1"], eps), p)
                        h = _norm(x, p["ln2"], eps)
                        one = lambda k, l=l, stack=stack: lambda e: \
                            jax.lax.dynamic_index_in_dim(
                                stack[k].reshape((-1,) + stack[k].shape[2:]),
                                l * n_held + e, keepdims=False)
                        x = x + _experts(file, h, p, one("w_gu"),
                                         one("w_down"))
            x = _norm(x[x.shape[0] - last:], params["lnf"], eps)
            rows.append(_matmul(x, params["lm_head"].T)[
                :, :file["vocab_size"]])
    return jnp.stack(rows)
