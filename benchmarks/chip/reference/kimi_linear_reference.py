"""The plain reference of the linear-attention / latent-attention family
(``kimi_linear``: Kimi-Linear-48B-A3B) in float32 ``jax.numpy``.

It follows the published equations, uses no kernel, no cache, no chunked
form and no code of the program under test.  ``norm`` is RMSNorm (eps from
the file).  ``x_0 = Emb[token]``; layer ``l`` (1-indexed) is ``x +=
mixer(norm(x))``, ``x += ffn(norm(x))``; logits ``= W_head norm_f(x)`` over
the rows of the vocabulary held here (an untied head).

- layers in ``linear_attn_config.kda_layers``, Kimi Delta Attention
  (``num_heads`` heads of ``head_dim`` = ``d_k`` = ``d_v``): ``q~ | k~ | v~ =
  W_qkv h``; each channel through a causal depthwise convolution of
  ``short_conv_kernel_size`` taps (no bias, zeros before the sequence) and
  SiLU; per head ``q = q~ / sqrt(|q~|^2 + 1e-6) / sqrt(d_k)``, ``k = k~ /
  sqrt(|k~|^2 + 1e-6)``, ``v = v~``; ``g = -exp(A_log[head]) softplus(W_fb
  (W_fa h) + dt_bias)`` per key channel; ``beta = sigmoid(W_b h)`` per head.
  The state ``S`` ``[d_k, d_v]`` per head, zero at the start, **token by
  token** (a ``lax.scan`` over time; the program scans prompts in chunks, in
  the WY form): ``S' = exp(g)[:, None] S``; ``u = beta (v - S'^T k)``; ``S =
  S' + k u^T``; ``o = S^T q``.  ``o <- norm_head(o; w) * sigmoid(W_gb (W_ga
  h) + b_g)`` (the norm over one head's channels, one scale shared by the
  heads); ``mixer = W_o o``;
- layers in ``full_attn_layers``, latent attention with no rotation
  (``mla_use_nope``) and no query bottleneck (``q_lora_rank`` null): per
  head ``[q_n | q_r] = W_q h``; ``[c~ | k_r] = W_kva h``, ``c = norm(c~)``;
  per head ``[k_n | v] = W_kvb c`` (keys and values are UP-PROJECTED here;
  the program absorbs the up-projection into the query and attends to the
  cached rows ``[c | k_r]``); score ``(q_n . k_n + q_r . k_r) / sqrt(d_nope +
  d_rope)``, ``k_r`` shared by the heads and used as projected; causal
  softmax; ``W_o concat_heads(p v)``;
- the FFN: the first ``first_k_dense_replace`` layers ``W_d (silu(W_g h) *
  W_u h)``; the others ``s = sigmoid(W_r h)`` over all
  ``published.num_experts``, the ``num_experts_per_token`` largest ``s + b``
  selected, weights ``routed_scaling_factor * s_i / sum_sel s``
  (``moe_renormalize``); ``sum_{i selected and held} w_i E_i(h) +
  E_shared(h)``, every ``E`` a SwiGLU.  The experts held are ids ``0 ..
  num_experts - 1`` of the deployment's (the file's count of them): what
  the absent ones would add is left out, as in the program.

Departures from the published description: none in the mathematics.  What
the config does not state (the low-rank shapes, ``b_g``, ``dt_bias``, the
l2 norm's eps, the selection bias, a convolution without bias) is the
file's ``assumed``.  Weights come in the program's layout because the
program draws them: ``runs``, one entry per repeated unit of layer labels
(``_units`` below reads the two lists as the program does), one stack per
position of the unit; ``W_q | W_k | W_v`` side by side in ``w_qkv`` and their
three convolutions in one ``conv_w``; gate beside up in ``w_gu``.  To fit
beside a stopped server every matrix is upcast to float32 a block of
columns at a time, each held expert alone (a ``lax.fori_loop``, so that one
upcast expert is alive at a time), and attention runs a block of query rows
at a time (``lax.map``), so that 4,500 tokens fit.  Every product runs at
``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_Q_BLOCK = 256          # query rows of attention at a time
_COL_BLOCK = 4608       # columns of a matrix upcast at a time
_MAX_UNIT = 4           # the program's: labels in the largest repeated unit
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


def _kda(file, h, p):
    """Kimi Delta Attention of ``h`` [S, d] -> [S, d], the recurrence."""
    lin = file["linear_attn_config"]
    H, K, taps = lin["num_heads"], lin["head_dim"], \
        lin["short_conv_kernel_size"]
    S = h.shape[0]
    u = _matmul(h, p["w_qkv"])                              # [S, 3 H K]
    w = _f32(p["conv_w"])                                   # [taps, 3 H K]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1])), u])
    u = jax.nn.silu(sum(w[j] * padded[j:j + S] for j in range(taps)))
    q, k, v = (t.reshape(S, H, K) for t in jnp.split(u, 3, axis=-1))
    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True)
                                       + _L2_EPS)
    q, k = unit(q) * K ** -0.5, unit(k)
    dt = jax.nn.softplus(_matmul(_matmul(h, p["w_fa"]), p["w_fb"])
                         + _f32(p["dt_bias"]))
    g = -jnp.exp(_f32(p["A_log"]))[:, None] * dt.reshape(S, H, K)
    beta = jax.nn.sigmoid(_matmul(h, p["w_b"]))             # [S, H]

    def token(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs        # [H,K] [H,K] [H,K] [H,K] [H]
        state = jnp.exp(g_t)[:, :, None] * state            # [H, d_k, d_v]
        u_t = b_t[:, None] * (v_t - jnp.einsum("hkv,hk->hv", state, k_t))
        state = state + k_t[:, :, None] * u_t[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((H, K, K)), (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + file["rms_norm_eps"]) * _f32(p["norm_o"])
    gate = jax.nn.sigmoid(_matmul(_matmul(h, p["w_ga"]), p["w_gb"])
                          + _f32(p["b_g"]))
    return _matmul(o.reshape(S, H * K) * gate, p["w_o"])


def _latent(file, h, p):
    """Latent attention of ``h`` [S, d] -> [S, d], explicit (up-projected),
    nothing rotated."""
    r, d_n = file["kv_lora_rank"], file["qk_nope_head_dim"]
    S = h.shape[0]
    q = jnp.einsum("sd,dhe->she", h, _f32(p["wq"]))
    kv = h @ _f32(p["wkv_a"])
    c = _norm(kv[:, :r], p["kv_norm"], file["rms_norm_eps"])
    k_r = kv[:, r:]                                         # [S, d_rope]
    up = jnp.einsum("sr,rhe->she", c, _f32(p["wkv_b"]))
    k_n, v = up[..., :d_n], up[..., d_n:]
    q_n, q_r = q[..., :d_n], q[..., d_n:]
    a = (d_n + file["qk_rope_head_dim"]) ** -0.5
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
    return jnp.einsum("she,hed->sd", o, _f32(p["wo"]))


def _experts(file, h, p, w_gu, w_down):
    """``routed(h) + shared(h)``.  ``w_gu`` / ``w_down``: functions from a
    held expert's local index to its two matrices, so that no layer's
    experts are ever copied out as a block."""
    s = jax.nn.sigmoid(h @ _f32(p["router"]))             # [S, all experts]
    _, chosen = jax.lax.top_k(s + _f32(p["router_bias"]),
                              file["num_experts_per_token"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if file["moe_renormalize"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * file["routed_scaling_factor"]
    out = _swiglu(h, p["ws_gu"], p["ws_down"])            # the shared expert

    def add_expert(local, out):
        # one held expert at a time: every token through it, weighted by
        # its routing weight where it chose this expert, else by zero
        w_e = jnp.sum(jnp.where(chosen == local, w, 0.0), -1, keepdims=True)
        return out + w_e * _swiglu(h, w_gu(local), w_down(local))

    return jax.lax.fori_loop(0, file["num_experts"], add_expert, out)


def _labels(file):
    """A layer's label: its mixer, and whether its FFN is the dense one."""
    kda = set(file["linear_attn_config"]["kda_layers"])
    return [("K" if l + 1 in kda else "L")
            + ("d" if l < file["first_k_dense_replace"] else "e")
            for l in range(file["num_hidden_layers"])]


def _units(labels):
    """``(unit, repeats)`` in depth order, as the program lays its
    parameters out: from each depth the unit of at most ``_MAX_UNIT`` labels
    that covers the most layers, a unit of several only where it repeats."""
    out, i = [], 0
    while i < len(labels):
        best = (1, 1)
        for u in range(1, _MAX_UNIT + 1):
            n = 1
            while labels[i + n * u:i + (n + 1) * u] == labels[i:i + u]:
                n += 1
            if (n > 1 or u == 1) and n * u > best[0] * best[1]:
                best = (u, n)
        out.append((labels[i:i + best[0]], best[1]))
        i += best[0] * best[1]
    return out


def forward(file: dict, params, tokens, last: int):
    """Float32 logits ``[B, last, vocab]`` at the last ``last`` positions
    of ``tokens`` ``[B, S]``."""
    eps = file["rms_norm_eps"]
    n_held = file["num_experts"]
    rows = []
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            x = _f32(params["wte"][tokens[b]])
            for (unit, n), run in zip(_units(_labels(file)), params["runs"]):
                parts = [run] if isinstance(run, dict) else run
                for l in range(n):
                    for label, stack in zip(unit, parts):
                        routed = label[1] == "e"
                        p = {k: v[l] for k, v in stack.items()
                             if not (routed and k in ("w_gu", "w_down"))}
                        mixer = _kda if label[0] == "K" else _latent
                        x = x + mixer(file, _norm(x, p["ln1"], eps), p)
                        h = _norm(x, p["ln2"], eps)
                        if not routed:
                            x = x + _swiglu(h, p["w_gu"], p["w_down"])
                            continue
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
