"""The plain reference of the hybrid state-space / attention family
(``granitemoehybrid``: Granite 4.0-H) in float32 ``jax.numpy``.

It follows the published equations, uses no kernel, no cache, no chunked
scan and no code of the program under test.  ``norm`` is RMSNorm (eps from
the file).  ``x_0 = embedding_multiplier * Emb[token]``.  Per layer ``x +=
residual_multiplier * mixer(norm_1(x))``, then ``x += residual_multiplier *
(routed(h) + shared(h))`` with ``h = norm_2(x)``; logits ``= Emb . norm_f(x)
/ logits_scaling`` over the rows of the vocabulary held here.

- state-space mixer (``layer_types[i] == "mamba"``): ``[z | u | dt] = W_in
  h`` (``d_inner`` | ``d_inner + 2 d_state`` | heads).  ``u_t <- silu(b +
  sum_j w_j u_{t-K+1+j})`` per channel, zeros before the sequence.  ``[v | B
  | C] = u`` (``v`` as heads of ``mamba_d_head``; ``B``, ``C`` shared by
  all heads: one group).  ``dt = softplus(dt + dt_bias)``, ``a =
  -exp(A_log)`` per head.  The state ``H`` per head, ``[mamba_d_head,
  d_state]``, zero at the start, **token by token** (a ``lax.scan`` over
  time; the program scans in chunks): ``H_t = exp(dt_t a) H_{t-1} + dt_t
  v_t B_t^T``, ``y_t = H_t C_t + D v_t``.  ``y <- norm_g(y * silu(z))``
  over all ``d_inner`` channels; ``mixer = W_out y``;
- attention (``"attention"``): q, k, v, o without bias; query head ``i``
  reads key-value head ``i // (heads / kv heads)``; no rotation and no
  position term; scores times ``attention_multiplier``; causal softmax;
- routed experts: ``l = W_r h`` over all ``published.num_local_experts``;
  the ``num_experts_per_tok`` largest ``l``; ``w = softmax`` of those;
  ``routed = sum_{i chosen and held} w_i E_i(h)``, ``E_i(h) = W_out,i
  (silu(p) * q)``, ``[p | q] = W_in,i h``; ``shared`` the same form at
  ``shared_intermediate_size`` with no gate weight.  The experts held are
  ids ``0 .. num_local_experts - 1`` of the deployment's (the file's count
  of them): what the absent ones would add is left out, as in the program.

Departures from the published code: none in the mathematics.  Weights come
in the program's layout (``runs``: one stack per run of consecutive layers
of one kind; gate beside up in ``w_gu`` / ``ws_gu``; ``W_in``'s columns for
``z | u`` in ``w_in`` and for ``dt`` in ``w_dt``) because the program draws
them.  To fit beside a stopped server every matrix is upcast to
float32 a block of columns at a time, each held expert alone (a
``lax.fori_loop``, so that one upcast expert is alive at a time), and
attention runs a block of query rows at a time (``lax.map``).  Every product
runs at ``default_matmul_precision("highest")``.
"""

from __future__ import annotations

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


def _gated(h, w_gu, w_down):
    f = w_gu.shape[-1] // 2
    act = jax.nn.silu(_matmul(h, w_gu[..., :f])) * _matmul(h, w_gu[..., f:])
    return _matmul(act, w_down)


def _mamba(file, h, p):
    """The state-space mixer of ``h`` [S, d] -> [S, d], the recurrence."""
    H, P = file["mamba_n_heads"], file["mamba_d_head"]
    N, K = file["mamba_d_state"], file["mamba_d_conv"]
    di = H * P
    S = h.shape[0]
    zu, dt = _matmul(h, p["w_in"]), _matmul(h, p["w_dt"])
    z, u = zu[:, :di], zu[:, di:]
    w = _f32(p["conv_w"])                                   # [K, channels]
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1])), u])
    u = jax.nn.silu(_f32(p["conv_b"]) + sum(
        w[j] * padded[j:j + S] for j in range(K)))
    v, Bm, Cm = u[:, :di].reshape(S, H, P), u[:, di:di + N], u[:, di + N:]
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))           # [S, H]
    a = -jnp.exp(_f32(p["A_log"]))

    def token(state, xs):
        v_t, b_t, c_t, dt_t = xs
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * v_t)[:, :, None] * b_t[None, None, :]
        return state, state @ c_t                           # [H, P]

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N)), (v, Bm, Cm, dt))
    y = (y + _f32(p["D"])[None, :, None] * v).reshape(S, di)
    y = _norm(y * jax.nn.silu(z), p["norm_g"], file["rms_norm_eps"])
    return _matmul(y, p["w_out"])


def _attention(file, h, p):
    """Grouped-head causal attention of ``h`` [S, d] -> [S, d]."""
    Hq, Hkv = file["num_attention_heads"], file["num_key_value_heads"]
    S = h.shape[0]
    D = p["wq"].shape[-1] // Hq
    q = _matmul(h, p["wq"]).reshape(S, Hq, D)
    k = jnp.repeat(_matmul(h, p["wk"]).reshape(S, Hkv, D), Hq // Hkv, axis=1)
    v = jnp.repeat(_matmul(h, p["wv"]).reshape(S, Hkv, D), Hq // Hkv, axis=1)
    n_blocks = -(-S // _Q_BLOCK)
    pad = n_blocks * _Q_BLOCK - S
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n_blocks, _Q_BLOCK, Hq, D)

    def block(args):
        qs, start = args
        s = jnp.einsum("qhe,khe->hqk", qs, k) * file["attention_multiplier"]
        visible = (jnp.arange(S)[None, :]
                   <= start + jnp.arange(_Q_BLOCK)[:, None])
        s = jnp.where(visible[None], s, -jnp.inf)
        return jnp.einsum("hqk,khe->qhe", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, (qb, jnp.arange(n_blocks) * _Q_BLOCK))
    o = o.reshape(n_blocks * _Q_BLOCK, Hq * D)[:S]
    return _matmul(o, p["wo"])


def _experts(file, h, p, w_gu, w_down):
    """``routed(h) + shared(h)``.  ``w_gu`` / ``w_down``: functions from a
    held expert's local index to its two matrices, so that no layer's
    experts are ever copied out as a block."""
    n_held = file["num_local_experts"]
    logits = h @ _f32(p["router"])                       # [S, all experts]
    top, chosen = jax.lax.top_k(logits, file["num_experts_per_tok"])
    w = jax.nn.softmax(top, axis=-1)
    out = _gated(h, p["ws_gu"], p["ws_down"])

    def add_expert(local, out):
        # one held expert at a time: every token through it, weighted by
        # its routing weight where it chose this expert, else by zero
        w_e = jnp.sum(jnp.where(chosen == local, w, 0.0), -1, keepdims=True)
        return out + w_e * _gated(h, w_gu(local), w_down(local))

    return jax.lax.fori_loop(0, n_held, add_expert, out)


def _runs(layer_types):
    """``(kind, layers)`` for each run of consecutive layers of a kind."""
    runs = []
    for kind in layer_types:
        if runs and runs[-1][0] == kind:
            runs[-1][1] += 1
        else:
            runs.append([kind, 1])
    return runs


def forward(file: dict, params, tokens, last: int):
    """Float32 logits ``[B, last, vocab]`` at the last ``last`` positions
    of ``tokens`` ``[B, S]``."""
    eps, r = file["rms_norm_eps"], file["residual_multiplier"]
    n_held = file["num_local_experts"]
    at = lambda tree, l: jax.tree_util.tree_map(lambda a: a[l], tree)
    rows = []
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            x = file["embedding_multiplier"] * _f32(params["wte"][tokens[b]])
            for (kind, n), run in zip(_runs(file["layer_types"]),
                                      params["runs"]):
                small = {k: v for k, v in run.items()
                         if k not in ("w_gu", "w_down")}
                for l in range(n):
                    p = at(small, l)
                    mixer = _mamba if kind == "mamba" else _attention
                    x = x + r * mixer(file, _norm(x, p["ln1"], eps), p)
                    one = lambda k, l=l, run=run: lambda e: \
                        jax.lax.dynamic_index_in_dim(
                            run[k].reshape((-1,) + run[k].shape[2:]),
                            l * n_held + e, keepdims=False)
                    x = x + r * _experts(file, _norm(x, p["ln2"], eps), p,
                                         one("w_gu"), one("w_down"))
            x = _norm(x[x.shape[0] - last:], params["lnf"], eps)
            rows.append(_matmul(x, params["wte"].T)[:, :file["vocab_size"]]
                        / file["logits_scaling"])
    return jnp.stack(rows)
