"""The plain reference of the hybrid family's single-part block
(``nemotron_h``: NVIDIA Nemotron-H / Nemotron 3 Nano) in float32
``jax.numpy``.

It follows the published equations, uses no kernel, no cache, no chunked
scan and no code of the program under test.  ``norm`` is RMSNorm (eps from
the file).  ``x_0 = Emb[token]``; block ``i`` is ONE part,
``hybrid_override_pattern[i]``, behind one norm: ``x += part(norm_i(x))``;
logits ``= W_head norm_f(x)`` over the rows of the vocabulary held here (an
untied head; no embedding, residual or logit multiplier).

- ``M``, the Mamba-2 mixer: ``[z | u | dt] = W_in h`` (``d_inner`` |
  ``d_inner + 2 n_groups d_state`` | heads, ``d_inner = mamba_num_heads x
  mamba_head_dim``).  ``u_t <- silu(b + sum_j w_j u_{t-K+1+j})`` per channel,
  zeros before the sequence.  ``[v | B | C] = u`` (``v`` as heads of
  ``mamba_head_dim``; ``B``, ``C`` ``[n_groups, d_state]``; head ``h`` reads
  group ``h // (heads / n_groups)``).  ``dt = softplus(dt + dt_bias)``, ``a
  = -exp(A_log)`` per head.  The state ``H`` per head, ``[mamba_head_dim,
  d_state]``, zero at the start, **token by token** (a ``lax.scan`` over
  time; the program scans in chunks): ``H_t = exp(dt_t a) H_{t-1} + dt_t
  v_t B_t^T``, ``y_t = H_t C_t + D v_t``.  ``y <- norm_g(y * silu(z))``, the
  mean square taken over each group's ``d_inner / n_groups`` channels apart
  (gate before norm); ``mixer = W_out y``;
- ``*``, attention: q, k, v, o without bias; ``num_attention_heads`` query
  heads of ``head_dim`` on ``num_key_value_heads``; query head ``i`` reads
  key-value head ``i // (heads / kv heads)``; scores over ``sqrt(head_dim)``;
  causal softmax; **no rotation and no position term** (the file's
  ``assumed.rotation``);
- ``E``, the experts: ``s = sigmoid(W_r h)`` over all
  ``published.n_routed_experts``; the ``num_experts_per_tok`` largest of ``s
  + e_score_correction_bias``; ``w = s_sel / sum(s_sel) x
  routed_scaling_factor`` (the bias moves the selection, never a weight);
  ``routed = sum_{i chosen and held} w_i E_i(h)``, ``E_i(h) = W_down,i
  relu(W_up,i h)^2`` (two matrices, no gate); ``shared`` the same form at
  ``moe_shared_expert_intermediate_size``, unweighted.  The experts held are
  ids ``0 .. n_routed_experts - 1`` of the deployment's (the file's count of
  them): what the absent ones would add is left out, as in the program.

Departures from the published code: none in the mathematics.  Weights come
in the program's layout because the program draws them: ``runs``, one entry
per repeated unit of kinds (``_units`` below reads the pattern as the
program does), one stack per position of the unit; ``W_in``'s columns for
``z | u`` in ``w_in`` and for ``dt`` in ``w_dt``; a routed expert's matrices
stored wider than ``moe_intermediate_size`` with zero padding, of which only
the published columns are read here.  To fit beside a stopped server every
matrix is upcast to float32 a block of columns at a time, each held expert
alone (a ``lax.fori_loop``, so that one upcast expert is alive at a time),
and attention runs a block of query rows at a time (``lax.map``), so that a
16k-token prompt fits.  Every product runs at
``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_Q_BLOCK = 128          # query rows of attention at a time
_COL_BLOCK = 4096       # columns of a matrix upcast at a time
_MAX_UNIT = 4           # the program's: kinds in the largest repeated unit


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


def _relu2(h, w_up, w_down):
    return _matmul(jnp.square(jax.nn.relu(_matmul(h, w_up))), w_down)


def _mamba(file, h, p):
    """The state-space mixer of ``h`` [S, d] -> [S, d], the recurrence."""
    H, P = file["mamba_num_heads"], file["mamba_head_dim"]
    N, K, G = file["ssm_state_size"], file["conv_kernel"], file["n_groups"]
    di = H * P
    S = h.shape[0]
    zu, dt = _matmul(h, p["w_in"]), _matmul(h, p["w_dt"])
    z, u = zu[:, :di], zu[:, di:]
    w = _f32(p["conv_w"])                                   # [K, channels]
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1])), u])
    u = jax.nn.silu(_f32(p["conv_b"]) + sum(
        w[j] * padded[j:j + S] for j in range(K)))
    v = u[:, :di].reshape(S, H, P)
    # a head's own B and C: its group's
    Bm = jnp.repeat(u[:, di:di + G * N].reshape(S, G, N), H // G, axis=1)
    Cm = jnp.repeat(u[:, di + G * N:].reshape(S, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))           # [S, H]
    a = -jnp.exp(_f32(p["A_log"]))

    def token(state, xs):
        v_t, b_t, c_t, dt_t = xs                # [H,P] [H,N] [H,N] [H]
        state = jnp.exp(dt_t * a)[:, None, None] * state \
            + (dt_t[:, None] * v_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N)), (v, Bm, Cm, dt))
    y = (y + _f32(p["D"])[None, :, None] * v).reshape(S, di)
    y = (y * jax.nn.silu(z)).reshape(S, G, di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + file["norm_eps"])
    return _matmul(y.reshape(S, di) * _f32(p["norm_g"]), p["w_out"])


def _attention(file, h, p):
    """Grouped-head causal attention of ``h`` [S, d] -> [S, d]."""
    Hq, Hkv = file["num_attention_heads"], file["num_key_value_heads"]
    D, S = file["head_dim"], h.shape[0]
    q = _matmul(h, p["wq"]).reshape(S, Hkv, Hq // Hkv, D)
    k = _matmul(h, p["wk"]).reshape(S, Hkv, D)
    v = _matmul(h, p["wv"]).reshape(S, Hkv, D)
    n_blocks = -(-S // _Q_BLOCK)
    pad = n_blocks * _Q_BLOCK - S
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0))).reshape(
        (n_blocks, _Q_BLOCK) + q.shape[1:])

    def block(args):
        qs, start = args
        s = jnp.einsum("qkge,ske->kgqs", qs, k) / (D ** 0.5)
        visible = (jnp.arange(S)[None, :]
                   <= start + jnp.arange(_Q_BLOCK)[:, None])
        s = jnp.where(visible[None, None], s, -jnp.inf)
        return jnp.einsum("kgqs,ske->qkge", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, (qb, jnp.arange(n_blocks) * _Q_BLOCK))
    o = o.reshape(n_blocks * _Q_BLOCK, Hq * D)[:S]
    return _matmul(o, p["wo"])


def _experts(file, h, p, w_up, w_down):
    """``routed(h) + shared(h)``.  ``w_up`` / ``w_down``: functions from a
    held expert's local index to its two matrices, so that no layer's
    experts are ever copied out as a block."""
    n_held, f = file["n_routed_experts"], file["moe_intermediate_size"]
    scores = jax.nn.sigmoid(h @ _f32(p["router"]))       # [S, all experts]
    _, chosen = jax.lax.top_k(scores + _f32(p["router_bias"]),
                              file["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    w = w / jnp.sum(w, -1, keepdims=True) * file["routed_scaling_factor"]
    out = _relu2(h, p["ws_up"], p["ws_down"])

    def add_expert(local, out):
        # one held expert at a time: every token through it, weighted by
        # its routing weight where it chose this expert, else by zero
        w_e = jnp.sum(jnp.where(chosen == local, w, 0.0), -1, keepdims=True)
        return out + w_e * _relu2(h, w_up(local)[:, :f], w_down(local)[:f])

    return jax.lax.fori_loop(0, n_held, add_expert, out)


def _units(pattern: str):
    """``(unit, repeats)`` in depth order, as the program lays its
    parameters out: from each depth the unit of at most ``_MAX_UNIT``
    characters that covers the most layers, a unit of several characters
    only where it repeats."""
    out, i = [], 0
    while i < len(pattern):
        best = (1, 1)
        for u in range(1, _MAX_UNIT + 1):
            n = 1
            while pattern[i + n * u:i + (n + 1) * u] == pattern[i:i + u]:
                n += 1
            if (n > 1 or u == 1) and n * u > best[0] * best[1]:
                best = (u, n)
        out.append((pattern[i:i + best[0]], best[1]))
        i += best[0] * best[1]
    return out


def forward(file: dict, params, tokens, last: int):
    """Float32 logits ``[B, last, vocab]`` at the last ``last`` positions
    of ``tokens`` ``[B, S]``."""
    eps = file["norm_eps"]
    n_held = file["n_routed_experts"]
    rows = []
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            x = _f32(params["wte"][tokens[b]])
            for (unit, n), run in zip(
                    _units(file["hybrid_override_pattern"]), params["runs"]):
                parts = [run] if isinstance(run, dict) else run
                for l in range(n):
                    for kind, stack in zip(unit, parts):
                        p = {k: v[l] for k, v in stack.items()
                             if k not in ("w_up", "w_down")}
                        if kind == "E":
                            one = lambda k, l=l, stack=stack: lambda e: \
                                jax.lax.dynamic_index_in_dim(
                                    stack[k].reshape(
                                        (-1,) + stack[k].shape[2:]),
                                    l * n_held + e, keepdims=False)
                            x = x + _experts(file, _norm(x, p["ln2"], eps),
                                             p, one("w_up"), one("w_down"))
                        else:
                            part = _mamba if kind == "M" else _attention
                            x = x + part(file, _norm(x, p["ln1"], eps), p)
            x = _norm(x[x.shape[0] - last:], params["lnf"], eps)
            rows.append(_matmul(x, params["head"].T)[:, :file["vocab_size"]])
    return jnp.stack(rows)
