"""The plain reference of the shortcut-connected double-layer family
(LongCat-Flash) in float32 ``jax.numpy``.

It follows the published equations in their *explicit* form and uses no
kernel, no cache, no scan and no code of the program under test.  RMSNorm
(eps from the file) throughout, no bias anywhere.  Per layer ``l`` of
``num_layers`` (each a DOUBLE layer), on the float32 stream ``x``:

    for i in (0, 1):
        x   <- x + MLA_i(norm(x))
        h_i  = norm(x)
        if i == 0:  s = MoE(h_0)              # kept aside: the shortcut
        x   <- x + W_d_i (silu(W_g_i h_i) * W_u_i h_i)
    x <- x + s                                 # after the SECOND dense FFN

- ``MLA(h)``: ``c_q = norm(W_qa h) * sqrt(hidden / q_lora_rank)``
  (``mla_scale_q_lora``); per head ``[q_n | q_r] = W_qb c_q``; ``[c_kv |
  k_r] = W_kva h``; ``c = norm(c_kv) * sqrt(hidden / kv_lora_rank)``
  (``mla_scale_kv_lora``: keys AND values); per head ``[k_n | v] = W_kvb c``
  (keys and values are UP-PROJECTED here; the program absorbs the
  up-projection into the query instead); score ``(q_n.k_n + R(q_r).R(k_r))
  / sqrt(qk_nope + qk_rope)``; causal softmax; ``W_o concat_heads(p v)``.
  ``R`` rotates the rotary dims in interleaved pairs ``(2j, 2j+1)`` by
  ``theta^(-2j/d_rope)``, no scaling of frequencies;
- ``MoE(h)``: ``p = softmax(W_r h)`` over all ``published.n_routed_experts
  + zero_expert_num`` outputs, the ``moe_topk`` largest ``p + b`` chosen
  (``b``: the selection bias, which never enters a weight), ``w_j =
  routed_scaling_factor * p_j`` with no renormalisation; ``sum_{j chosen, j
  held} w_j E_j(h) + (sum_{j chosen, j >= published.n_routed_experts} w_j)
  * h``: a zero-compute expert is the identity, ``E`` a SwiGLU.  The experts
  held are ids ``0 .. n_routed_experts - 1`` of the deployment's (the
  file's count of them): what the absent ones would add is left out, as in
  the program, and the identity part is added in full (it is the token's
  chip's to add);
- ``norm``, then the untied head over the held rows of the vocabulary.

Departures from the published code: none in the mathematics.  ``q`` is
scaled through ``c_q`` (the published code multiplies ``W_qb c_q``; the same
number).  The multi-token-prediction layer is not part of the served model.
Weights come in the program's layout (gate beside up in ``w_gu``; a layer's
two attentions and two dense FFNs as stacks ``attn0`` / ``attn1`` /
``dense0`` / ``dense1``, each ``[layers, ...]``) because the program draws
them.  To fit beside a stopped server every matrix is upcast
to float32 a block of columns at a time, each held expert alone
(``lax.fori_loop``: one upcast expert alive at a time), and attention runs a
block of query rows at a time (``lax.map``).  Every product runs at
``default_matmul_precision("highest")``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .latent_moe_reference import _f32, _matmul, _norm, _rotate, _swiglu

_Q_BLOCK = 256          # query rows of attention at a time
_HEADS = 8              # heads of attention at a time
_FFN_BLOCK = 1536       # columns of a dense feed-forward at a time


def _blocked(t, rows):
    """``t`` [S, ...] -> ``[blocks, rows, ...]``, zero rows at the end."""
    n = -(-t.shape[0] // rows)
    pad = n * rows - t.shape[0]
    return jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1)) \
        .reshape((n, rows) + t.shape[1:])


def _dense_ffn(h, w_gu, w_down, l):
    """``W_d (silu(W_g h) * W_u h)`` for ``h`` [S, d] with layer ``l`` of the
    stacks ``w_gu`` [L, d, 2f] and ``w_down`` [L, f, d], ``_FFN_BLOCK``
    columns of the feed-forward's width at a time: each block is sliced out
    of the stack where it lies and its slice depends on the loop's index,
    so one block's upcast matrices are alive at a time (an upcast that does
    not depend on it is hoisted out of a loop whole, and a layer indexed
    out first is a copy of the layer)."""
    d, f = w_down.shape[2], w_down.shape[1]
    B = min(_FFN_BLOCK, f)
    assert f % B == 0
    cols = lambda at: _f32(jax.lax.dynamic_slice(
        w_gu, (l, 0, at), (1, d, B))[0])

    def block(j, out):
        act = jax.nn.silu(h @ cols(j * B)) * (h @ cols(f + j * B))
        return out + act @ _f32(jax.lax.dynamic_slice(
            w_down, (l, j * B, 0), (1, B, d))[0])

    return jax.lax.fori_loop(0, f // B, block, jnp.zeros_like(h))


def _attention(file, h, attn, l, inv_freq):
    """``h`` [S, d], the normed stream -> ``W_o attention`` [S, d] with
    layer ``l`` of the stacks ``attn``, explicit (up-projected), ``_HEADS``
    heads at a time, each group's matrices sliced out of the stacks where
    they lie."""
    eps, d = file["rms_norm_eps"], file["hidden_size"]
    r, rq = file["kv_lora_rank"], file["q_lora_rank"]
    d_n, H = file["qk_nope_head_dim"], file["num_attention_heads"]
    S = h.shape[0]
    G = min(_HEADS, H)
    assert H % G == 0
    c_q = _norm(h @ _f32(attn["wq_a"][l]), attn["q_norm"][l], eps) \
        * (math.sqrt(d / rq) if file["mla_scale_q_lora"] else 1.0)
    kv = h @ _f32(attn["wkv_a"][l])
    c = _norm(kv[:, :r], attn["kv_norm"][l], eps) \
        * (math.sqrt(d / r) if file["mla_scale_kv_lora"] else 1.0)
    k_r = _rotate(kv[:, r:], inv_freq)                        # [S, d_rope]
    a = 1.0 / math.sqrt(d_n + file["qk_rope_head_dim"])
    starts = jnp.arange(-(-S // _Q_BLOCK)) * _Q_BLOCK

    def heads(g, out):
        """Heads ``g G .. g G + G - 1``, added to ``out`` [S, d]."""
        def of(name, axis):
            w = attn[name]
            at = [l] + [0] * (w.ndim - 1)
            size = [1] + list(w.shape[1:])
            at[axis], size[axis] = g * G, G
            return _f32(jax.lax.dynamic_slice(w, at, size)[0])
        q = jnp.einsum("sr,rhe->she", c_q, of("wq_b", 2))
        up = jnp.einsum("sr,rhe->she", c, of("wkv_b", 2))
        k_n, v = up[..., :d_n], up[..., d_n:]
        q_n, q_r = q[..., :d_n], _rotate(q[..., d_n:], inv_freq)

        def block(args):
            qn, qr, start = args
            s = (jnp.einsum("qhe,khe->hqk", qn, k_n)
                 + jnp.einsum("qhe,ke->hqk", qr, k_r)) * a
            visible = (jnp.arange(S)[None, :]
                       <= start + jnp.arange(_Q_BLOCK)[:, None])
            s = jnp.where(visible[None], s, -jnp.inf)
            return jnp.einsum("hqk,khe->qhe", jax.nn.softmax(s, -1), v)

        o = jax.lax.map(block, (_blocked(q_n, _Q_BLOCK),
                                _blocked(q_r, _Q_BLOCK), starts))
        o = o.reshape((-1,) + o.shape[2:])[:S]
        return out + jnp.einsum("she,hed->sd", o, of("wo", 1))

    return jax.lax.fori_loop(0, H // G, heads, jnp.zeros_like(h))


def _moe(file, h, p, held, w_gu, w_down):
    """``(MoE(h) [S, d], chosen [S, moe_topk])``.  ``p``: one layer's router
    and selection bias; ``w_gu`` / ``w_down``: functions from a held
    expert's local index to its two matrices."""
    n_real = file.get("published", {}).get("n_routed_experts",
                                           file["n_routed_experts"])
    prob = jax.nn.softmax(h @ _f32(p["router"]), axis=-1)    # all outputs
    assert prob.shape[-1] == n_real + file["zero_expert_num"]
    _, chosen = jax.lax.top_k(prob + _f32(p["router_bias"]), file["moe_topk"])
    w = jnp.take_along_axis(prob, chosen, axis=-1) \
        * file["routed_scaling_factor"]
    # zero_expert_type identity: the pairs on ids past the real experts
    out = jnp.sum(jnp.where(chosen >= n_real, w, 0.0), -1, keepdims=True) * h
    ids = jnp.asarray(held, jnp.int32)

    def add_expert(local, out):
        # one held expert at a time: every token through it, weighted by
        # its routing weight where it chose this expert, else by zero
        w_e = jnp.sum(jnp.where(chosen == ids[local], w, 0.0), -1,
                      keepdims=True)
        return out + w_e * _swiglu(h, w_gu(local), w_down(local))

    return jax.lax.fori_loop(0, len(held), add_expert, out), chosen


def _stream(file: dict, params, row):
    """One row of tokens ``[S]`` through the layers: ``(x [S, d] before the
    last norm, [layers, S, moe_topk] the ids each layer's gate chose)``."""
    eps = file["rms_norm_eps"]
    dim = file["qk_rope_head_dim"]
    inv_freq = float(file["rope_theta"]) ** (
        -jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    held = tuple(range(file["n_routed_experts"]))
    blocks = params["blocks"]
    moe = blocks["moe"]
    x = _f32(params["wte"][row])
    chose = []
    for l in range(file["num_layers"]):
        one = lambda k, l=l: lambda e: jax.lax.dynamic_index_in_dim(
            moe[k].reshape((-1,) + moe[k].shape[2:]),
            l * len(held) + e, keepdims=False)
        for i in (0, 1):
            attn = blocks[f"attn{i}"]
            x = x + _attention(file, _norm(x, attn["ln1"][l], eps),
                               attn, l, inv_freq)
            h = _norm(x, attn["ln2"][l], eps)
            if i == 0:
                gate = {k: moe[k][l] for k in ("router", "router_bias")}
                s, chosen = _moe(file, h, gate, held, one("w_gu"),
                                 one("w_down"))
                chose.append(chosen)
            dense = blocks[f"dense{i}"]
            x = x + _dense_ffn(h, dense["w_gu"], dense["w_down"], l)
        x = x + s
    return x, jnp.stack(chose)


def forward(file: dict, params, tokens, last: int):
    """Float32 logits ``[B, last, vocab]`` at the last ``last`` positions
    of ``tokens`` ``[B, S]``."""
    rows = []
    with jax.default_matmul_precision("highest"):
        for b in range(tokens.shape[0]):
            x, _ = _stream(file, params, tokens[b])
            x = _norm(x[x.shape[0] - last:], params["lnf"],
                      file["rms_norm_eps"])
            rows.append(_matmul(x, params["lm_head"].T)[
                :, :file["vocab_size"]])
    return jnp.stack(rows)


def choices(file: dict, params, tokens):
    """``[B, layers, S, moe_topk]`` int32: the router outputs each layer's
    gate chooses for every token of ``tokens`` ``[B, S]`` (what
    ``longcat_flash_control.py --in-common`` holds the program's gate
    to)."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_stream(file, params, tokens[b])[1]
                          for b in range(tokens.shape[0])])
