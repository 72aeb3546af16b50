"""Speculative decoding: draft-model proposals verified by the target in
chunks (beyond the reference, which serves one token per target forward).

Two modes, both with an exactness guarantee.  Greedy (``temperature=0``):
each round the draft decodes ``draft_k`` tokens autoregressively, the
target verifies the whole chunk in ONE ``extend`` call (chunked prefill
over the live cache), and the longest agreeing prefix plus the target's
own next token are emitted — bit-identical to the target decoding alone.
Sampling (``temperature>0``): the :func:`spec_accept` rejection rule
(Leviathan et al. 2023 / Chen et al. 2023) accepts each draft token with
probability ``min(1, p_t/p_d)`` and resamples from the residual on
rejection — the emitted tokens are distributed EXACTLY as sampling from
the target at that temperature.  Either way the draft only changes how
many target forwards the output takes.  Decode is memory-bound on TPU
(the whole weight set streams per token), so verifying k+1 positions per
target pass is a direct latency lever whenever the draft agrees often.

Cache rollback is O(1): rejected draft positions are simply left beyond
``cache.length`` — visibility masking ignores them and sequential writes
overwrite them, so "undo" is a scalar length reset.

The whole loop (draft scan → verify extend → accept/rollback) runs inside
one ``lax.while_loop`` — a single XLA program per (prompt_len, n_tokens)
signature.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..models import cache_family, gpt, gpt_inference

PyTree = Any

# Per-slot RNG discipline for BATCHED speculation (the serving tick).
# Each slot's round key splits off its per-tick key chain (the PR 6
# fold_in contract); the draft steps and the accept/resample draw then
# fold DISJOINT domain constants into that round key, so the uniforms
# the rejection rule compares against are independent of the draws that
# produced the proposals — reusing one stream would correlate u with
# the draft sample and break the exactness theorem.
SPEC_DRAFT_DOMAIN = 0x5D000000   # + step index j, draft proposal stream
SPEC_ACCEPT_DOMAIN = 0x5A000000  # accept/resample stream


def spec_draft_keys(round_keys: jax.Array, j) -> jax.Array:
    """Per-slot draft-step keys: fold step ``j`` into the ``[B, 2]`` round
    keys under the draft domain (``j`` may be traced — scan index)."""
    return jax.vmap(jax.random.fold_in,
                    in_axes=(0, None))(round_keys, SPEC_DRAFT_DOMAIN + j)


def spec_accept_keys(round_keys: jax.Array) -> jax.Array:
    """Per-slot accept/resample keys for the same round — a fold-in
    sequence disjoint from every :func:`spec_draft_keys` stream."""
    return jax.vmap(jax.random.fold_in,
                    in_axes=(0, None))(round_keys, SPEC_ACCEPT_DOMAIN)


def spec_accept_batch(keys: jax.Array, d_tokens: jnp.ndarray,
                      d_probs: jnp.ndarray, t_probs: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Batched :func:`spec_accept`: one independent rejection rule per
    slot.  ``keys [B, 2]`` (from :func:`spec_accept_keys`), ``d_tokens
    [B, K]``, ``d_probs [B, K, V]``, ``t_probs [B, K+1, V]`` →
    ``(a [B], next_token [B])``.  Each row's emitted marginal equals
    sampling from ITS target distribution — the distributional unit test
    checks rows with different distributions simultaneously."""
    return jax.vmap(spec_accept)(keys, d_tokens, d_probs, t_probs)


def spec_accept(key: jax.Array, d_tokens: jnp.ndarray, d_probs: jnp.ndarray,
                t_probs: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The speculative-sampling acceptance rule (Leviathan et al. 2023 /
    Chen et al. 2023): given K draft tokens with their draft distributions
    ``d_probs [K, V]`` and the target distributions ``t_probs [K+1, V]``
    over the same positions (+1 = the bonus position), accept draft token
    i with probability ``min(1, p_t(x_i)/p_d(x_i))``; at the first
    rejection, resample from the residual ``norm(max(p_t - p_d, 0))``;
    if everything is accepted, sample the bonus from ``t_probs[K]``.

    Returns ``(a, next_token)`` — the accepted count (0..K) and the one
    extra emitted token.  The emitted marginal equals sampling from the
    target alone (the theorem this function's unit test checks
    empirically).
    """
    K = d_tokens.shape[0]
    u_key, r_key = jax.random.split(key)
    u = jax.random.uniform(u_key, (K,))
    p_t = jnp.take_along_axis(t_probs[:K], d_tokens[:, None], 1)[:, 0]
    p_d = jnp.take_along_axis(d_probs, d_tokens[:, None], 1)[:, 0]
    accept = u < jnp.minimum(1.0, p_t / jnp.maximum(p_d, 1e-20))
    a = jnp.sum(jnp.cumprod(accept.astype(jnp.int32)))
    # residual at the first rejection (row a); bonus row when a == K
    resid = jnp.maximum(t_probs[a] - jnp.where(a < K, d_probs[a % K], 0.0),
                        0.0)
    resid_sum = jnp.sum(resid)
    # an all-accepted round has resid == t_probs[K] (no draft to subtract);
    # a fully-overlapping residual (sum 0) falls back to the target row
    probs = jnp.where(resid_sum > 1e-20, resid / jnp.maximum(resid_sum, 1e-20),
                      t_probs[a])
    nxt = jax.random.categorical(r_key, jnp.log(jnp.maximum(probs, 1e-30)))
    return a, nxt.astype(jnp.int32)


def speculative_generate(target_params: PyTree, target_cfg: gpt.GPTConfig,
                         draft_params: PyTree, draft_cfg: gpt.GPTConfig,
                         prompt: jnp.ndarray, max_new_tokens: int,
                         draft_k: int = 7,
                         kv_dtype=None, temperature: float = 0.0,
                         top_k: int = 0, top_p: float = 1.0,
                         key=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Speculative decode.  prompt [B, S] → (tokens [B, N],
    n_target_forwards [] = verify rounds + the prefill).

    ``temperature == 0`` (default): greedy draft-and-verify — output
    bit-identical to the target decoding alone, for ANY batch size: rows
    accept different draft counts per round, so their frontiers diverge
    and every subsequent draft step / verify chunk runs RAGGED (per-row
    cache append + per-row visibility); a round advances each unfinished
    row by its own 1 + accepted count.  ``temperature > 0``: speculative
    SAMPLING (:func:`spec_accept` rejection rule) — the emitted tokens
    are distributed exactly as sampling from the target at that
    temperature (with ``top_k``/``top_p`` applied to draft AND target
    through the shared :func:`sampling.filter_logits`, so the theorem
    holds against the filtered target), with the draft only changing the
    number of target passes; sampling serves batch 1.

    ``n_target_forwards`` counts the verify passes (plus the prefill) the
    run needed — the quantity speculation reduces; plain decode needs N.

    The verify chunk is ``draft_k + 1`` tokens; keep it a multiple of the
    8-row sublane tile (the default, 7+1=8) so the verify ``extend``
    rides the chunked-prefill Pallas kernel instead of the dense
    fallback.
    """
    B = prompt.shape[0]
    if float(temperature) > 0.0 and B != 1:
        raise NotImplementedError(
            "speculative SAMPLING serves batch 1 (per-row rejection "
            "resampling); batched speculation is greedy")
    if not (target_cfg.vocab_size == draft_cfg.vocab_size):
        raise ValueError("draft and target must share a vocabulary "
                         f"({draft_cfg.vocab_size} vs {target_cfg.vocab_size})")
    from .engine import _tile_cache_len
    # family dispatch: the TARGET may be MoE (verify rides its extend);
    # the draft stays dense (a draft's whole point is being small)
    tfam, dfam = cache_family(target_cfg), gpt_inference.DENSE
    t_cache_kw = {"kv_dtype": kv_dtype}
    N, K = int(max_new_tokens), int(draft_k)
    V = target_cfg.vocab_size
    S = prompt.shape[1]
    # room for prompt + emitted + one full speculative overshoot; unlike
    # plain generate, the LAST verify round can write up to K tokens past
    # the final emission, so the whole overshoot must fit the context —
    # a clamped cache would silently corrupt accepted K/V near the edge
    need = S + N + K + 1
    ctx = min(target_cfg.max_seq_len, draft_cfg.max_seq_len)
    if need > ctx:
        raise ValueError(
            f"prompt ({S}) + max_new_tokens ({N}) + speculative overshoot "
            f"({K + 1}) exceeds max_seq_len ({ctx}); reduce draft_k or the "
            "token budget")
    tcache = tfam.init_cache(target_cfg, B, _tile_cache_len(need, ctx),
                             **t_cache_kw)
    dcache = dfam.init_cache(draft_cfg, B, _tile_cache_len(need, ctx))

    sample = float(temperature) > 0.0
    temp = jnp.float32(max(float(temperature), 1e-6))
    key0 = key if key is not None else jax.random.PRNGKey(0)

    from .sampling import filter_logits

    def flt(lg):
        return filter_logits(lg, temp, top_k=top_k, top_p=top_p)

    tlogits, tcache = tfam.prefill(target_params, prompt,
                                   target_cfg, tcache)
    _, dcache = dfam.prefill(draft_params, prompt, draft_cfg, dcache)
    last_t = tlogits[:, -1, :V].astype(jnp.float32)
    if sample:
        key0, sub = jax.random.split(key0)
        cur = jax.random.categorical(sub, flt(last_t)).astype(jnp.int32)
    else:
        cur = jnp.argmax(last_t, -1).astype(jnp.int32)   # pending [B]

    out0 = jnp.zeros((B, N + K + 1), jnp.int32)
    lens0 = jnp.full((B,), S, jnp.int32)   # per-row emitted-prefix frontier
    done0 = jnp.zeros((B,), jnp.int32)
    rows = jnp.arange(B)

    def cond(st):
        done, *_ = st
        return jnp.any(done < N)

    def body(st):
        done, cur, out, tcache, dcache, lens, fwds, rng = st
        rng, dkey, akey = jax.random.split(rng, 3)
        # FINISHED rows keep running (SPMD: every row computes every
        # round) but their frontier is clamped to the highest any ACTIVE
        # row can hold (identity for active rows, since done <= N-1 ⇒
        # lens <= S+N-1): their draft/verify writes then land in-bounds
        # at slots their dead prefix no longer needs, instead of relying
        # on out-of-bounds scatter-drop past the `need`-sized cache
        l_eff = jnp.minimum(lens, S + N - 1)

        # ---- draft: K tokens per row from [cur, d1..d_{K-1}] (greedy, or
        # sampled at the SAME temperature so acceptance rates stay high);
        # every step appends at each row's OWN frontier (ragged decode)
        def dstep(carry, dk):
            tok, dc, l = carry
            lg, dc = dfam.decode_step(draft_params, tok, draft_cfg, dc,
                                      lengths=l)
            lg = lg[:, :V].astype(jnp.float32)
            if sample:
                f = flt(lg)
                probs = jax.nn.softmax(f, -1)[0]
                nxt = jax.random.categorical(dk, f, axis=-1
                                             ).astype(jnp.int32)
            else:
                probs = jnp.zeros((V,), jnp.float32)
                nxt = jnp.argmax(lg, -1).astype(jnp.int32)
            return (nxt, dc, l + 1), (nxt, probs)

        (last_d, dcache, _), (drafts, d_probs) = lax.scan(
            dstep, (cur, dcache, l_eff), jax.random.split(dkey, K))
        # drafts: [K, B].  Feed d_K too so the draft cache covers a full
        # acceptance
        _, dcache = dfam.decode_step(draft_params, last_d, draft_cfg, dcache,
                                     lengths=l_eff + K)

        # ---- verify: ONE target pass over [cur, d1..dK] per row, each
        # row's chunk at ITS frontier (ragged extend)
        window = jnp.concatenate([cur[:, None], drafts.T], axis=1)  # [B,K+1]
        vlogits, tcache = tfam.extend(target_params, window,
                                      target_cfg, tcache, lengths=l_eff)
        vlg = vlogits[..., :V].astype(jnp.float32)            # [B, K+1, V]

        if sample:
            # rejection rule (B == 1): emitted tokens are distributed
            # exactly as target sampling (of the filtered distribution);
            # the window is [cur, accepted drafts] with nxt the pending
            # resample/bonus token
            t_probs = jax.nn.softmax(flt(vlg[0]), -1)
            a1, nxt1 = spec_accept(akey, drafts[:, 0], d_probs, t_probs)
            a, nxt = a1[None], nxt1[None]
        else:
            # accepted drafts are exactly the target's own greedy tokens
            g = jnp.argmax(vlg, -1).astype(jnp.int32)         # [B, K+1]
            agree = (drafts.T == g[:, :K]).astype(jnp.int32)
            a = jnp.sum(jnp.cumprod(agree, axis=1), axis=1)   # [B] 0..K
            nxt = g[rows, a]                                  # [B]
        # writing the full K+1 window is safe: slots past a+1 are
        # provisional and overwritten by the next round's window; finished
        # rows park their writes in the [N, N+K] slack (outside the
        # returned [:, :N] slice)
        col0 = jnp.minimum(done, N)
        out = out.at[rows[:, None],
                     col0[:, None] + jnp.arange(K + 1)[None]].set(window)
        active = done < N
        adv = jnp.where(active, a + 1, 0)
        lens = lens + adv            # per-row O(1) undo: frontier reset
        tcache = dataclasses.replace(tcache, length=jnp.max(lens))
        dcache = dataclasses.replace(dcache, length=jnp.max(lens))
        cur = jnp.where(active, nxt, cur)
        return (done + adv, cur, out, tcache, dcache, lens, fwds + 1, rng)

    done, _, out, _, _, _, fwds, _ = lax.while_loop(
        cond, body,
        (done0, cur, out0, tcache, dcache, lens0, jnp.int32(1), key0))
    return out[:, :N], fwds
