#!/usr/bin/env python3
"""Chip smoke: GPT-2 350M trains and serves end to end on one TPU chip.

The quickest proof that the system still starts on the chip.  One process,
one import of JAX, no CPU path: without a TPU it exits non-zero and prints
no result.  With no arguments it needs one chip and runs two phases at the
model's published widths (24 layers, d1024, 16 heads, 1024 tokens, bf16;
weights random from ``--seed``):

1. *train*: ``deepspeed_tpu.initialize`` -> ``engine.train_batch_fused``,
   one warm-up step and three timed steps on one repeated batch.
2. *serve*: ``deepspeed_tpu.init_inference`` -> ``engine.serve`` ->
   ``ServingGateway.submit``: eight requests of mixed length through four
   slots with paged KV, then a follow-up turn on each of two sessions that
   were parked to host memory; every greedy reply is compared with
   ``engine.generate`` on the same prompt.

``--chips 4`` runs, and only runs, the sharded phase and what it is compared
with: three ZeRO-3 steps over ``ParallelDims(dp=2, tp=2)`` on all four
chips of a host, against the same model, seed and global batch at ZeRO-0 on
one of them.

Each phase prints its set-up and compile seconds, step or request wall
times (smoke timings on the host's clock, not metrics), the device's peak
memory and its kernel check.  Any failed assertion or raised error ends the
run non-zero.  The last line of standard output is
``{"ok": true, "device": {"platform", "kind", "count"}}`` as JAX reports
the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt
from deepspeed_tpu.parallel.mesh import (MODEL_AXIS, ParallelDims,
                                         initialize_mesh, reset_mesh_manager)
from deepspeed_tpu.runtime.model import from_gpt
from deepspeed_tpu.utils.compile_watch import CompileWatch
from deepspeed_tpu.utils.platform import enable_compile_cache, require_tpu

#: training micro-batch on one chip: the largest multiple of 8 whose fused
#: step the TPU compiler fits into a v5e's 15.75 GiB (it plans 15.19 GiB at
#: 24 and refuses 26; ``scripts/aot_train_step.py --micro-batch N``)
MICRO_BATCH = 24
#: per-replica micro-batch of the four-chip phase (global batch 8, which
#: the one-chip comparison takes whole)
SHARDED_MICRO_BATCH = 4
#: two greedy chains may part where the reference's own top-2 logit margin
#: is under this many bf16 ulps (2^-8 relative) of its top logit: random
#: weights give nearly flat logits, and the serving path (chunked prefill,
#: cache kernels) rounds differently from the stateless one
MARGIN_ULPS = 4
#: losses of the sharded and the one-chip run agree to this relative bound
#: (one bf16 ulp: tensor parallelism reorders the bf16 reductions)
LOSS_RTOL = 2.0 ** -8

GPT2_350M = dataclasses.replace(gpt.GPT2_350M, max_seq_len=1024,
                                dtype=jnp.bfloat16)
PROMPT_LENS = (40, 700, 130, 16, 512, 257, 90, 333)
NEW_TOKENS = 32
FOLLOW_UP_LEN = 24


def _log(phase: str, **fields) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in fields.items()),
          flush=True)


def _peak_bytes(device):
    """Peak bytes of live buffers on ``device`` over the process so far.
    The TPU runtime does not count a running program's temporaries here:
    those are in the compiler's plan (``scripts/aot_train_step.py``)."""
    stats = device.memory_stats()   # None where the backend reports none
    return stats["peak_bytes_in_use"] if stats else None


def ds_config(micro_batch: int, zero_stage: int, tp: int = 1) -> dict:
    return {
        "train_micro_batch_size_per_gpu": micro_batch,
        "gradient_accumulation_steps": 1,
        "steps_per_print": 1 << 30,
        "optimizer": {"type": "Adam",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "zero_optimization": {"stage": zero_stage},
        "bf16": {"enabled": True},
        "tensor_parallel": {"enabled": tp > 1, "size": tp},
    }


def _train_steps(phase: str, cfg: gpt.GPTConfig, ds_config: dict, mm,
                 batch: dict, seed: int, n_steps: int = 3):
    """Engine through ``initialize``; one warm-up and ``n_steps`` fenced
    ``train_batch_fused`` steps on ``batch``.  Returns ``(engine, losses,
    compiled step text)``; asserts finite losses and no compile after the
    warm-up."""
    t0 = time.perf_counter()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=from_gpt(cfg), config=ds_config, mesh_manager=mm,
        rng=jax.random.PRNGKey(seed))
    jax.block_until_ready(engine.state)
    t1 = time.perf_counter()
    program, args = engine._fused_program(batch)
    text = program.lower(*args).compile().as_text()
    watch = CompileWatch(engine.compile_registry).open()
    warm = float(jax.block_until_ready(engine.train_batch_fused(batch)))
    watch.mark_warm()
    t2 = time.perf_counter()
    losses, step_s = [], []
    for _ in range(n_steps):
        t = time.perf_counter()
        losses.append(float(jax.block_until_ready(
            engine.train_batch_fused(batch))))
        step_s.append(round(time.perf_counter() - t, 4))
    watch.assert_no_recompiles(f"chip_smoke {phase}")
    assert all(np.isfinite([warm] + losses)), f"non-finite loss: {losses}"
    _log(phase, setup_s=round(t1 - t0, 1), compile_s=round(t2 - t1, 1),
         smoke_step_s=step_s, warmup_loss=round(warm, 4),
         losses=[round(x, 4) for x in losses],
         compiles=engine.compile_counts())
    return engine, losses, text


def _tokens(cfg: gpt.GPTConfig, rows: int, seed: int) -> dict:
    rng = np.random.default_rng(seed)
    return {"tokens": rng.integers(
        0, cfg.vocab_size,
        size=(rows, cfg.max_seq_len + 1)).astype(np.int32)}


def train_phase(cfg: gpt.GPTConfig, micro_batch: int, seed: int, device,
                kernels: bool) -> None:
    """Adam, ZeRO-1, bf16, remat saving the attention outputs (the
    configuration the ``gpt2m-train-s1024`` cell times) on ``device``."""
    cfg = dataclasses.replace(cfg, remat=True, remat_policy="attn_out")
    mm = initialize_mesh(ParallelDims(dp=1), devices=[device])
    _, losses, text = _train_steps(
        "train", cfg, ds_config(micro_batch, zero_stage=1), mm,
        _tokens(cfg, micro_batch, seed), seed)
    assert losses[-1] < losses[0], \
        f"loss did not fall on one repeated batch: {losses}"
    if kernels:
        assert "tpu_custom_call" in text, \
            "no Mosaic kernel in the compiled train step: attention ran " \
            "the dense reference, not the flash kernels"
    _log("train", micro_batch=micro_batch, seq_len=cfg.max_seq_len,
         flash_kernels_in_step=text.count("tpu_custom_call"),
         peak_bytes_in_use=_peak_bytes(device))


def _reference_replies(engine, prompts, new_tokens: int):
    """``engine.generate`` (the stateless path) on every prompt in one
    right-padded ragged batch, and the margin between its two largest
    logits at every reply position, in ulps of bf16 at the top logit."""
    vocab = engine.model_config.vocab_size
    lens = np.asarray([len(p) for p in prompts])
    padded = np.zeros((len(prompts), lens.max()), np.int32)
    for row, p in zip(padded, prompts):
        row[:len(p)] = p
    replies = np.asarray(engine.generate(padded, max_new_tokens=new_tokens,
                                         prompt_lens=lens))
    # teacher-force the reference's own sequences for their logits: the
    # logits at position p predict token p+1
    full = np.zeros((len(prompts), lens.max() + new_tokens), np.int32)
    for row, p, r in zip(full, prompts, replies):
        row[:len(p)] = p
        row[len(p):len(p) + new_tokens] = r
    top2 = np.asarray(jax.lax.top_k(
        engine.forward(full)[..., :vocab], 2)[0])
    at = (lens - 1)[:, None] + np.arange(new_tokens)[None]
    top2 = np.take_along_axis(top2, at[..., None], axis=1)
    ulp = np.abs(top2[..., 0]) * 2.0 ** -8
    return replies, (top2[..., 0] - top2[..., 1]) / ulp


def _agreed_prefix(got, want, margin_ulps) -> int:
    """Length of the prefix on which ``got`` must equal ``want``: up to
    the first position where the reference's margin is under the
    tolerance.  Raises if they differ inside it, or at the first token."""
    flat = np.flatnonzero(margin_ulps < MARGIN_ULPS)
    must = int(flat[0]) if flat.size else len(want)
    differ = np.flatnonzero(got != want)
    first = int(differ[0]) if differ.size else len(want)
    assert first >= max(must, 1), (
        f"reply parts from engine.generate at token {first}, where the "
        f"reference's margin is {margin_ulps[first]:.1f} bf16 ulps "
        f"(tolerance {MARGIN_ULPS}): got {got.tolist()} want "
        f"{want.tolist()}")
    return first


def serve_phase(cfg: gpt.GPTConfig, seed: int, kernels: bool, *,
                prompt_lens=PROMPT_LENS, new_tokens: int = NEW_TOKENS,
                follow_up_len: int = FOLLOW_UP_LEN, slots: int = 4,
                prefill_chunk: int = 128) -> None:
    """More requests than slots, prompts from 16 to 700 tokens, and two
    sessions whose finished turns are parked to host memory and re-admitted
    for a follow-up: queueing, admission, chunked prefill, paging, park and
    readmit, and ragged decode all run."""
    device = jax.devices()[0]
    t0 = time.perf_counter()
    engine = deepspeed_tpu.init_inference(
        model=(cfg, gpt.init(cfg, jax.random.PRNGKey(seed))),
        config={"dtype": "bfloat16"})
    gateway = engine.serve(config={
        "slots": slots, "max_len": cfg.max_seq_len,
        "prefill_chunk": prefill_chunk,
        # a watermark of one byte is permanent memory pressure: every
        # session that retires into the block pool is then parked to host
        # memory, which is where the follow-up turns have to find it
        "paging": {"enabled": True, "hbm_high_watermark": 1}})
    t1 = time.perf_counter()

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in prompt_lens]
    sessions = {0: "chat-a", len(prompts) - 1: "chat-b"}
    handles = [gateway.submit(p, max_new_tokens=new_tokens,
                              session_id=sessions.get(i))
               for i, p in enumerate(prompts)]
    replies = [h.result(timeout=900) for h in handles]
    deadline = time.monotonic() + 60
    while gateway.snapshot()["paging"]["sessions_ram"] < len(sessions):
        assert time.monotonic() < deadline, \
            f"sessions never parked: {gateway.snapshot()['paging']}"
        time.sleep(0.05)
    for i, sid in sessions.items():
        turn = rng.integers(0, cfg.vocab_size, (follow_up_len,)).astype(
            np.int32)
        prompts.append(np.concatenate([prompts[i], replies[i], turn]))
        handles.append(gateway.submit(prompts[-1], max_new_tokens=new_tokens,
                                      session_id=sid))
    replies += [h.result(timeout=900) for h in handles[len(replies):]]
    t2 = time.perf_counter()
    snap = gateway.snapshot()
    batcher = gateway._batcher
    tick_text = batcher._p["tick"].lower(
        engine.params, batcher.cache, batcher.lengths, batcher._last,
        batcher.keys, batcher.greedy, batcher.temp,
        batcher.active).compile().as_text()
    gateway.shutdown()

    assert snap["completed"] == len(prompts), snap
    assert snap["readmits"] == len(sessions) and snap["parked"] >= len(
        sessions), f"follow-up turns did not re-admit parked KV: {snap}"
    assert snap["recompiles"] == 0 and all(
        n <= 1 for n in snap["compile_counts"].values()), \
        f"a serving program compiled twice: {snap['compile_counts']}"
    if kernels:
        assert "tpu_custom_call" in tick_text, \
            "no Mosaic kernel in the decode tick: cached attention ran " \
            "the dense reference, not the decode kernel"

    want, margins = _reference_replies(engine, prompts, new_tokens)
    agreed = [_agreed_prefix(np.asarray(r), w, m)
              for r, w, m in zip(replies, want, margins)]
    t3 = time.perf_counter()
    _log("serve", setup_s=round(t1 - t0, 1),
         serve_wall_s=round(t2 - t1, 1), reference_s=round(t3 - t2, 1),
         smoke_request_s=[round(h.t_done - h.t_submit, 2) for h in handles],
         smoke_ttft_s=[round(h.ttft_s, 2) for h in handles])
    _log("serve", requests=len(prompts), completed=snap["completed"],
         readmits=snap["readmits"], parked=snap["parked"],
         ticks=snap["ticks"], recompiles=snap["recompiles"],
         compiled_programs=sum(snap["compile_counts"].values()),
         decode_kernels_in_tick=tick_text.count("tpu_custom_call"),
         peak_bytes_in_use=_peak_bytes(device))
    _log("serve", tokens_matched=f"{sum(agreed)}/{len(prompts) * new_tokens}",
         agreed_prefix=agreed, tolerance_bf16_ulps=MARGIN_ULPS)


def _devices_of(array) -> set:
    return {shard.device for shard in array.addressable_shards}


def sharded_phase(cfg: gpt.GPTConfig, micro_batch: int, seed: int, devices,
                  dims: ParallelDims, kernels: bool) -> None:
    """Three ZeRO-3 steps on a mesh of all ``devices`` against the same
    model, seed and global batch at ZeRO-0 on the first of them."""
    cfg = dataclasses.replace(cfg, remat=True, remat_policy="attn_out")
    mm = initialize_mesh(dims, devices=devices)
    batch = _tokens(cfg, micro_batch * mm.dp_world_size, seed)
    engine, sharded, text = _train_steps(
        "sharded", cfg, ds_config(micro_batch, 3, tp=mm.tp_world_size), mm,
        batch, seed)
    assert " all-gather" in text and (
        " reduce-scatter" in text or " all-reduce" in text), \
        "the compiled ZeRO-3 step has no parameter gather or gradient reduce"
    if kernels:
        assert "tpu_custom_call" in text, "no flash kernel in the sharded step"
    wqkv = engine.state["params"]["blocks"]["wqkv"]
    moment = next(leaf for leaf in jax.tree_util.tree_leaves(
        engine.state["opt_state"]) if leaf.shape == wqkv.shape)
    placed = {"wqkv": _devices_of(wqkv), "adam_moment": _devices_of(moment)}
    assert all(len(d) == len(devices) for d in placed.values()), \
        f"ZeRO-3 state is not spread over {len(devices)} devices: {placed}"
    if mm.tp_world_size > 1:
        assert MODEL_AXIS in str(wqkv.sharding.spec), wqkv.sharding.spec
    peaks = [_peak_bytes(d) for d in devices]
    assert all(p != 0 for p in peaks), f"an idle device: {peaks}"
    _log("sharded", mesh=dict(mm.mesh.shape), wqkv_spec=wqkv.sharding.spec,
         wqkv_shard=wqkv.addressable_shards[0].data.shape,
         shard_devices={k: len(v) for k, v in placed.items()},
         all_gather=text.count(" all-gather"),
         reduce_scatter=text.count(" reduce-scatter"),
         all_reduce=text.count(" all-reduce"),
         flash_kernels_in_step=text.count("tpu_custom_call"),
         peak_bytes_in_use=peaks)
    del engine, wqkv, moment
    gc.collect()

    one = initialize_mesh(ParallelDims(dp=1), devices=devices[:1])
    _, single, _ = _train_steps(
        "one-chip", cfg, ds_config(micro_batch * mm.dp_world_size, 0), one,
        batch, seed)
    gaps = [abs(a - b) / max(abs(a), abs(b)) for a, b in zip(sharded, single)]
    _log("sharded", zero3_losses=sharded, zero0_losses=single,
         relative_gaps=[f"{g:.2e}" for g in gaps], tolerance=LOSS_RTOL)
    assert all(g <= LOSS_RTOL for g in gaps), \
        f"ZeRO-3 dp x tp losses {sharded} left ZeRO-0 losses {single}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded phase, on a four-chip host")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and batches")
    args = ap.parse_args(argv)
    devices = require_tpu()
    if len(devices) < args.chips:
        raise RuntimeError(f"--chips {args.chips} needs {args.chips} chips; "
                           f"jax.devices() reports {len(devices)}")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    _log("chip_smoke", **device, seed=args.seed,
         compile_cache=enable_compile_cache())
    t0 = time.perf_counter()
    cfg = GPT2_350M
    if args.chips == 4:
        sharded_phase(cfg, SHARDED_MICRO_BATCH, args.seed, devices[:4],
                      ParallelDims(dp=2, tp=2), kernels=True)
    else:
        train_phase(cfg, MICRO_BATCH, args.seed, devices[0], kernels=True)
        reset_mesh_manager()
        gc.collect()   # the engine's state must be gone before serving
        serve_phase(cfg, args.seed, kernels=True)
    _log("chip_smoke", wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
