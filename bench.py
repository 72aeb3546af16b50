"""Benchmark entry: training throughput + MFU of one model on the attached TPU.

One model per call; prints ONE JSON line on success:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}

  python bench.py           GPT-2 350M, seq 1024 (samples/s/chip + MFU)
  python bench.py bert      BERT-large MLM, seq 128 (the reference's
                            headline: 272 samples/s on one V100,
                            docs/_tutorials/bert-pretraining.md:392)
  python bench.py offload   the largest GPT preset that trains on one chip
                            under ZeRO-2 + host-offloaded Adam

The reference publishes no GPT number that compares directly, so GPT's
``vs_baseline`` is measured MFU / 0.45, the north-star MFU target.

There is no CPU path: without a TPU, or on a ``device_kind`` the peaks
table does not list, the run fails and prints no metric.  A chip belongs to
one process at a time, so this process starts no child once it has touched
JAX; ``offload`` walks its ladder from a parent that never imports JAX, one
child per rung.  Uses the engine's fused train-batch path (gas micro-steps +
update in one jit).
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

#: peak dense bf16 FLOP/s of one chip, keyed by ``jax.Device.device_kind``.
#: A kind that is not listed is an error, not a default.
#: "TPU v5 lite" is v5e: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
#: bf16, 16 GB HBM at 819 GB/s).
PEAK_BF16_FLOPS = {"TPU v5 lite": 197e12}

#: micro-batch of the GPT-2 350M bench: the largest multiple of 8 whose
#: fused step the TPU compiler fits into 15.75 GiB of HBM
#: (``scripts/aot_train_step.py``; 32 is refused at 18.27 GiB)
GPT_MICRO_BATCH = 24
BERT_MICRO_BATCH = 384


def peak_bf16_flops(device_kind: str) -> float:
    if device_kind not in PEAK_BF16_FLOPS:
        raise RuntimeError(
            f"no peak FLOP/s on record for device_kind {device_kind!r}; add "
            f"it to bench.PEAK_BF16_FLOPS with its source "
            f"(known: {sorted(PEAK_BF16_FLOPS)})")
    return PEAK_BF16_FLOPS[device_kind]


def _emit(line: str) -> None:
    # flush: the offload parent harvests a killed child's pipe, which
    # would otherwise still hold block-buffered step lines
    print(line, flush=True)
    d = os.environ.get("BENCH_ARTIFACT_DIR")
    if d:   # keep every finished measurement, even if the run is cut later
        os.makedirs(d, exist_ok=True)
        with open(os.path.join(d, "bench_log.jsonl"), "a") as f:
            f.write(json.dumps({"ts": time.strftime("%Y-%m-%dT%H:%M:%S"),
                                "argv": sys.argv[1:],
                                "line": json.loads(line)}) + "\n")


# ZeRO-offload capability ladder: largest first.  Each rung runs in its
# own subprocess because one RESOURCE_EXHAUSTED poisons the TPU client
# for every later allocation in the same process (measured: after a 2.7B
# OOM even 350M mb=8 failed in-process, while the same config succeeds
# fresh).  accum="bf16" rides the 16-bit gradient accumulator
# (data_types.grad_accum_dtype) — at gas=1 the backward already produces
# bf16 grads, so accumulating in bf16 loses nothing and halves the
# dominant 4-bytes/param term.
_OFFLOAD_LADDER = [("gpt2-2.7b", 2, "bf16"), ("gpt2-2.7b", 1, "bf16"),
                   ("gpt2-1.3b", 2, "bf16"), ("gpt2-1.3b", 1, "bf16"),
                   ("gpt2-760m", 4, None), ("gpt2-350m", 8, None)]

_NO_TPU = "no TPU"   # what utils.platform.require_tpu says


def _bench_offload() -> None:
    """`python bench.py offload` (parent): the largest-fitting GPT preset
    under ZeRO + cpu offload_optimizer (BASELINE config #3 proxy on one
    chip; reference capability anchor docs/_tutorials/zero.md:29 — 1.5B
    ZeRO-1 on 8 V100s; one v5e hosting 1.3B+offload matches it per-chip).

    The parent never imports JAX, so it never holds the chip: it walks the
    ladder, one child at a time, and forwards the first success's line."""
    import subprocess

    deadline = time.monotonic() + float(
        os.environ.get("BENCH_OFFLOAD_DEADLINE_S", "520"))
    last_err = "ladder exhausted"
    for name, mb, accum in _OFFLOAD_LADDER:
        budget = deadline - time.monotonic()
        if budget < 45:
            last_err = f"deadline before trying {name} mb={mb}"
            break
        env = dict(os.environ)
        env["BENCH_OFFLOAD_ONE"] = f"{name}:{mb}:{accum or ''}"
        # orphan self-destruct: if this parent is killed, the child must
        # not outlive the budget holding the chip
        env["BENCH_CHILD_TTL"] = str(int(budget))
        try:
            r = subprocess.run([sys.executable, os.path.abspath(__file__),
                                "offload"], env=env, capture_output=True,
                               text=True, timeout=budget - 10)
        except subprocess.TimeoutExpired as te:
            # the child emits one line per completed step — harvest the
            # best finished measurement even from a deadline kill
            out = te.stdout or ""
            if isinstance(out, bytes):
                out = out.decode(errors="replace")
            lines = [ln for ln in out.splitlines() if '"metric"' in ln]
            if lines:
                sys.stderr.write(f"bench offload: {name} mb={mb} hit the "
                                 "deadline; keeping its last step line\n")
                print(lines[-1])
                return
            sys.stderr.write(f"bench offload: {name} mb={mb} timed out\n")
            last_err = f"{name} mb={mb} timed out"
            continue
        sys.stderr.write(r.stderr[-2000:])
        lines = [ln for ln in r.stdout.splitlines() if '"metric"' in ln]
        if r.returncode == 0 and lines:
            print(lines[-1])
            return
        last_err = (r.stderr or r.stdout).strip().splitlines()[-1][:300] \
            if (r.stderr or r.stdout).strip() else f"rc={r.returncode}"
        sys.stderr.write(f"bench offload: {name} mb={mb} failed "
                         f"(rc={r.returncode})\n")
        if _NO_TPU in last_err:
            break   # no rung can run without the chip
    sys.exit(f"bench offload: no offload config ran: {last_err}")


def _bench_offload_child(devices) -> None:
    """One ladder rung (env BENCH_OFFLOAD_ONE="name:mb:accum") in a fresh
    process."""
    import dataclasses
    import signal

    if os.environ.get("BENCH_CHILD_TTL"):
        signal.alarm(int(os.environ["BENCH_CHILD_TTL"]))

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.parallel.mesh import ParallelDims, initialize_mesh
    from deepspeed_tpu.runtime.model import from_gpt

    name, mb_s, accum = os.environ["BENCH_OFFLOAD_ONE"].split(":")
    mb, accum = int(mb_s), (accum or None)
    config = dataclasses.replace(gpt.PRESETS[name], max_seq_len=1024,
                                 dtype=jnp.bfloat16, remat=True)
    steps, warmup = 4, 1

    mm = initialize_mesh(ParallelDims(dp=-1))
    ds = {"train_micro_batch_size_per_gpu": mb,
          "gradient_accumulation_steps": 1,
          "steps_per_print": 1 << 30,
          "optimizer": {"type": "Adam",
                        "params": {"lr": 1e-4, "weight_decay": 0.01}},
          "zero_optimization": {"stage": 2,
                                "offload_optimizer": {"device": "cpu"}},
          "bf16": {"enabled": True}}
    if accum is not None:
        ds["data_types"] = {"grad_accum_dtype": accum}
    if name == "gpt2-2.7b":
        # 2.7B fits only with the strict one-leaf transient — the
        # pipelined window's second in-flight leaf (~1.7 GB) would OOM
        # (memory_model.offload_peak_bytes pins this)
        ds["zero_optimization"]["offload_optimizer"][
            "pipeline_transfers"] = False
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=from_gpt(config), config=ds, mesh_manager=mm,
        rng=jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {"tokens": rng.integers(
        0, config.vocab_size,
        size=(mb, config.max_seq_len + 1)).astype(np.int32)}
    warm_losses, losses = [], []
    for _ in range(warmup):
        warm_losses.append(float(engine.train_batch_fused(batch)))
    jax.block_until_ready(engine.state["params"])
    n_params = sum(int(np.prod(l.shape)) for l in
                   jax.tree_util.tree_leaves(engine.state["params"]))

    def emit(done, dt):
        # warmup losses count toward training-progress evidence (the
        # parent may harvest the line after one step)
        all_losses = warm_losses + losses
        _emit(json.dumps({
            "metric": "gpt_zero_offload_samples_per_sec_per_chip",
            "value": round(done * mb / dt, 3),
            "unit": "samples/s/chip",
            # capability metric: 1.0 when the 1.3B class trains on one
            # chip with a decreasing loss
            "vs_baseline": 1.0 if (n_params >= 1.2e9
                                   and all_losses[-1] < all_losses[0])
            else 0.0,
            "detail": {"model": name, "params_m": round(n_params / 1e6),
                       "micro_batch": mb, "seq_len": config.max_seq_len,
                       "platform": devices[0].platform,
                       "device_kind": devices[0].device_kind,
                       "losses": all_losses, "timed_steps": done,
                       "loss_decreasing": all_losses[-1] < all_losses[0],
                       "zero_stage": 2, "offload": "cpu",
                       "grad_accum_dtype": accum or "fp32"},
        }))

    # one line per completed step (last line wins): a parent that kills
    # this child on deadline still harvests the best finished measurement
    t0 = time.perf_counter()
    for i in range(steps):
        losses.append(float(engine.train_batch_fused(batch)))
        emit(i + 1, time.perf_counter() - t0)


def main() -> None:
    mode = sys.argv[1] if len(sys.argv) > 1 else "gpt"
    if mode not in ("gpt", "bert", "offload"):
        sys.exit(f"usage: python bench.py [bert|offload] (got {mode!r})")
    if mode == "offload" and not os.environ.get("BENCH_OFFLOAD_ONE"):
        return _bench_offload()  # parent: holds no device, spawns rungs

    from deepspeed_tpu.utils.platform import enable_compile_cache, require_tpu
    devices = require_tpu()
    peak_per_chip = peak_bf16_flops(devices[0].device_kind)
    enable_compile_cache()
    if mode == "offload":
        return _bench_offload_child(devices)

    import dataclasses

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models import bert, gpt
    from deepspeed_tpu.parallel.mesh import ParallelDims, initialize_mesh
    from deepspeed_tpu.runtime.model import from_gpt
    n_chips = len(devices)

    if mode == "bert":
        # remat + dense attention at seq 128 (short-seq dense beats the
        # streaming kernel)
        config = dataclasses.replace(bert.BERT_LARGE, max_seq_len=128,
                                     dtype=jnp.bfloat16, remat=True)
        if os.environ.get("BENCH_NO_REMAT") == "1":
            config = dataclasses.replace(config, remat=False)
        micro_batch, gas, steps, warmup = BERT_MICRO_BATCH, 1, 10, 1
        model_spec = bert.model_spec(config)
        flops_per_tok = bert.flops_per_token(config)
        metric = "bert_large_mlm_samples_per_sec_per_chip"
        baseline = 272.0  # samples/s on 1x V100 (reference headline)
    else:
        # 350M is the biggest preset whose full Adam state fits one
        # 16GB chip with batch to spare.  remat_policy attn_out saves
        # each block's flash o+lse and provably removes the backward's
        # fwd-kernel re-run (tests/unit/models/test_remat_policy.py pins
        # the HLO); override with BENCH_REMAT_POLICY=nothing for A/B rows
        config = dataclasses.replace(gpt.GPT2_350M, max_seq_len=1024,
                                     dtype=jnp.bfloat16, remat=True,
                                     remat_policy="attn_out")
        micro_batch, gas, steps, warmup = GPT_MICRO_BATCH, 1, 10, 1
        if os.environ.get("BENCH_DENSE_ATTN") == "1":
            # sweep knob: XLA's dense attention path — at head_dim 64
            # the flash kernel is VPU-bound (mask/exp swamp the K=64
            # matmuls), so MXU-friendly dense scores can win even at
            # seq 1024 when remat keeps the S^2 buffer transient
            config = dataclasses.replace(config, use_flash_attention=False)
        if os.environ.get("BENCH_NO_REMAT") == "1":
            # sweep knob: drop remat entirely — removes the extra
            # forward (~25% of executed flops) if the no-remat
            # activations fit at a micro-batch that still feeds MXU
            config = dataclasses.replace(config, remat=False,
                                         remat_policy="nothing")
        if os.environ.get("BENCH_GAS"):
            gas = int(os.environ["BENCH_GAS"])
        if os.environ.get("BENCH_LOSS_CHUNK"):
            # sweep knob: a cap on the positions a chunk of the loss head
            # holds (the head already runs in chunks that follow from the
            # bytes of its float32 logits: models/gpt.py::_loss_layout)
            config = dataclasses.replace(
                config, loss_chunk=int(os.environ["BENCH_LOSS_CHUNK"]))
        if os.environ.get("BENCH_REMAT_POLICY"):
            # sweep knob: "attn_out" saves each block's attention
            # output so the backward remat skips re-running the
            # VPU-bound attention forward; "dots" saves matmul outputs
            # (bigger memory, less recompute)
            config = dataclasses.replace(
                config, remat_policy=os.environ["BENCH_REMAT_POLICY"])
        model_spec = from_gpt(config)
        flops_per_tok = gpt.flops_per_token(config)
        metric = "gpt2_train_samples_per_sec_per_chip"
        baseline = None
    # sweep knob: one micro-batch per call.  A micro-batch that does not
    # fit fails the run; there is no ladder, because a retry would need a
    # fresh process while this one still holds the chip
    if os.environ.get("BENCH_MB"):
        micro_batch = int(os.environ["BENCH_MB"])

    seq = config.max_seq_len
    mm = initialize_mesh(ParallelDims(dp=-1))
    ds_config = {
        "train_micro_batch_size_per_gpu": micro_batch,
        "gradient_accumulation_steps": gas,
        "steps_per_print": 1 << 30,
        "optimizer": {"type": "Adam",
                      "params": {"lr": 1e-4, "weight_decay": 0.01}},
        "zero_optimization": {"stage": 2 if n_chips > 1 else 1},
        "bf16": {"enabled": True},
    }
    # sweep knob: a 16-bit accumulator halves the grad tree's HBM,
    # which can buy a bigger micro-batch (at gas=1 the backward's
    # grads are already bf16, so nothing is lost)
    if os.environ.get("BENCH_ACCUM_DTYPE"):
        ds_config["data_types"] = {
            "grad_accum_dtype": os.environ["BENCH_ACCUM_DTYPE"]}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model_spec, config=ds_config, mesh_manager=mm,
        rng=jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    global_batch = micro_batch * mm.dp_world_size * gas
    if mode == "bert":
        tokens = rng.integers(0, config.vocab_size,
                              size=(global_batch, seq)).astype(np.int32)
        labels = np.where(rng.random((global_batch, seq)) < 0.15,
                          tokens, -100)
        batch = {"tokens": tokens, "mlm_labels": labels.astype(np.int32)}
    else:
        batch = {"tokens": rng.integers(
            0, config.vocab_size,
            size=(global_batch, seq + 1)).astype(np.int32)}
    for _ in range(warmup):
        loss = engine.train_batch_fused(batch)
    jax.block_until_ready((loss, engine.state))

    # BENCH_TRACE=<dir>: capture an xplane profile of the timed steps
    # (stall attribution evidence); tracing adds overhead, so the trace
    # run's own number should not be compared against untraced rows
    trace_dir = os.environ.get("BENCH_TRACE")
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = engine.train_batch_fused(batch)
    jax.block_until_ready((loss, engine.state))
    dt = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()
        sys.stderr.write(f"bench: xplane trace in {trace_dir}\n")

    samples_per_sec = steps * global_batch / dt
    tokens_per_sec = samples_per_sec * seq
    mfu = tokens_per_sec * flops_per_tok / (peak_per_chip * n_chips)

    # vs_baseline: BERT compares samples/s directly against the reference's
    # published 272/V100; GPT (no published equivalent) reports MFU vs the
    # 0.45 north star
    if baseline is not None:
        vs = round((samples_per_sec / n_chips) / baseline, 4)
    else:
        vs = round(mfu / 0.45, 4)
    _emit(json.dumps({
        "metric": metric,
        "value": round(samples_per_sec / n_chips, 3),
        "unit": "samples/s/chip",
        "vs_baseline": vs,
        "detail": {
            "model": f"{config.n_layer}L-{config.d_model}d",
            "seq_len": seq,
            "micro_batch": micro_batch,
            "global_batch": global_batch,
            "n_chips": n_chips,
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "peak_bf16_flops_per_chip": peak_per_chip,
            "tokens_per_sec": round(tokens_per_sec, 1),
            "mfu": round(mfu, 4),
            "final_loss": float(loss),
            "compiles": engine.compile_counts(),
            "zero_stage": ds_config["zero_optimization"]["stage"],
            "grad_accum_dtype": os.environ.get("BENCH_ACCUM_DTYPE", "fp32"),
        },
    }))


if __name__ == "__main__":
    main()
