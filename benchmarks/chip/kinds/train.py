"""A training job: ``deepspeed_tpu.initialize`` -> ``train_batch_fused``.

The job file gives the sequence length, the micro-batch per chip, the ZeRO
stage, the parallel layout, the remat policy and the optimizer.  Every step
takes a fresh batch drawn from ``--seed`` by a host thread that works while
the device runs the step before.
"""

from __future__ import annotations

import gc
import queue
import threading
import time

import numpy as np

from ..builders import resolve
from ..harness import Context, Span, TraceSlice, log
from ..reference import compare


def _batches(ctx: Context, rows: int, width: int, vocab: int, stop):
    """Seeded batches from a daemon thread, two ahead of the step."""
    q: "queue.Queue" = queue.Queue(maxsize=2)
    rng = np.random.default_rng(ctx.seed)

    def fill():
        while not stop.is_set():
            batch = {"tokens": rng.integers(
                0, vocab, size=(rows, width)).astype(np.int32)}
            while not stop.is_set():
                try:
                    q.put(batch, timeout=0.1)
                    break
                except queue.Full:
                    pass

    thread = threading.Thread(target=fill, daemon=True, name="bench-batches")
    thread.start()
    return q, thread


def run(ctx: Context) -> None:
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.parallel.mesh import (DP_GROUP, ParallelDims,
                                             initialize_mesh)
    from deepspeed_tpu.runtime.model import ModelSpec
    from deepspeed_tpu.utils.compile_watch import CompileWatch

    job = ctx.cell.traffic
    seq = int(job["seq_len"])
    micro = int(job["micro_batch"])
    cfg = ctx.build_model_config(
        max_seq_len=seq, dtype=jnp.bfloat16, remat=bool(job["remat"]),
        remat_policy=job["remat_policy"])
    mm = initialize_mesh(ParallelDims(**job["parallel"]),
                         devices=ctx.devices)
    ds = {"train_micro_batch_size_per_gpu": micro,
          "gradient_accumulation_steps": 1,
          "steps_per_print": 1 << 30,
          "optimizer": job["optimizer"],
          "zero_optimization": {"stage": int(job["zero_stage"])},
          "bf16": {"enabled": True},
          "tensor_parallel": {"enabled": mm.tp_world_size > 1,
                              "size": mm.tp_world_size}}
    if ctx.trace:
        # the engine's tracer records train.step and its children only in
        # the traced run: the end-to-end run pays for no instrumentation
        ds["telemetry"] = {"enabled": True, "spans": {"enabled": True},
                           "metrics": {"enabled": False}}
    module = resolve(ctx.cell.config["train_module"])(cfg)
    if not isinstance(module, ModelSpec):
        raise TypeError(f"{ctx.cell.config['train_module']} returned "
                        f"{type(module).__name__}, not a ModelSpec")
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=module, config=ds, mesh_manager=mm, rng=ctx.seed_key())
    jax.block_until_ready(engine.state)
    ctx.phase("weights_and_engine")

    rows = micro * mm.dp_world_size
    tokens_per_step = rows * seq
    stop = threading.Event()
    batches, feeder = _batches(ctx, rows, seq + 1, cfg.vocab_size, stop)
    watch = CompileWatch(engine.compile_registry).open()
    losses = []
    try:
        for _ in range(int(job["warmup_steps"])):
            losses.append(float(jax.block_until_ready(
                engine.train_batch_fused(batches.get()))))
        watch.mark_warm()
        engine.tracer.clear()
        ctx.phase("warmup")

        slice_ = TraceSlice(ctx) if ctx.trace else None
        first = int(job["trace_first_step"])
        last = first + int(job["trace_steps"])
        gc.collect()
        gc.freeze()
        gc.disable()
        t_open = ctx.open_window()
        step_end = []
        while True:
            n = len(step_end)
            if slice_ and n == first:
                slice_.start()
            t0 = time.monotonic()
            loss = jax.block_until_ready(
                engine.train_batch_fused(batches.get()))
            t1 = time.monotonic()
            ctx.spans.append(Span("bench.train_step", t0, t1 - t0, "main"))
            step_end.append(t1)
            losses.append(float(loss))
            if slice_ and n + 1 == last:
                slice_.stop()
            traced_now = slice_ and first <= n + 1 < last
            if t1 - t_open >= ctx.seconds and not traced_now:
                break
        ctx.close_window()
        gc.enable()
    finally:
        stop.set()
        feeder.join(timeout=5)

    elapsed = step_end[-1] - t_open
    # steps under the profiler run slower: the end-to-end run has none
    ctx.scalars["steps"] = len(step_end)
    ctx.scalars["tokens_per_s_per_chip"] = (
        len(step_end) * tokens_per_step / elapsed / len(ctx.devices))
    ctx.scalars["tokens_per_step"] = tokens_per_step
    ctx.samples["step_ms"] = [
        (b - a) * 1e3 for a, b in zip([t_open] + step_end[:-1], step_end)]
    ctx.attempted, ctx.failed = len(step_end), 0
    ctx.checks["losses_finite"] = bool(np.isfinite(losses).all())
    ctx.checks["no_recompile"] = not watch.check()
    log("train", steps=len(step_end), elapsed_s=round(elapsed, 3),
        first_loss=round(losses[0], 4), last_loss=round(losses[-1], 4),
        compile_counts=engine.compile_counts())
    ctx.spans += [Span(r.name, r.t0, r.dur, r.thread, r.args)
                  for r in engine.tracer.spans()]
    if slice_:
        slice_.reduce()

    # the logits check, outside the window: the model code the step runs
    # (bf16, flash kernels, the engine's own sharding of the weights) against
    # the plain float32 reference on a few seeded sequences.  The weights are
    # drawn afresh from the seed, in the layout the engine keeps its own:
    # steps on random tokens pull every logit towards zero, so an error
    # relative to the largest logit of the *trained* weights grows with the
    # number of steps a window held (0.010 fresh, 0.016-0.029 after 30-67
    # steps; PERF.md 6) and would judge the window's length, not the code
    init = resolve(ctx.cell.config["init"])
    forward = resolve(ctx.cell.config["reference"]).forward
    n_seq, last_pos = int(job["check_sequences"]), int(job["check_last"])
    last_pos = min(last_pos, seq)
    check = np.random.default_rng(ctx.seed + 7).integers(
        0, cfg.vocab_size, size=(n_seq, seq)).astype(np.int32)
    check = jax.device_put(check, NamedSharding(
        mm.mesh, P(DP_GROUP if n_seq % mm.dp_world_size == 0 else None)))
    params = jax.jit(lambda key: init(cfg, key), out_shardings=(
        jax.tree_util.tree_map(lambda x: x.sharding,
                               engine.state["params"])))(ctx.seed_key())
    served = params
    if ctx.control.get("weights") == "int8":    # a negative control
        from ..reference.control import round_to_int8
        served = jax.jit(round_to_int8)(params)
    system = jax.jit(lambda p, t: engine.module.apply_fn(p, t)[
        :, seq - last_pos:, :cfg.vocab_size])(served, check)
    ref = jax.jit(lambda p, t: forward(
        ctx.cell.config, p, t, last_pos))(params, check)
    compare.record(ctx, compare.relative_error(system, ref),
                   compare.rms_error(system, ref), sequences=n_seq,
                   positions=last_pos)
