"""What the serving kinds share: the server built through the normal entry
points (``init_inference`` -> ``engine.serve`` -> ``ServingGateway``), its
warm-up, the spans of its tracer, and the logits check through the slot
path."""

from __future__ import annotations

from typing import List

import numpy as np

from ..builders import resolve
from ..harness import Context, Span, log
from ..loadgen import Request
from ..reference import compare


def build_server(ctx: Context):
    """``(engine, gateway)``: weights made on the device from the seed in
    one jitted call, in the type they are served in; only the programs this
    traffic uses are warmed."""
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.telemetry.spans import Tracer

    cfg = ctx.build_model_config(dtype=jnp.bfloat16)
    init = resolve(ctx.cell.config["init"])
    params = jax.jit(lambda key: init(cfg, key, jnp.bfloat16))(ctx.seed_key())
    jax.block_until_ready(params)
    ctx.phase("weights")
    ctx.reference_params = params
    engine = deepspeed_tpu.init_inference(
        model=(cfg, params),
        config={"dtype": "bfloat16", **ctx.control.get("inference", {})})
    serving = ctx.cell.traffic["serving"]
    # the program's tracer records serve.tick / serve.admit only in the
    # traced run: the end-to-end run pays for no instrumentation
    tracer = Tracer(enabled=ctx.trace, capacity=1 << 20, name="serving")
    gateway = engine.serve(config=serving, tracer=tracer)
    ctx.phase("engine_and_gateway")
    # one request through the gateway that crosses a chunk boundary warms
    # prefill, extend, the slot write, the tick and the release, and the
    # gateway's own small programs; nothing else is compiled
    chunk = int(serving["prefill_chunk"])
    warm = gateway.submit(np.zeros((chunk + 1,), np.int32), max_new_tokens=2)
    warm.result(timeout=1100)
    tracer.clear()
    ctx.phase("warmup")
    return engine, gateway


def submitter(gateway):
    def submit(req: Request):
        return gateway.submit(req.tokens, max_new_tokens=req.max_new_tokens)
    return submit


#: the traced slice ends this long before the window's close
_SLICE_ENDS_BEFORE_CLOSE_S = 0.5


def slice_of(ctx: Context):
    """``(start, length)`` of the traced slice in seconds of the window: its
    last ``trace_len_s`` seconds but half a second.  Stopping the profiler
    and writing its trace takes the host seconds, and a server at four
    fifths of its knee that falls behind in the middle of the window never
    catches up inside it (PERF.md 5), so the stop falls after the close."""
    length = float(ctx.cell.traffic["trace_len_s"])
    return ctx.seconds - length - _SLICE_ENDS_BEFORE_CLOSE_S, length


def harvest_spans(ctx: Context, gateway) -> None:
    for r in gateway.tracer.spans():
        ctx.spans.append(Span(r.name, r.t0, r.dur, r.thread, r.args))


def live_context_tokens(requests: List[Request]) -> float:
    """Cached tokens held by the requests decoding now."""
    return float(sum(len(r.tokens) + r.handle.tokens_out for r in requests
                     if r.handle is not None and r.handle.state == "decoding"))


def finish(ctx: Context, engine, gateway) -> None:
    """After the window: stop the scheduler, then check the logits."""
    snap = gateway.snapshot()
    ctx.checks["no_recompile"] = snap["recompiles"] == 0 and all(
        n <= 1 for n in snap["compile_counts"].values())
    gateway.shutdown(drain=False, timeout=60)
    log("serve", ticks=snap["ticks"], admitted=snap["admitted"],
        completed=snap["completed"], rejected=snap["rejected"],
        failed=snap["failed"], recompiles=snap["recompiles"],
        programs=sum(snap["compile_counts"].values()))
    check_logits(ctx, engine, gateway)


def check_logits(ctx: Context, engine, gateway) -> None:
    """Chunked prefill and then decode through the slot path against the
    plain reference's full forward pass, on logits: a few seeded prompts,
    a few greedy ticks, and the logits after the prefill and after every
    tick compared with the reference's at the same positions of prompt +
    reply.  The reference gets the weights the benchmark drew, not the
    engine's copy of them, so a precision lowered inside the engine shows."""
    import jax

    spec = ctx.cell.traffic["check"]
    ticks = int(spec["ticks"])
    max_len = int(ctx.cell.traffic["serving"]["max_len"])
    lens = [min(int(n), max_len - ticks - 1) for n in spec["prompt_lens"]]
    vocab = engine.model_config.vocab_size
    rng = np.random.default_rng(ctx.seed + 7)
    prompts = [rng.integers(0, vocab, n).astype(np.int32) for n in lens]
    # the program's public entry for its slot path's logits: the stopped
    # gateway's own compiled programs and slot cache
    replies, got = gateway.probe_logits(prompts, ticks)
    forward = resolve(ctx.cell.config["reference"]).forward
    reference = jax.jit(lambda prm, t: forward(
        ctx.cell.config, prm, t, ticks + 1))
    worst = rms = 0.0
    for p, reply, logits in zip(prompts, replies, got):
        full = np.concatenate([p, np.asarray(reply, np.int32)])[None]
        ref = np.asarray(reference(ctx.reference_params, full))[0]
        worst = max(worst, compare.relative_error(logits[:, :vocab], ref))
        rms = max(rms, compare.rms_error(logits[:, :vocab], ref))
    compare.record(ctx, worst, rms, prompts=lens, ticks=ticks)
