#!/usr/bin/env python3
"""Compile-count regression report: BENCH_COMPILE.json.

Runs the tiny CPU fixtures — a short train loop on the real
``DeepSpeedEngine`` and a multi-request serving session through the real
``ServingGateway`` — under a ``CompileWatch``
(``deepspeed_tpu/utils/compile_watch.py``), then writes per-program
compile counts and compile seconds.  The committed artifact makes compile
regressions diffable per PR, the same way ``BENCH_SERVE.json`` tracks
serving throughput: a program showing 2 compiles where the baseline shows
1 means a shape/dtype leak into a supposedly stable program.

Usage:
    python scripts/compile_report.py [--train-steps 3] [--warmup 1]
                                     [--requests 8] [--slots 3]
                                     [--out BENCH_COMPILE.json]

Exit codes: 0 zero post-warmup recompiles in both fixtures; 1 any
recompile (the report is still written, with the offending programs and
their arg-shape signatures).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _programs_block(registry) -> dict:
    secs = registry.compile_seconds()
    return {name: {"compiles": count,
                   "compile_s": round(secs.get(name, 0.0), 4)}
            for name, count in sorted(registry.counts().items())}


def _recompile_rows(events) -> list:
    return [{"program": e.program, "registry": e.registry,
             "count": e.count, "shapes": e.shapes,
             "compile_s": round(e.seconds, 4)} for e in events]


def run_train(args) -> dict:
    """Short train loop on the tiny GPT: warmup steps compile the step
    programs, steady steps must not compile anything."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.runtime.model import from_gpt
    from deepspeed_tpu.utils.compile_watch import CompileWatch

    cfg = gpt.GPTConfig(vocab_size=256, max_seq_len=64, n_layer=2, n_head=4,
                        d_model=64, dtype=jnp.float32, vocab_round_to=128)
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=from_gpt(cfg),
        config={"train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": 1,
                "steps_per_print": 1000,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-3}},
                "zero_optimization": {"stage": 0}},
        rng=jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    def batch(i):
        return {"tokens": rng.integers(0, 256, size=(2, 17)).astype(np.int32)}

    with CompileWatch(engine.compile_registry) as watch:
        for i in range(args.warmup):
            engine.forward(batch(i))
            engine.backward()
            engine.step()
        watch.mark_warm()
        for i in range(args.train_steps):
            engine.forward(batch(args.warmup + i))
            engine.backward()
            engine.step()
        recompiles = watch.recompiles
    return {
        "warmup_steps": args.warmup,
        "steady_steps": args.train_steps,
        "programs": _programs_block(engine.compile_registry),
        "steady_recompiles": _recompile_rows(recompiles),
        "host_syncs": engine.compile_registry.host_syncs(),
    }


def run_serving(args) -> dict:
    """Heterogeneous requests through a small gateway; serving programs
    are shape-stable by construction, so every program must compile at
    most once, ever."""
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt

    cfg = gpt.GPTConfig(vocab_size=256, max_seq_len=128, n_layer=2, n_head=4,
                        d_model=64, dtype=jnp.float32, vocab_round_to=128)
    params = gpt.init(cfg, jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(model=(cfg, params),
                                          config={"dtype": "float32"})
    gw = engine.serve(config={"slots": args.slots, "max_len": 64,
                              "prefill_chunk": 8})
    rng = np.random.default_rng(1)
    handles = []
    for i in range(args.requests):
        prompt = rng.integers(1, 256,
                              (int(rng.integers(3, 20)),)).astype(np.int32)
        handles.append(gw.submit(prompt,
                                 max_new_tokens=int(rng.integers(2, 10)),
                                 do_sample=bool(i % 2), temperature=0.9,
                                 seed=i))
    for h in handles:
        h.result(timeout=300.0)
    snap = gw.snapshot()
    registry = gw._batcher.registry
    events = [e for e in registry.events if e.count > 1]
    gw.shutdown()
    return {
        "requests": args.requests,
        "slots": args.slots,
        "programs": _programs_block(registry),
        "steady_recompiles": _recompile_rows(events),
        "host_syncs": registry.host_syncs(),
        "metrics": {"recompiles": snap["recompiles"],
                    "host_syncs": snap["host_syncs"],
                    "tokens_out": snap["tokens_out"]},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--train-steps", type=int, default=3,
                    help="steady-state steps after warmup")
    ap.add_argument("--warmup", type=int, default=1,
                    help="each step program compiles once, in the first step")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--out", default="BENCH_COMPILE.json")
    args = ap.parse_args(argv)

    train = run_train(args)
    serving = run_serving(args)
    result = {"train": train, "serving": serving}
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, args.out)

    bad = train["steady_recompiles"] + serving["steady_recompiles"]
    n_train = sum(v["compiles"] for v in train["programs"].values())
    n_serve = sum(v["compiles"] for v in serving["programs"].values())
    print(f"wrote {args.out}:")
    print(f"  train    {len(train['programs'])} programs, "
          f"{n_train} compiles, {len(train['steady_recompiles'])} "
          "post-warmup")
    print(f"  serving  {len(serving['programs'])} programs, "
          f"{n_serve} compiles, {len(serving['steady_recompiles'])} "
          "post-warmup")
    for row in bad:
        print(f"  RECOMPILE {row['registry']}/{row['program']} "
              f"count={row['count']} shapes=[{row['shapes']}]",
              file=sys.stderr)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
