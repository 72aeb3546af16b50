#!/usr/bin/env python3
"""Quantized-collective regression report: BENCH_COMM.json.

Runs the tiny CPU grad-collapse fixture — a 2-slice
(``ParallelDims(dcn=2)``, 2 virtual CPU devices) train run per collapse
mode (fp32 ``mean``, ``int8``, ``int4``, ``onebit``) plus the
``zero_int8`` row (dp=2, ``zero_optimization.quantized_collectives``) —
and records, per mode:

- logical vs wire bytes per boundary collapse and the compression ratio
  (single-sourced from ``runtime/comm/quantized.py`` accounting — the
  same numbers the engine streams as ``comm.*`` metrics);
- collapse wall time from the ``comm.reduce`` span aggregates;
- the loss trajectory and its divergence from the fp32-mean run;
- post-warmup recompiles (the compile-discipline gate).

Exit 1 (unless ``--no-gate``) on: compression ratio below the advertised
floor (int8 >= 3.5x, int4 >= 7x) or regressed vs the committed baseline,
loss parity beyond the documented tolerance, or any steady-state
recompile — the ``BENCH_COMPILE.json``/``BENCH_TELEMETRY.json`` gate
pattern applied to the comm hot path (docs/performance.md "Quantized
collectives").

Usage:
    python scripts/comm_bench.py [--steps 4] [--warmup 3]
                                 [--modes none,int8,int4,onebit,zero_int8]
                                 [--out BENCH_COMM.json] [--no-gate]

Prints one JSON summary line to stdout (one row, for a caller that
collects them); human-readable detail goes to stderr.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepspeed_tpu.utils.platform import force_cpu_platform  # noqa: E402

# 2 devices: dp=1 x dcn=2 (this jax's XLA can't partition the
# partial-manual collapse with auto axes > 1 — see tests/unit/comm/
# test_collective_matrix.py); persistent cache off per conftest caveat
force_cpu_platform(n_devices=2, persistent_cache=False)

import numpy as np  # noqa: E402

#: documented per-mode final-loss divergence tolerance vs fp32 mean on
#: this fixture (docs/performance.md "Quantized collectives")
LOSS_TOL = {"none": 0.0, "int8": 0.02, "int4": 0.08, "onebit": 0.35,
            "zero_int8": 0.02}

#: advertised wire-compression floors on the grad collapse
RATIO_FLOOR = {"none": 1.0, "int8": 3.5, "int4": 7.0, "onebit": 8.0,
               "zero_int8": 3.5}

#: allowed relative ratio slack vs the committed baseline
RATIO_REGRESSION_TOL = 0.02

ALL_MODES = ("none", "int8", "int4", "onebit", "zero_int8")


def _engine_for(mode: str):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models import gpt
    from deepspeed_tpu.parallel.mesh import (ParallelDims, initialize_mesh,
                                             reset_mesh_manager)
    from deepspeed_tpu.runtime.model import from_gpt

    cfg = gpt.GPTConfig(vocab_size=256, max_seq_len=64, n_layer=2, n_head=4,
                        d_model=64, dtype=jnp.float32, vocab_round_to=128)
    reset_mesh_manager()
    ds = {"train_micro_batch_size_per_gpu": 4,
          "gradient_accumulation_steps": 1,
          "optimizer": {"type": "Adam", "params": {"lr": 3e-3}},
          "zero_optimization": {"stage": 1},
          "telemetry": {"enabled": True, "spans": {"enabled": True},
                        "metrics": {"enabled": False}},
          "steps_per_print": 1 << 30}
    if mode == "zero_int8":
        mm = initialize_mesh(ParallelDims(dp=2))
        ds["zero_optimization"] = {"stage": 2,
                                   "quantized_collectives": "int8",
                                   "quantized_block": 512}
    else:
        mm = initialize_mesh(ParallelDims(dp=1, dcn=2))
        if mode != "none":
            ds["dcn"] = {"grad_compression": mode,
                         "compression_block": 512}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=from_gpt(cfg), config=ds, mesh_manager=mm,
        rng=jax.random.PRNGKey(0))
    return engine


def run_mode(mode: str, steps: int, warmup: int) -> dict:
    import jax
    from deepspeed_tpu.telemetry.spans import SpanName
    from deepspeed_tpu.utils.compile_watch import CompileWatch

    engine = _engine_for(mode)
    rng = np.random.default_rng(0)
    losses = []
    with CompileWatch(engine.compile_registry) as watch:
        for i in range(warmup + steps):
            if i == warmup:
                watch.mark_warm()
                # steady-state wall numbers: drop warmup spans (compiles)
                engine.tracer.clear()
            batch = {"tokens": rng.integers(
                0, 256, size=(8, 65)).astype(np.int32)}
            loss = engine.forward(batch)
            engine.backward()
            engine.step()
            losses.append(float(jax.device_get(loss)))
        recompiles = [
            {"program": e.program, "count": e.count, "shapes": e.shapes}
            for e in watch.recompiles]
    agg = engine.tracer.aggregates().get(SpanName.COMM_REDUCE,
                                         {"count": 0, "total_s": 0.0})
    logical = engine._collapse_logical_bytes
    wire = engine._collapse_wire_bytes
    return {
        "losses": [round(x, 6) for x in losses],
        "final_loss": round(losses[-1], 6),
        "logical_bytes_per_collapse": logical,
        "wire_bytes_per_collapse": wire,
        "compression_ratio": round(logical / wire, 4),
        "collapse_count": agg["count"],
        "collapse_wall_ms_mean": round(
            1e3 * agg["total_s"] / agg["count"], 4) if agg["count"] else None,
        "span_inventory": engine.tracer.span_inventory(),
        "steady_recompiles": recompiles,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--steps", type=int, default=4,
                    help="steady-state steps after warmup")
    ap.add_argument("--warmup", type=int, default=4)
    ap.add_argument("--modes", default=",".join(ALL_MODES),
                    help="comma-separated subset of "
                         f"{','.join(ALL_MODES)}")
    ap.add_argument("--out", default="BENCH_COMM.json")
    ap.add_argument("--no-gate", action="store_true",
                    help="record only; never exit 1 (sweep rows)")
    args = ap.parse_args(argv)
    modes = [m.strip() for m in args.modes.split(",") if m.strip()]
    bad = [m for m in modes if m not in ALL_MODES]
    if bad:
        ap.error(f"unknown modes {bad}; want a subset of {ALL_MODES}")

    results = {}
    for mode in modes:
        results[mode] = run_mode(mode, args.steps, args.warmup)
        r = results[mode]
        print(f"[comm_bench] {mode}: ratio={r['compression_ratio']}x "
              f"collapse={r['collapse_wall_ms_mean']}ms "
              f"final_loss={r['final_loss']} "
              f"recompiles={len(r['steady_recompiles'])}", file=sys.stderr)

    problems = []
    base_final = results.get("none", {}).get("final_loss")
    for mode, r in results.items():
        if r["compression_ratio"] < RATIO_FLOOR[mode]:
            problems.append(
                f"{mode}: compression ratio {r['compression_ratio']} below "
                f"floor {RATIO_FLOOR[mode]}")
        if r["steady_recompiles"]:
            problems.append(
                f"{mode}: {len(r['steady_recompiles'])} steady-state "
                f"recompile(s): {r['steady_recompiles']}")
        if not all(np.isfinite(r["losses"])):
            problems.append(f"{mode}: non-finite loss")
        if base_final is not None and mode != "none":
            div = abs(r["final_loss"] - base_final)
            r["final_loss_divergence"] = round(div, 6)
            if div > LOSS_TOL[mode]:
                problems.append(
                    f"{mode}: loss divergence {div:.4f} beyond tolerance "
                    f"{LOSS_TOL[mode]}")

    # ratio regression vs the committed artifact (the BENCH_SERVE pattern)
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                committed = json.load(f).get("modes", {})
        except (OSError, ValueError):
            committed = {}
        for mode, r in results.items():
            old = committed.get(mode, {}).get("compression_ratio")
            if old and r["compression_ratio"] < \
                    old * (1 - RATIO_REGRESSION_TOL):
                problems.append(
                    f"{mode}: compression ratio regressed "
                    f"{old} -> {r['compression_ratio']}")

    result = {
        "config": {"steps": args.steps, "warmup": args.warmup,
                   "block": 512, "loss_tol": LOSS_TOL,
                   "ratio_floor": RATIO_FLOOR},
        "modes": results,
        "problems": problems,
    }
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, args.out)

    summary = {"bench": "comm",
               "modes": {m: {"ratio": r["compression_ratio"],
                             "collapse_ms": r["collapse_wall_ms_mean"],
                             "final_loss": r["final_loss"]}
                         for m, r in results.items()},
               "problems": len(problems)}
    print(json.dumps(summary))
    for p in problems:
        print(f"[comm_bench] PROBLEM: {p}", file=sys.stderr)
    if args.no_gate:
        return 0
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
